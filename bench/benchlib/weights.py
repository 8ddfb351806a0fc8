"""Random weights from the seed, made by the benchmark, on the device.

The program is handed these; the reference makes the same tree again from
the same seed after the program's state is gone, so it reads nothing the
program made.  One jitted call fills every leaf of the program's parameter
tree, in the type the program serves from (float32 master weights):

  * matrices: normal / sqrt(fan_in), fan_in the second-to-last axis;
  * the token embedding: standard normal;
  * norm scales: 1 + 0.1 * normal, norm and projection biases 0.1 * normal,
    so the bias and affine paths carry weight in the comparison.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A key that tells apart every seed up to 2**64 (``jax.random.key``
    keeps only the low 32 bits without x64)."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _leaf(key, path, shape, dtype):
    name = str(getattr(path[-1], "key", path[-1]))
    if name == "scale":
        return (1.0 + 0.1 * jax.random.normal(key, shape)).astype(dtype)
    if name == "bias" or name.startswith("b_"):
        return (0.1 * jax.random.normal(key, shape)).astype(dtype)
    if name == "embed":
        return jax.random.normal(key, shape, dtype)
    return (jax.random.normal(key, shape) / jnp.sqrt(shape[-2])).astype(dtype)


def abstract_tree(cfg):
    from repro.models import model as model_lib

    return jax.eval_shape(lambda: model_lib.init_params(jax.random.key(0), cfg))


def make_params(cfg, seed: int, abstract=None):
    """The parameter tree of ``cfg`` filled from ``seed``, on the device."""
    abstract = abstract if abstract is not None else abstract_tree(cfg)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    @jax.jit
    def fill(key):
        out = []
        for i, (path, a) in enumerate(leaves):
            out.append(_leaf(jax.random.fold_in(key, i), path, a.shape, a.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return fill(seed_key(seed))
