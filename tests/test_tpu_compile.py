"""The serving path's Pallas kernels compile for a TPU v5e at stablelm-1.6b's
published widths.

Interpret mode runs the kernel bodies but never meets the TPU compiler's
tiling and VMEM rules.  These tests lower each main-path kernel for one
described (not attached) v5e chip and compile it ahead of time, so a block
shape the chip would refuse fails here.  The topology is described inside a
fixture: only the worker that runs this file loads the TPU compiler.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models import model as model_lib
from repro.serving import steps
from test_serving_weights import f32_convert_shapes

CFG = get_config("stablelm-1.6b")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def pallas():
    """Route kernels.ops to the compiled kernels, as on a TPU backend."""
    ops.set_backend("pallas")
    yield
    ops.set_backend("auto")


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo  # the Pallas kernel, not a fallback


@pytest.mark.parametrize("B", [8, 200])
def test_exit_confidence_compiles(one_chip, pallas, B):
    # the monolithic steps hand the head over as the f32 master weight
    _compile(
        ops.exit_confidence,
        one_chip,
        ((B, CFG.d_model), CFG.dtype),
        ((CFG.d_model, CFG.vocab_size), jnp.float32),
    )


@pytest.mark.parametrize("kv_heads", [CFG.num_kv_heads, CFG.num_heads // 4])
def test_decode_attention_compiles(one_chip, pallas, kv_heads):
    B, S, hd = 8, 1024, CFG.head_dim
    _compile(
        ops.decode_attention,
        one_chip,
        ((B, CFG.num_heads, hd), CFG.dtype),
        ((B, S, kv_heads, hd), jnp.bfloat16),
        ((B, S, kv_heads, hd), jnp.bfloat16),
        ((B,), jnp.int32),
    )


def test_paged_decode_attention_compiles(one_chip, pallas):
    B, bs, n_logical, hd = 8, 16, 64, CFG.head_dim
    nb = B * n_logical + 1  # + the trash block
    kvh = CFG.num_kv_heads
    _compile(
        lambda q, kp, vp, tab, lens: ops.paged_decode_attention(
            q, kp, vp, tab, lens, seq_len=n_logical * bs
        ),
        one_chip,
        ((B, CFG.num_heads, hd), CFG.dtype),
        ((nb, bs, kvh, hd), jnp.bfloat16),
        ((nb, bs, kvh, hd), jnp.bfloat16),
        ((B, n_logical), jnp.int32),
        ((B,), jnp.int32),
    )


def test_stage_programs_cast_no_weight(one_chip, pallas):
    """From the serving copy, stage decode and the exit head compile with no
    cast of a weight; from the f32 tree, with a cast of every matrix."""
    B, max_len = 8, 400

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
        )

    params = model_lib.abstract_params(CFG)
    weights = jax.eval_shape(lambda p: model_lib.serving_params(p, CFG), params)
    x = jax.ShapeDtypeStruct((B, 1, CFG.d_model), CFG.dtype, sharding=one_chip)
    rows = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    store = on_chip(jax.eval_shape(
        lambda: model_lib.init_stage_slot_caches(CFG, 1, 2 * B + 1, max_len)
    ))
    matrices = {
        leaf.shape
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            {"stage": params["stages"][0], "lm_head": params["lm_head"]}
        )[0]
        if model_lib.read_through_cast(path)
    }

    def cast(tree):
        tree = on_chip(tree)
        decode = steps.make_stage_decode(CFG, 1).lower(tree, x, store, rows)
        head = steps.make_exit_head_step(CFG, CFG.exit_stages[0]).lower(tree, x)
        return set().union(
            *(f32_convert_shapes(p.compile().as_text()) for p in (decode, head))
        ) & matrices

    assert cast(params) == matrices
    assert cast(weights) == set()
