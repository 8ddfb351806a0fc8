"""Shared primitive layers: norms, embeddings, RoPE, FFNs.

Conventions:
  * params are nested dicts of jnp arrays; leaf names drive sharding rules
    (see repro.sharding.specs.param_spec).
  * every ``apply``-style function takes activations in compute dtype
    (bf16 by default) and params in whatever dtype they are held in:
    training holds f32 master copies, serving holds the matrices in the
    compute dtype (``model.serving_params``); casting happens at the matmul
    boundary either way.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Params = dict[str, Any]


def truncated_normal_init(key, shape, scale: float, dtype=jnp.float32):
    """He-style truncated normal, stddev = scale / sqrt(fan_in)."""
    fan_in = shape[0] if len(shape) >= 2 else max(shape[-1], 1)
    std = scale / np.sqrt(fan_in)
    return std * jax.random.truncated_normal(key, -3.0, 3.0, shape, dtype)


def dense_init(key, d_in: int, d_out: int, *, scale: float = 1.0, dtype=jnp.float32):
    return truncated_normal_init(key, (d_in, d_out), scale, dtype)


# the dtype ``matmul`` feeds the MXU
COMPUTE_DTYPE = jnp.bfloat16


def matmul(x: jnp.ndarray, w: jnp.ndarray, *, compute_dtype=COMPUTE_DTYPE) -> jnp.ndarray:
    """x @ w with both operands cast to the compute dtype (MXU-friendly)."""
    return jnp.matmul(x.astype(compute_dtype), w.astype(compute_dtype))


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_init(d: int) -> Params:
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm(params: Params, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """RMSNorm in f32 for stability, output back in x.dtype."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * params["scale"]
    return y.astype(dtype)


def layernorm_init(d: int) -> Params:
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


def layernorm(params: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps) * params["scale"] + params["bias"]
    return y.astype(dtype)


def norm_init(kind: str, d: int) -> Params:
    if kind == "rmsnorm":
        return rmsnorm_init(d)
    if kind == "layernorm":
        return layernorm_init(d)
    raise ValueError(f"unknown norm {kind!r}")


def apply_norm(kind: str, params: Params, x: jnp.ndarray) -> jnp.ndarray:
    return rmsnorm(params, x) if kind == "rmsnorm" else layernorm(params, x)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------

_ACTS = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "relu": jax.nn.relu,
}


def activation(name: str):
    return _ACTS[name]


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    """Inverse frequencies for even head dims (f32, [head_dim // 2])."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta**exponent)


def apply_rope(
    x: jnp.ndarray,  # [..., seq, heads, head_dim]
    positions: jnp.ndarray,  # [..., seq]
    theta: float = 1e4,
) -> jnp.ndarray:
    """Standard rotate-half RoPE over the last dim, position-indexed."""
    head_dim = x.shape[-1]
    inv = rope_frequencies(head_dim, theta)
    angles = positions[..., :, None].astype(jnp.float32) * inv  # [..., seq, hd/2]
    cos = jnp.cos(angles)[..., :, None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embeddings
# ---------------------------------------------------------------------------


def embedding_init(key, vocab: int, d: int) -> Params:
    return {"embed": truncated_normal_init(key, (vocab, d), 1.0)}


def embed(params: Params, tokens: jnp.ndarray, compute_dtype=jnp.bfloat16) -> jnp.ndarray:
    """The tokens' rows, gathered in the table's dtype, then cast: only the
    gathered rows are converted, so the table is served as it is held."""
    return params["embed"][tokens].astype(compute_dtype)


# ---------------------------------------------------------------------------
# Feed-forward blocks
# ---------------------------------------------------------------------------


def glu_ffn_init(key, d: int, d_ff: int) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, d, d_ff),
        "w_up": dense_init(k2, d, d_ff),
        "w_down": dense_init(k3, d_ff, d),
    }


def glu_ffn(params: Params, x: jnp.ndarray, act: str = "silu") -> jnp.ndarray:
    """Gated FFN (SwiGLU et al.): down(act(gate(x)) * up(x))."""
    g = activation(act)(matmul(x, params["w_gate"]))
    u = matmul(x, params["w_up"])
    return matmul(g * u, params["w_down"])


def mlp_ffn_init(key, d: int, d_ff: int) -> Params:
    k1, k2 = jax.random.split(key, 2)
    return {"w_up": dense_init(k1, d, d_ff), "w_down": dense_init(k2, d_ff, d)}


def mlp_ffn(params: Params, x: jnp.ndarray, act: str = "gelu") -> jnp.ndarray:
    return matmul(activation(act)(matmul(x, params["w_up"])), params["w_down"])


# ---------------------------------------------------------------------------
# Loop strategy: scan (compact HLO) vs unrolled (exact cost_analysis)
# ---------------------------------------------------------------------------
# XLA's HLO cost analysis visits a while-loop body ONCE regardless of trip
# count, so the roofline measurement path unrolls every counted loop.  The
# production path keeps lax.scan/map (small HLO, fast compiles).  sLSTM's
# per-timestep recurrence is excluded (trip count == seq_len) and corrected
# analytically in repro.roofline.corrections.

UNROLL_LOOPS = False


def set_unroll(flag: bool) -> None:
    global UNROLL_LOOPS
    UNROLL_LOOPS = bool(flag)


def loop_map(fn, xs):
    """lax.map, or an unrolled python loop when UNROLL_LOOPS is set."""
    if not UNROLL_LOOPS:
        return jax.lax.map(fn, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    ys = [fn(jax.tree.map(lambda a: a[i], xs)) for i in range(n)]
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *ys)


def loop_scan(body, carry, xs, length: int | None = None):
    """lax.scan, or an unrolled python loop when UNROLL_LOOPS is set."""
    if not UNROLL_LOOPS:
        return jax.lax.scan(body, carry, xs, length=length)
    n = length if xs is None else jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        x_i = None if xs is None else jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    if all(y is None for y in ys):
        return carry, None
    return carry, jax.tree.map(lambda *leaves: jnp.stack(leaves), *ys)
