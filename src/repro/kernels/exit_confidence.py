"""Pallas TPU fused early-exit confidence head — the paper-specific hot spot.

The exit branch b_h needs only two scalars per row to apply the paper's
threshold test (conf >= c_h): the top-1 softmax probability and its argmax.
The naive path materializes [batch, vocab] logits in HBM (for qwen2.5-32b:
128 x 152064 x 4B = 78 MB written + read back per exit stage per decode
step).  This kernel streams vocab tiles of the LM head through VMEM,
matmuls on the MXU, and keeps a running (max, sum-exp, argmax) — the
logits never leave VMEM.

  grid = (batch_blocks, vocab_blocks); vocab axis sequential, carrying
  (m, l, argmax) scratch.  conf = 1 / sum_v exp(logit_v - max) because the
  top-1 term contributes exp(0).

The head block is streamed in the dtype it arrives in, so callers pass it in
the activation dtype (``ops.exit_confidence`` casts): a bf16 [d, block_v]
tile is half the VMEM of an f32 one, which is what lets block_v=1024 fit at
d=2048.  Outputs are lane-dense [B, 128] tiles (every lane holds the row's
value) so the batch axis tiles in multiples of 8 rows for any B; the wrapper
returns lane 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _exit_kernel(
    h_ref,  # [block_b, d]
    w_ref,  # [d, block_v]
    conf_ref,  # [block_b, 128] (lane-broadcast)
    idx_ref,  # [block_b, 128] (lane-broadcast)
    m_scr,  # [block_b, 128] f32 running max
    l_scr,  # [block_b, 128] f32 running sum-exp
    a_scr,  # [block_b, 128] i32 running argmax
    *,
    block_v: int,
    vocab: int,
    num_v_blocks: int,
):
    iv = pl.program_id(1)

    @pl.when(iv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        a_scr[...] = jnp.zeros_like(a_scr)

    logits = jax.lax.dot_general(
        h_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [block_b, block_v]
    bb = logits.shape[0]
    col = iv * block_v + jax.lax.broadcasted_iota(jnp.int32, (bb, block_v), 1)
    valid = col < vocab
    logits = jnp.where(valid, logits, NEG_INF)

    block_max = jnp.max(logits, axis=1, keepdims=True)  # [bb, 1]
    block_arg = iv * block_v + jnp.argmax(logits, axis=1, keepdims=True).astype(
        jnp.int32
    )

    m_prev = m_scr[:, :1]
    better = block_max > m_prev
    m_new = jnp.maximum(m_prev, block_max)
    p_sum = jnp.sum(jnp.exp(logits - m_new), axis=1, keepdims=True)
    l_scr[...] = jnp.broadcast_to(
        l_scr[:, :1] * jnp.exp(m_prev - m_new) + p_sum, l_scr.shape
    )
    a_scr[...] = jnp.broadcast_to(
        jnp.where(better, block_arg, a_scr[:, :1]), a_scr.shape
    )
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(iv == num_v_blocks - 1)
    def _emit():
        l = l_scr[...]
        conf_ref[...] = 1.0 / jnp.where(l > 0.0, l, 1.0)
        idx_ref[...] = a_scr[...]


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_v", "interpret")
)
def exit_confidence(
    h: jnp.ndarray,  # [B, d]
    w: jnp.ndarray,  # [d, V]
    *,
    block_b: int = 128,
    block_v: int = 1024,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (top1 softmax prob [B] f32, argmax [B] i32)."""
    B, d = h.shape
    V = w.shape[1]
    # one batch block when B fits (a block equal to the full dim always
    # tiles); otherwise block_b rows, a multiple of 8 sublanes on TPU
    block_b = min(block_b, B)
    block_v = min(block_v, V)
    b_pad = (-B) % block_b
    v_pad = (-V) % block_v
    if b_pad:
        h = jnp.pad(h, ((0, b_pad), (0, 0)))
    if v_pad:
        w = jnp.pad(w, ((0, 0), (0, v_pad)))
    nb = (B + b_pad) // block_b
    nv = (V + v_pad) // block_v

    kernel = functools.partial(
        _exit_kernel, block_v=block_v, vocab=V, num_v_blocks=nv
    )
    conf, idx = pl.pallas_call(
        kernel,
        grid=(nb, nv),
        in_specs=[
            pl.BlockSpec((block_b, d), lambda ib, iv: (ib, 0)),
            pl.BlockSpec((d, block_v), lambda ib, iv: (0, iv)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, 128), lambda ib, iv: (ib, 0)),
            pl.BlockSpec((block_b, 128), lambda ib, iv: (ib, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B + b_pad, 128), jnp.float32),
            jax.ShapeDtypeStruct((B + b_pad, 128), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_b, 128), jnp.float32),
            pltpu.VMEM((block_b, 128), jnp.float32),
            pltpu.VMEM((block_b, 128), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="exit_confidence",
    )(h, w)
    return conf[:B, 0], idx[:B, 0]
