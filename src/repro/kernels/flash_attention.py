"""Pallas TPU flash attention (prefill): online softmax over KV blocks.

TPU-native design (not a CUDA port):
  * grid = (batch, q_heads, q_blocks, kv_blocks); the LAST axis is the
    sequential ("arbitrary") one, so the (m, l, acc) running state lives in
    VMEM scratch across kv blocks — the TPU analogue of a CUDA thread-block
    loop, but driven by the Mosaic pipeline, with q/k/v tiles DMA'd
    HBM -> VMEM ahead of compute.
  * Q tile (block_q x head_dim) stays resident in VMEM for a whole row of
    kv blocks; K/V tiles stream through.  Matmul dims are MXU-aligned
    (block sizes multiples of 128, head_dim 128 for every assigned arch).
  * GQA folds into the index map: q head h reads kv head h // groups — no
    KV replication in HBM.
  * Causal + sliding-window masking skip *entire* kv blocks via pl.when
    (the block-diagonal walk), and mask within the two boundary blocks.

Forward-only: the serving data plane (prefill) is where the paper's delay
model spends its alpha_h; training uses the XLA chunked path which autodiffs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref,  # [1, block_q, 1, hd]
    k_ref,  # [1, block_k, 1, hd]
    v_ref,  # [1, block_k, 1, hd]
    o_ref,  # [1, block_q, 1, hd]
    m_scr,  # [block_q, 128] f32
    l_scr,  # [block_q, 128] f32
    acc_scr,  # [block_q, hd] f32
    *,
    sm_scale: float,
    causal: bool,
    window: int | None,
    block_q: int,
    block_k: int,
    kv_len: int,
    num_kv_blocks: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q
    k_start = ik * block_k

    # --- whole-block skip test (static against traced block indices) -------
    live = k_start < kv_len  # padded tail blocks
    if causal:
        live = jnp.logical_and(live, k_start <= q_start + block_q - 1)
    if window is not None:
        live = jnp.logical_and(live, k_start + block_k - 1 > q_start - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, :, 0, :]  # [block_q, hd]
        k = k_ref[0, :, 0, :]  # [block_k, hd]
        v = v_ref[0, :, 0, :]
        s = jax.lax.dot_general(
            q,
            k,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_q, block_k]
        s = s * sm_scale

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = k_pos < kv_len
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        if window is not None:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]  # [block_q, 1]
        block_max = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, block_max)
        # exp shift; fully-masked rows keep m == NEG_INF and p == 0
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)

        l_new = l_scr[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype),
            v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_q, hd]
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == num_kv_blocks - 1)
    def _emit():
        l = l_scr[:, :1]
        out = acc_scr[...] / jnp.where(l > 0.0, l, 1.0)
        o_ref[0, :, 0, :] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal",
        "window",
        "block_q",
        "block_k",
        "sm_scale",
        "interpret",
    ),
)
def flash_attention(
    q: jnp.ndarray,  # [B, Sq, Hq, hd]
    k: jnp.ndarray,  # [B, Sk, KVH, hd]
    v: jnp.ndarray,  # [B, Sk, KVH, hd]
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 128,
    block_k: int = 128,
    sm_scale: float | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    B, Sq, Hq, hd = q.shape
    Sk, KVH = k.shape[1], k.shape[2]
    if Hq % KVH != 0:
        raise ValueError(f"q heads {Hq} not a multiple of kv heads {KVH}")
    groups = Hq // KVH
    if sm_scale is None:
        sm_scale = float(1.0 / np.sqrt(hd))

    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    q_pad = (-Sq) % block_q
    k_pad = (-Sk) % block_k
    if q_pad:
        q = jnp.pad(q, ((0, 0), (0, q_pad), (0, 0), (0, 0)))
    if k_pad:
        k = jnp.pad(k, ((0, 0), (0, k_pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, k_pad), (0, 0), (0, 0)))
    nq = (Sq + q_pad) // block_q
    nk = (Sk + k_pad) // block_k

    kernel = functools.partial(
        _flash_kernel,
        sm_scale=sm_scale,
        causal=causal,
        window=window,
        block_q=block_q,
        block_k=block_k,
        kv_len=Sk,
        num_kv_blocks=nk,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, 1, hd), lambda b, h, iq, ik: (b, iq, h, 0)),
            pl.BlockSpec(
                (1, block_k, 1, hd), lambda b, h, iq, ik, g=groups: (b, ik, h // g, 0)
            ),
            pl.BlockSpec(
                (1, block_k, 1, hd), lambda b, h, iq, ik, g=groups: (b, ik, h // g, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, block_q, 1, hd), lambda b, h, iq, ik: (b, iq, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Sq + q_pad, Hq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
    if q_pad:
        out = out[:, :Sq]
    return out
