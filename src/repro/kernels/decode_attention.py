"""Pallas TPU flash-decode: one query token vs. a long KV cache.

Decode attention is memory-bound (arithmetic intensity ~1 FLOP/byte: each
cached (k, v) element is read once per step), so the kernel's job is to
stream the KV cache HBM -> VMEM at full bandwidth while keeping the online
softmax state in registers/VMEM:

  * grid = (batch, head_blocks, kv_blocks); last axis sequential, carrying
    (m, l, acc) scratch across the cache walk.
  * the cache is viewed as ``[B, S, KVH * hd]`` (a free reshape) and walked
    in lane blocks of ``hp`` kv heads, ``hp * hd`` lanes wide — 128 lanes
    when ``hd`` divides 128 (two heads for hd=64).  TPU tiles the last two
    block dims in (8, 128) units, so a block holding ONE head of a
    ``[.., KVH, hd]`` array (a 1 in the second-minor dim) does not compile;
    a lane block of the flattened heads does, at any G.
  * the queries of a lane block are packed block-diagonally
    (``pack_queries``): row (j, g) holds query head g of kv head j in lanes
    [j*hd, (j+1)*hd) and zeros elsewhere, so one [rows, W] x [W, block_k]
    matmul scores every head of the tile, the zero lanes adding exact
    zeros.  All G query heads of a kv head share each streamed KV block.
  * per-row validity comes from ``lengths`` (scalar-prefetched into SMEM),
    so ragged batches share one compiled kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def heads_per_block(kvh: int, hd: int) -> int:
    """KV heads per lane block: 128 lanes' worth when ``hd`` divides 128,
    one head when ``hd`` is a multiple of 128, else every head (a block that
    spans the whole lane dim tiles whatever its width)."""
    hp = max(1, 128 // hd)
    if kvh % hp or (hp * hd) % 128:
        hp = kvh
    return hp


def pack_queries(q: jnp.ndarray, kvh: int, hp: int) -> jnp.ndarray:
    """q [B, Hq, hd] -> block-diagonal [B, KVH // hp, hp * G, hp * hd]."""
    B, Hq, hd = q.shape
    G = Hq // kvh
    q5 = q.reshape(B, kvh // hp, hp, G, hd)
    eye = jnp.eye(hp, dtype=q.dtype)
    qp = q5[:, :, :, :, None, :] * eye[None, None, :, None, :, None]
    return qp.reshape(B, kvh // hp, hp * G, hp * hd)


def unpack_outputs(o: jnp.ndarray, hp: int, hd: int) -> jnp.ndarray:
    """Inverse of ``pack_queries`` on the kernel output: keep each row's
    own head lanes.  [B, n, hp * G, hp * hd] -> [B, Hq, hd]."""
    B, n, R, _ = o.shape
    G = R // hp
    o6 = o.reshape(B, n, hp, G, hp, hd)
    j = jnp.arange(hp)
    own = o6[:, :, j, :, j, :]  # [hp, B, n, G, hd]
    return jnp.moveaxis(own, 0, 2).reshape(B, n * hp * G, hd)


def attend_tile(q, k, v, k_start, length, m_scr, l_scr, acc_scr, sm_scale: float):
    """One online-softmax step of packed queries ``q`` [R, W] over a cache
    tile ``k``/``v`` [T, W] holding positions ``k_start + [0, T)``."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [R, T]
    s = s * sm_scale
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = k_pos < length
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = jnp.broadcast_to(
        l_scr[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True), l_scr.shape
    )
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [R, W]
    acc_scr[...] = acc_scr[...] * alpha + pv
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)


def init_state(m_scr, l_scr, acc_scr) -> None:
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def emit(o_ref, l_scr, acc_scr) -> None:
    l = l_scr[:, :1]
    o_ref[...] = (acc_scr[...] / jnp.where(l > 0.0, l, 1.0)).astype(o_ref.dtype)


def _decode_kernel(
    len_ref,  # SMEM [B] i32 (scalar prefetch)
    q_ref,  # [R, W] packed queries of this lane block
    k_ref,  # [block_k, W]
    v_ref,  # [block_k, W]
    o_ref,  # [R, W]
    m_scr,  # [R, 128] f32
    l_scr,  # [R, 128] f32
    acc_scr,  # [R, W] f32
    *,
    sm_scale: float,
    block_k: int,
    num_kv_blocks: int,
):
    b = pl.program_id(0)
    ik = pl.program_id(2)
    length = len_ref[b]

    @pl.when(ik == 0)
    def _init():
        init_state(m_scr, l_scr, acc_scr)

    k_start = ik * block_k

    @pl.when(k_start < length)
    def _compute():
        attend_tile(
            q_ref[...], k_ref[...], v_ref[...], k_start, length,
            m_scr, l_scr, acc_scr, sm_scale,
        )

    @pl.when(ik == num_kv_blocks - 1)
    def _emit():
        emit(o_ref, l_scr, acc_scr)


@functools.partial(
    jax.jit, static_argnames=("block_k", "sm_scale", "interpret")
)
def decode_attention(
    q: jnp.ndarray,  # [B, Hq, hd]
    k: jnp.ndarray,  # [B, S, KVH, hd]
    v: jnp.ndarray,  # [B, S, KVH, hd]
    lengths: jnp.ndarray,  # [B] i32 — valid prefix of each cache row
    *,
    block_k: int = 512,
    sm_scale: float | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    B, Hq, hd = q.shape
    S, KVH = k.shape[1], k.shape[2]
    if Hq % KVH != 0:
        raise ValueError(f"q heads {Hq} not a multiple of kv heads {KVH}")
    if sm_scale is None:
        sm_scale = float(1.0 / np.sqrt(hd))
    hp = heads_per_block(KVH, hd)
    W = hp * hd
    R = hp * (Hq // KVH)

    block_k = min(block_k, S)
    k_pad = (-S) % block_k
    k = k.reshape(B, S, KVH * hd)
    v = v.reshape(B, S, KVH * hd)
    if k_pad:
        k = jnp.pad(k, ((0, 0), (0, k_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, k_pad), (0, 0)))
    nk = (S + k_pad) // block_k

    kernel = functools.partial(
        _decode_kernel, sm_scale=sm_scale, block_k=block_k, num_kv_blocks=nk
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # lengths land in SMEM up front
        grid=(B, KVH // hp, nk),
        in_specs=[
            pl.BlockSpec((None, None, R, W), lambda b, h, ik, lens: (b, h, 0, 0)),
            pl.BlockSpec((None, block_k, W), lambda b, h, ik, lens: (b, ik, h)),
            pl.BlockSpec((None, block_k, W), lambda b, h, ik, lens: (b, ik, h)),
        ],
        out_specs=pl.BlockSpec(
            (None, None, R, W), lambda b, h, ik, lens: (b, h, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((R, 128), jnp.float32),
            pltpu.VMEM((R, 128), jnp.float32),
            pltpu.VMEM((R, W), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH // hp, R, W), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="decode_attention",
    )(lengths.astype(jnp.int32), pack_queries(q, KVH, hp), k, v)
    return unpack_outputs(out, hp, hd)
