"""Pallas TPU flash-decode over a PAGED KV cache (vLLM-style block pool).

Same online-softmax walk as ``repro.kernels.decode_attention``, but the KV
cache is a physical block pool ``[num_blocks, block_size, kv_heads, hd]``
addressed through a per-row block table ``[B, n_logical]`` instead of a
contiguous ``[B, S, ...]`` arena.  The table rides in as a scalar-prefetch
operand (SMEM before the body runs), so the k/v ``BlockSpec`` index maps can
dereference it: grid step ``(b, h, j)`` DMAs physical block ``table[b, j]``
straight from the pool — the virtual sequence is never materialized in HBM.

  * grid = (batch, head_blocks, n_logical); last axis sequential, carrying
    the (m, l, acc) scratch across the row's block walk.
  * the pool is viewed as ``[num_blocks, block_size, KVH * hd]`` and read in
    the dense kernel's lane blocks of ``hp`` heads against block-diagonally
    packed queries (see ``decode_attention`` for why a one-head block does
    not tile on TPU).
  * unallocated logical blocks point at the pool's trash row; their
    positions are ``>= lengths[b]`` so the whole tile is skipped (masked and
    ``pl.when``-gated, same as padded tail blocks in the dense kernel).
  * one pool block per grid step: ``block_size`` rows are the tile's
    second-minor dim, which equals the pool's, so any block size tiles;
    larger blocks stream fewer, longer DMAs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import (
    attend_tile,
    emit,
    heads_per_block,
    init_state,
    pack_queries,
    unpack_outputs,
)


def _paged_decode_kernel(
    table_ref,  # SMEM [B, n_logical] i32 (scalar prefetch)
    len_ref,  # SMEM [B] i32 (scalar prefetch)
    q_ref,  # [R, W] packed queries of this lane block
    k_ref,  # [block_size, W] — physical block table_ref[b, j]
    v_ref,  # [block_size, W]
    o_ref,  # [R, W]
    m_scr,  # [R, 128] f32
    l_scr,  # [R, 128] f32
    acc_scr,  # [R, W] f32
    *,
    sm_scale: float,
    block_size: int,
    num_logical: int,
):
    b = pl.program_id(0)
    j = pl.program_id(2)
    length = len_ref[b]

    @pl.when(j == 0)
    def _init():
        init_state(m_scr, l_scr, acc_scr)

    k_start = j * block_size

    @pl.when(k_start < length)
    def _compute():
        attend_tile(
            q_ref[...], k_ref[...], v_ref[...], k_start, length,
            m_scr, l_scr, acc_scr, sm_scale,
        )

    @pl.when(j == num_logical - 1)
    def _emit():
        emit(o_ref, l_scr, acc_scr)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def paged_decode_attention(
    q: jnp.ndarray,  # [B, Hq, hd]
    k_pool: jnp.ndarray,  # [NB, bs, KVH, hd]
    v_pool: jnp.ndarray,  # [NB, bs, KVH, hd]
    table: jnp.ndarray,  # [B, n_logical] i32
    lengths: jnp.ndarray,  # [B] i32 — valid prefix of each row
    *,
    sm_scale: float | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    B, Hq, hd = q.shape
    NB, bs, KVH = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    if Hq % KVH != 0:
        raise ValueError(f"q heads {Hq} not a multiple of kv heads {KVH}")
    n_logical = table.shape[1]
    if sm_scale is None:
        sm_scale = float(1.0 / np.sqrt(hd))
    hp = heads_per_block(KVH, hd)
    W = hp * hd
    R = hp * (Hq // KVH)

    kernel = functools.partial(
        _paged_decode_kernel,
        sm_scale=sm_scale,
        block_size=bs,
        num_logical=n_logical,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block table + lengths land in SMEM up front
        grid=(B, KVH // hp, n_logical),
        in_specs=[
            pl.BlockSpec(
                (None, None, R, W), lambda b, h, j, tab, lens: (b, h, 0, 0)
            ),
            pl.BlockSpec(
                (None, bs, W), lambda b, h, j, tab, lens: (tab[b, j], 0, h)
            ),
            pl.BlockSpec(
                (None, bs, W), lambda b, h, j, tab, lens: (tab[b, j], 0, h)
            ),
        ],
        out_specs=pl.BlockSpec(
            (None, None, R, W), lambda b, h, j, tab, lens: (b, h, 0, 0)
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
        name="paged_decode_attention",
    )(
        table.astype(jnp.int32),
        lengths.astype(jnp.int32),
        pack_queries(q, KVH, hp),
        k_pool.reshape(NB, bs, KVH * hd),
        v_pool.reshape(NB, bs, KVH * hd),
    )
    return unpack_outputs(out, hp, hd)
