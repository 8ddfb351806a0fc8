"""Staged decoder with early-exit heads — the data plane the paper's
control plane (DTO-EE) schedules.

A model is ``num_stages`` pipeline stages; each stage scans over repeated
block *periods* (see ArchConfig.period).  Early-exit branches (paper: b_h)
hang off the stages in ``cfg.exit_stages``: RMSNorm + the shared LM head;
confidence = top-1 softmax probability, exactly what DTO-EE thresholds.

Three entry points per architecture:
  * loss_fn        — training forward with deep supervision over exits
  * prefill        — full-sequence forward that also builds decode caches
  * decode_step    — one token against the caches, returning per-exit
                     (confidence, argmax) so the serving engine can apply
                     the paper's thresholds C
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import attention, layers, moe, ssm
from repro.models.layers import Params
from repro.sharding import constrain

# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------


def _block_init(key, kind: str, cfg: ArchConfig) -> Params:
    d = cfg.d_model
    if kind in ("attn", "dense_attn", "moe_attn"):
        ka, kf = jax.random.split(key)
        attn_p = (
            attention.mla_init(ka, cfg.mla)
            if cfg.mla is not None
            else attention.gqa_init(ka, cfg.attn_dims())
        )
        p: Params = {
            "norm1": layers.norm_init(cfg.norm, d),
            "attn": attn_p,
            "norm2": layers.norm_init(cfg.norm, d),
        }
        if kind == "moe_attn":
            p["moe"] = moe.moe_init(kf, cfg.moe)
        elif cfg.ffn == "mlp":
            p["ffn"] = layers.mlp_ffn_init(kf, d, cfg.d_ff)
        else:
            p["ffn"] = layers.glu_ffn_init(kf, d, cfg.d_ff)
        return p
    if kind == "mamba":
        return {"norm": layers.norm_init(cfg.norm, d), "mamba": ssm.mamba_init(key, cfg.mamba)}
    if kind == "mlstm":
        return {"norm": layers.norm_init(cfg.norm, d), "mlstm": ssm.mlstm_init(key, cfg.xlstm)}
    if kind == "slstm":
        return {"norm": layers.norm_init(cfg.norm, d), "slstm": ssm.slstm_init(key, cfg.xlstm)}
    raise ValueError(kind)


def init_params(key, cfg: ArchConfig) -> Params:
    keys = jax.random.split(key, 3 + cfg.num_periods)
    params: Params = {}
    if cfg.frontend == "tokens":
        params["embed"] = layers.embedding_init(keys[0], cfg.vocab_size, cfg.d_model)
    params["lm_head"] = layers.dense_init(keys[1], cfg.d_model, cfg.vocab_size)
    params["final_norm"] = layers.norm_init(cfg.norm, cfg.d_model)
    params["exit_norms"] = {
        f"exit_{h}": layers.norm_init(cfg.norm, cfg.d_model) for h in cfg.exit_stages
    }

    stages = []
    period_keys = iter(keys[3:])
    for n_periods in cfg.stage_periods():
        stage_key = next(period_keys)
        blocks = []
        for i, kind in enumerate(cfg.period):
            pk = jax.random.fold_in(stage_key, i)
            stacked = jax.vmap(lambda k: _block_init(k, kind, cfg))(
                jax.random.split(pk, n_periods)
            )
            blocks.append(stacked)
        stages.append({"blocks": tuple(blocks)})
    params["stages"] = stages
    return params


# Leaves the programs read in float32, by name: norm scales and biases, the
# Mamba decay and step parameters, the xLSTM gate biases, sLSTM's recurrent
# gates, and the token embedding (its rows are gathered, then cast).  Every
# other leaf reaches the arithmetic only through a cast to
# ``layers.COMPUTE_DTYPE``: the matrices ``layers.matmul`` and the MoE/MLA
# einsums read, the head, the attention and conv biases.
# tests/test_serving_weights.py holds the rule to the serving programs'
# jaxprs for every registered architecture.
F32_LEAVES = frozenset(
    {"scale", "bias", "a_log", "dt_bias", "if_bias", "gate_bias", "r_gates", "embed"}
)


def read_through_cast(path) -> bool:
    """Whether the leaf at ``path`` (a ``tree_flatten_with_path`` key path)
    is read only through a cast to ``layers.COMPUTE_DTYPE``."""
    return str(getattr(path[-1], "key", path[-1])) not in F32_LEAVES


def serving_params(params: Params, cfg: ArchConfig) -> Params:
    """``params`` with every leaf the programs read only through a cast held
    in that dtype, cast leaf by leaf; the rest as given.  Same operands for
    the same arithmetic, without the programs casting the weights on every
    call.  Where ``cfg.dtype`` is not the compute dtype the casts do not all
    land on ``cfg.dtype``, and the tree is returned as it is."""
    if jnp.dtype(cfg.dtype) != jnp.dtype(layers.COMPUTE_DTYPE):
        return params
    return jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.asarray(a).astype(cfg.dtype) if read_through_cast(path) else a,
        params,
    )


def abstract_params(cfg: ArchConfig) -> Params:
    """ShapeDtypeStruct pytree — no allocation (dry-run path)."""
    return jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))


def count_params(cfg: ArchConfig, active_only: bool = False) -> int:
    p = abstract_params(cfg)
    total = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(p))
    if active_only and cfg.moe is not None:
        n_moe = sum(1 for k in cfg.period if k == "moe_attn") * cfg.num_periods
        inactive_per_block = (
            (cfg.moe.num_experts - cfg.moe.top_k) * 3 * cfg.moe.d_model * cfg.moe.d_ff_expert
        )
        total -= n_moe * inactive_per_block
    return total


# ---------------------------------------------------------------------------
# Cache construction
# ---------------------------------------------------------------------------


def _block_cache(kind: str, cfg: ArchConfig, batch: int, max_len: int) -> Params | None:
    if kind in ("attn", "dense_attn", "moe_attn"):
        if cfg.mla is not None:
            return attention.make_mla_cache(batch, max_len, cfg.mla)
        dims = cfg.attn_dims()
        if dims.sliding_window is not None and dims.sliding_window < max_len:
            return attention.make_window_cache(batch, dims)
        return attention.make_kv_cache(batch, max_len, dims)
    if kind == "mamba":
        return ssm.make_mamba_cache(batch, cfg.mamba)
    if kind == "mlstm":
        return ssm.make_mlstm_cache(batch, cfg.xlstm)
    if kind == "slstm":
        return ssm.make_slstm_cache(batch, cfg.xlstm)
    raise ValueError(kind)


def init_caches(cfg: ArchConfig, batch: int, max_len: int) -> list:
    """Concrete zeroed caches, mirroring the stage/period/stack structure."""
    caches = []
    for n_periods in cfg.stage_periods():
        per_stage = []
        for kind in cfg.period:
            one = _block_cache(kind, cfg, batch, max_len)
            stacked = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_periods,) + a.shape).copy(), one
            )
            per_stage.append(stacked)
        caches.append(tuple(per_stage))
    return caches


def cache_specs(cfg: ArchConfig, batch: int, max_len: int):
    return jax.eval_shape(lambda: init_caches(cfg, batch, max_len))


# ---------------------------------------------------------------------------
# Block application (train / prefill)
# ---------------------------------------------------------------------------


def _cache_from_kv(k: jnp.ndarray, v: jnp.ndarray, window: int | None, max_len: int) -> Params:
    B, S = k.shape[0], k.shape[1]
    if window is not None and window < max_len:
        W = window
        cache = {
            "k": jnp.zeros((B, W) + k.shape[2:], jnp.bfloat16),
            "v": jnp.zeros((B, W) + v.shape[2:], jnp.bfloat16),
            "pos": jnp.asarray(S, jnp.int32),
            "slot_pos": jnp.full((W,), -1, jnp.int32),
        }
        n = min(S, W)
        start = S - n
        pos_tail = np.arange(0, n) + start  # static
        slots = pos_tail % W
        cache["k"] = cache["k"].at[:, slots].set(k[:, start:].astype(jnp.bfloat16))
        cache["v"] = cache["v"].at[:, slots].set(v[:, start:].astype(jnp.bfloat16))
        cache["slot_pos"] = cache["slot_pos"].at[slots].set(pos_tail.astype(np.int32))
        return cache
    cache = attention.make_kv_cache(B, max_len, _dims_from_kv(k))
    return attention.prefill_into_cache(cache, k, v)


def _dims_from_kv(k: jnp.ndarray) -> attention.AttnDims:
    # only shapes matter for make_kv_cache
    return attention.AttnDims(
        d_model=0, num_heads=k.shape[2], num_kv_heads=k.shape[2], head_dim=k.shape[3]
    )


def _block_apply(
    kind: str,
    p: Params,
    x: jnp.ndarray,
    cfg: ArchConfig,
    positions: jnp.ndarray,
    mode: str,  # "train" | "prefill"
    max_len: int = 0,
):
    """Returns (x', cache_or_None, aux_loss)."""
    build_cache = mode == "prefill"
    aux = jnp.zeros((), jnp.float32)
    if kind in ("attn", "dense_attn", "moe_attn"):
        h = layers.apply_norm(cfg.norm, p["norm1"], x)
        cache = None
        if cfg.mla is not None:
            if build_cache:
                out, (c_kv, k_pe) = attention.mla_forward(
                    p["attn"], h, cfg.mla, positions, cfg.q_chunk, return_latent=True
                )
                cache = attention.make_mla_cache(x.shape[0], max_len, cfg.mla)
                cache = attention.mla_prefill_into_cache(cache, c_kv, k_pe)
            else:
                out = attention.mla_forward(p["attn"], h, cfg.mla, positions, cfg.q_chunk)
        else:
            dims = cfg.attn_dims()
            if build_cache:
                out, (k, v) = attention.gqa_forward(
                    p["attn"], h, dims, positions, cfg.q_chunk, return_kv=True
                )
                cache = _cache_from_kv(k, v, dims.sliding_window, max_len)
            else:
                out = attention.gqa_forward(p["attn"], h, dims, positions, cfg.q_chunk)
        x = x + out
        h2 = layers.apply_norm(cfg.norm, p["norm2"], x)
        if kind == "moe_attn":
            ffn_out, aux = moe.moe_forward(p["moe"], h2, cfg.moe)
        elif cfg.ffn == "mlp":
            ffn_out = layers.mlp_ffn(p["ffn"], h2, cfg.act)
        else:
            ffn_out = layers.glu_ffn(p["ffn"], h2, cfg.act)
        return x + ffn_out, cache, aux

    h = layers.apply_norm(cfg.norm, p["norm"], x)
    if kind == "mamba":
        if build_cache:
            out, state = ssm.mamba_forward(p["mamba"], h, cfg.mamba, return_state=True)
            cache = ssm.make_mamba_cache(x.shape[0], cfg.mamba)
            cache = dict(cache, ssd=state, pos=jnp.asarray(x.shape[1], jnp.int32))
            # conv tail: last K-1 pre-conv features; recomputed cheaply
            _, xbc, _ = ssm._mamba_split(p["mamba"], h[:, -(cfg.mamba.conv_kernel - 1) :], cfg.mamba)
            cache["conv"] = xbc.astype(cache["conv"].dtype)
            return x + out, cache, aux
        out = ssm.mamba_forward(p["mamba"], h, cfg.mamba)
        return x + out, None, aux
    if kind == "mlstm":
        if build_cache:
            out, (C, n, m) = ssm.mlstm_forward(p["mlstm"], h, cfg.xlstm, return_state=True)
            cache = ssm.make_mlstm_cache(x.shape[0], cfg.xlstm)
            up = layers.matmul(
                h[:, -(cfg.xlstm.conv_kernel - 1) :], p["mlstm"]["up_proj"]
            )
            cache = dict(
                cache,
                C=C,
                n=n,
                m=m,
                conv=jnp.split(up, 2, axis=-1)[0].astype(cache["conv"].dtype),
                pos=jnp.asarray(x.shape[1], jnp.int32),
            )
            return x + out, cache, aux
        out = ssm.mlstm_forward(p["mlstm"], h, cfg.xlstm)
        return x + out, None, aux
    if kind == "slstm":
        # sLSTM block output includes its own residual & FFN (xLSTM block form)
        if build_cache:
            out, (c, n, hs, m) = ssm.slstm_forward(p["slstm"], h, cfg.xlstm, return_state=True)
            cache = ssm.make_slstm_cache(x.shape[0], cfg.xlstm)
            cache = dict(cache, c=c, n=n, h=hs, m=m, pos=jnp.asarray(x.shape[1], jnp.int32))
            return x + out, cache, aux
        out = ssm.slstm_forward(p["slstm"], h, cfg.xlstm)
        return x + out, None, aux
    raise ValueError(kind)


def _block_decode(
    kind: str,
    p: Params,
    x: jnp.ndarray,
    cache: Params,
    cfg: ArchConfig,
    ragged: bool = False,
    paged_seq_len: int | None = None,
):
    """One-token block step.  ``ragged=True`` treats ``cache["pos"]`` as a
    per-row int32 [B] vector (the serving engine's slot-cache batches mix
    requests at different prefix lengths); SSM state steps are position-free,
    so only the attention variants branch.  ``paged_seq_len`` selects the
    paged-attention path: the cache carries a physical block pool plus a
    per-row block ``table`` instead of contiguous rows (still ragged)."""
    if kind in ("attn", "dense_attn", "moe_attn"):
        h = layers.apply_norm(cfg.norm, p["norm1"], x)
        if paged_seq_len is not None:
            if cfg.mla is not None:
                out, cache = attention.mla_decode_paged(
                    p["attn"], h, cache, cfg.mla, paged_seq_len
                )
            else:
                out, cache = attention.gqa_decode_paged(
                    p["attn"], h, cache, cfg.attn_dims(), paged_seq_len
                )
        elif cfg.mla is not None:
            mla_fn = attention.mla_decode_ragged if ragged else attention.mla_decode
            out, cache = mla_fn(p["attn"], h, cache, cfg.mla)
        else:
            gqa_fn = attention.gqa_decode_ragged if ragged else attention.gqa_decode
            out, cache = gqa_fn(p["attn"], h, cache, cfg.attn_dims())
        x = x + out
        h2 = layers.apply_norm(cfg.norm, p["norm2"], x)
        if kind == "moe_attn":
            ffn_out, _ = moe.moe_forward(p["moe"], h2, cfg.moe)
        elif cfg.ffn == "mlp":
            ffn_out = layers.mlp_ffn(p["ffn"], h2, cfg.act)
        else:
            ffn_out = layers.glu_ffn(p["ffn"], h2, cfg.act)
        return x + ffn_out, cache
    h = layers.apply_norm(cfg.norm, p["norm"], x)
    if kind == "mamba":
        out, cache = ssm.mamba_decode(p["mamba"], h, cache, cfg.mamba)
    elif kind == "mlstm":
        out, cache = ssm.mlstm_decode(p["mlstm"], h, cache, cfg.xlstm)
    elif kind == "slstm":
        out, cache = ssm.slstm_decode(p["slstm"], h, cache, cfg.xlstm)
    else:
        raise ValueError(kind)
    return x + out, cache


# ---------------------------------------------------------------------------
# Stage runners
# ---------------------------------------------------------------------------


def _run_stage(
    stage: Params,
    x: jnp.ndarray,
    cfg: ArchConfig,
    positions: jnp.ndarray,
    mode: str,
    max_len: int = 0,
):
    """Scan over this stage's periods.  Returns (x, stacked_caches, aux)."""
    period = cfg.period

    def body(carry, per_params):
        x, aux = carry
        caches = []
        for i, kind in enumerate(period):
            x, cache, a = _block_apply(kind, per_params[i], x, cfg, positions, mode, max_len)
            caches.append(cache)
            aux = aux + a
        # REPRO_SP=0 drops the sequence-parallel residual constraint
        # (a §Perf knob: its backward reshards cotangents in f32)
        import os as _os

        if _os.environ.get("REPRO_SP", "1") == "1":
            x = constrain(x, "batch", "seq", None)
        else:
            x = constrain(x, "batch", None, None)
        ys = tuple(caches) if mode == "prefill" else None
        return (x, aux), ys

    body = jax.checkpoint(body)
    (x, aux), stage_caches = layers.loop_scan(
        body, (x, jnp.zeros((), jnp.float32)), stage["blocks"]
    )
    return x, stage_caches, aux


def _decode_stage(
    stage: Params,
    x: jnp.ndarray,
    caches,
    cfg: ArchConfig,
    ragged: bool = False,
    paged_seq_len: int | None = None,
):
    period = cfg.period

    def body(x, inp):
        per_params, per_cache = inp
        new_caches = []
        for i, kind in enumerate(period):
            x, nc = _block_decode(
                kind, per_params[i], x, per_cache[i], cfg, ragged, paged_seq_len
            )
            new_caches.append(nc)
        return x, tuple(new_caches)

    x, new_caches = layers.loop_scan(body, x, (stage["blocks"], caches))
    return x, new_caches


# ---------------------------------------------------------------------------
# Per-stage entry points (the collaborative serving data plane)
# ---------------------------------------------------------------------------
#
# ``prefill`` / ``decode_step`` below run all H stages monolithically; the
# serving engine instead hands the residual stream replica-to-replica, so it
# needs the SAME math split at stage granularity: one prefill that builds one
# stage's caches, one cached decode step against them, and a slot-resident
# cache layout whose batch rows belong to different requests.


def prefill_stage(
    params: Params, stage_idx: int, x: jnp.ndarray, cfg: ArchConfig, max_len: int
):
    """Prefill through stage ``stage_idx`` (1-indexed): residual stream in,
    (residual stream out, stage caches sized ``max_len``) back."""
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    out, caches, _ = _run_stage(
        params["stages"][stage_idx - 1], x, cfg, positions, "prefill", max_len
    )
    return out, caches


def decode_stage_ragged(
    params: Params, stage_idx: int, x: jnp.ndarray, caches, cfg: ArchConfig
):
    """One token through stage ``stage_idx`` against its caches, with
    per-row positions (``cache["pos"]``: int32 [B])."""
    return _decode_stage(params["stages"][stage_idx - 1], x, caches, cfg, ragged=True)


# cache leaves with a ``max_len`` sequence dimension — the only ones the
# paged layout moves into the block pool; everything else (per-slot SSM
# state, conv tails, positions) stays slot-indexed
PAGED_CACHE_LEAVES = ("k", "v", "c_kv", "k_pe")


def validate_slot_layout(cfg: ArchConfig, stage_idx: int, max_len: int) -> None:
    """Reject configs the slot-resident cache layouts cannot represent, up
    front and with an actionable message (not mid-tree-map)."""
    if cfg.uses_attention and cfg.mla is None:
        w = cfg.attn_dims().sliding_window
        if w is not None and w < max_len:
            raise ValueError(
                f"stage {stage_idx} of config {cfg.name!r}: slot-resident "
                f"caches need full attention caches, but sliding_window={w} "
                f"< max_len={max_len}. Serve with max_len <= sliding_window, "
                "set ArchConfig.sliding_window=None, or use the monolithic "
                "decode path; per-slot window rings are a ROADMAP item."
            )


def init_stage_slot_caches(cfg: ArchConfig, stage_idx: int, num_slots: int, max_len: int):
    """Zeroed slot-resident caches for one stage's replica (dense layout).

    Leaves are shaped ``[n_periods, num_slots, ...]`` with ``pos`` a per-slot
    int32 vector — each slot holds one request's stage-local cache row, so a
    decode batch can gather any subset of slots (continuous batching).
    Sliding-window ring caches are not representable per-slot yet.
    """
    validate_slot_layout(cfg, stage_idx, max_len)
    n_periods = cfg.stage_periods()[stage_idx - 1]
    per_stage = []
    for kind in cfg.period:
        one = _block_cache(kind, cfg, num_slots, max_len)
        one["pos"] = jnp.zeros((num_slots,), jnp.int32)
        stacked = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_periods,) + a.shape).copy(), one
        )
        per_stage.append(stacked)
    return tuple(per_stage)


def init_stage_paged_caches(
    cfg: ArchConfig,
    stage_idx: int,
    num_slots: int,
    num_blocks: int,
    block_size: int,
    max_len: int,
):
    """Zeroed PAGED caches for one stage's replica: ``(pool, state)``.

    ``pool`` holds the sequence-dimension leaves (``k``/``v`` or MLA
    ``c_kv``/``k_pe``) as physical block pools ``[n_periods, num_blocks,
    block_size, ...]`` addressed through per-request block tables; ``state``
    keeps everything per-slot (``pos`` plus any SSM state), shaped
    ``[n_periods, num_slots, ...]`` exactly like the dense layout.  Both
    counts INCLUDE their trailing trash row (padded batch rows write there).
    """
    validate_slot_layout(cfg, stage_idx, max_len)
    n_periods = cfg.stage_periods()[stage_idx - 1]

    def stack(d):
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_periods,) + a.shape).copy(), d
        )

    pool_stage, state_stage = [], []
    for kind in cfg.period:
        if kind in ("attn", "dense_attn", "moe_attn"):
            if cfg.mla is not None:
                one = attention.make_mla_cache(num_blocks, block_size, cfg.mla)
            else:
                one = attention.make_kv_cache(num_blocks, block_size, cfg.attn_dims())
            pool = {k: v for k, v in one.items() if k in PAGED_CACHE_LEAVES}
            state = {}
        else:
            pool = {}
            state = {
                k: v
                for k, v in _block_cache(kind, cfg, num_slots, max_len).items()
                if k != "pos"
            }
        state["pos"] = jnp.zeros((num_slots,), jnp.int32)
        pool_stage.append(stack(pool))
        state_stage.append(stack(state))
    return tuple(pool_stage), tuple(state_stage)


def decode_stage_paged(
    params: Params,
    stage_idx: int,
    x: jnp.ndarray,
    pool_caches,
    state_rows,
    tables: jnp.ndarray,  # int32 [B, n_logical]
    cfg: ArchConfig,
    seq_len: int,
):
    """One token through stage ``stage_idx`` reading/writing the block pool
    through per-row block tables.

    ``pool_caches``: per-period pool dicts ``[n_periods, num_blocks, bs, ...]``
    (updated in place, returned whole); ``state_rows``: the batch's gathered
    per-slot rows ``[n_periods, B, ...]`` including ``pos``.  Returns
    ``(x_out, new_caches)`` with each period's dict holding both the updated
    pools and the updated batch rows.
    """
    n_periods = cfg.stage_periods()[stage_idx - 1]
    caches = []
    for pool_d, state_d in zip(pool_caches, state_rows):
        c = dict(state_d)
        c.update(pool_d)
        if pool_d:  # attention kinds read through the table
            c["table"] = jnp.broadcast_to(
                tables[None], (n_periods,) + tables.shape
            )
        caches.append(c)
    return _decode_stage(
        params["stages"][stage_idx - 1],
        x,
        tuple(caches),
        cfg,
        ragged=True,
        paged_seq_len=seq_len,
    )


# ---------------------------------------------------------------------------
# Heads
# ---------------------------------------------------------------------------


def _head_matrix(params: Params, cfg: ArchConfig) -> jnp.ndarray:
    return params["lm_head"]


def lm_logits(params: Params, hidden: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    return layers.matmul(hidden, _head_matrix(params, cfg)).astype(jnp.float32)


def _head_confidence(params: Params, norm_params, hidden: jnp.ndarray, cfg: ArchConfig):
    """(confidence, argmax) of one LM-head branch on [B, 1, d] hidden states.

    Routed through kernels.ops so the fused Pallas head is used on TPU —
    [B, vocab] logits are never materialized.
    """
    from repro.kernels import ops as kernel_ops

    h = layers.apply_norm(cfg.norm, norm_params, hidden[:, 0])
    return kernel_ops.exit_confidence(h, _head_matrix(params, cfg))


def exit_confidence(params: Params, hidden: jnp.ndarray, stage: int, cfg: ArchConfig):
    """(confidence, argmax) of exit branch b_h on [B, 1, d] hidden states."""
    return _head_confidence(params, params["exit_norms"][f"exit_{stage}"], hidden, cfg)


def final_confidence(params: Params, hidden: jnp.ndarray, cfg: ArchConfig):
    """(confidence, argmax) of the final head — the mandatory exit shares the
    early branches' fused path."""
    return _head_confidence(params, params["final_norm"], hidden, cfg)


def chunked_xent(
    hidden: jnp.ndarray,  # [B, S, d]
    labels: jnp.ndarray,  # [B, S] (-1 == masked)
    head: jnp.ndarray,  # [d, V]
    chunk: int = 512,
):
    """Mean token NLL without materializing [B, S, V] logits."""
    B, S, d = hidden.shape
    chunk = min(chunk, S)
    if S % chunk != 0:
        chunk = S
    nC = S // chunk
    hc = hidden.reshape(B, nC, chunk, d).swapaxes(0, 1)
    yc = labels.reshape(B, nC, chunk).swapaxes(0, 1)

    def one(args):
        h, y = args
        logits = layers.matmul(h, head).astype(jnp.float32)  # [B, C, V]
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, jnp.maximum(y, 0)[..., None], axis=-1)[..., 0]
        mask = (y >= 0).astype(jnp.float32)
        return jnp.sum((lse - tgt) * mask), jnp.sum(mask)

    nll, cnt = layers.loop_map(one, (hc, yc))
    return jnp.sum(nll), jnp.sum(cnt)


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------


def _embed_inputs(params: Params, batch: dict, cfg: ArchConfig) -> jnp.ndarray:
    if cfg.frontend == "embeds":
        x = batch["embeds"].astype(cfg.dtype)
    else:
        x = layers.embed(params["embed"], batch["tokens"], cfg.dtype)
    return constrain(x, "batch", "seq", None)


def forward_hidden(params: Params, batch: dict, cfg: ArchConfig):
    """Full forward; returns (final_hidden, {stage: exit_hidden}, aux)."""
    x = _embed_inputs(params, batch, cfg)
    S = x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    exits: dict[int, jnp.ndarray] = {}
    aux_total = jnp.zeros((), jnp.float32)
    for si, stage in enumerate(params["stages"], start=1):
        x, _, aux = _run_stage(stage, x, cfg, positions, "train")
        aux_total = aux_total + aux
        if si in cfg.exit_stages:
            exits[si] = x
    return x, exits, aux_total


def loss_fn(params: Params, batch: dict, cfg: ArchConfig, aux_weight: float = 0.01):
    """Deep-supervision LM loss: final head + weighted early-exit heads."""
    x, exits, moe_aux = forward_hidden(params, batch, cfg)
    head = _head_matrix(params, cfg)
    labels = batch["labels"]

    h_final = layers.apply_norm(cfg.norm, params["final_norm"], x)
    nll, cnt = chunked_xent(h_final, labels, head)
    total = nll
    weight_sum = cnt
    per_exit = {}
    for h_stage in cfg.exit_stages:
        he = layers.apply_norm(
            cfg.norm, params["exit_norms"][f"exit_{h_stage}"], exits[h_stage]
        )
        e_nll, e_cnt = chunked_xent(he, labels, head)
        per_exit[f"exit_{h_stage}_loss"] = e_nll / jnp.maximum(e_cnt, 1.0)
        total = total + cfg.exit_loss_weight * e_nll
        weight_sum = weight_sum + cfg.exit_loss_weight * e_cnt

    loss = total / jnp.maximum(weight_sum, 1.0) + aux_weight * moe_aux
    metrics = {
        "loss": loss,
        "final_loss": nll / jnp.maximum(cnt, 1.0),
        "moe_aux": moe_aux,
        **per_exit,
    }
    return loss, metrics


def prefill(params: Params, batch: dict, cfg: ArchConfig, max_len: int):
    """Returns (next_token [B], exit_conf [B, n_exits], exit_token [B, n_exits],
    caches)."""
    x = _embed_inputs(params, batch, cfg)
    B, S = x.shape[0], x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    caches, confs, toks = [], [], []
    for si, stage in enumerate(params["stages"], start=1):
        x, stage_caches, _ = _run_stage(stage, x, cfg, positions, "prefill", max_len)
        caches.append(stage_caches)
        if si in cfg.exit_stages:
            c, t = exit_confidence(params, x[:, -1:], si, cfg)
            confs.append(c)
            toks.append(t)
    # final head through the same fused path as the exit branches: f32-
    # accumulated logits that never materialize [B, vocab]
    _, next_token = final_confidence(params, x[:, -1:], cfg)
    exit_conf = jnp.stack(confs, axis=1) if confs else jnp.zeros((B, 0), jnp.float32)
    exit_tok = jnp.stack(toks, axis=1) if toks else jnp.zeros((B, 0), jnp.int32)
    return next_token, exit_conf, exit_tok, caches


def decode_step(params: Params, batch: dict, caches: list, cfg: ArchConfig):
    """One token for every sequence; returns (next_token, exit_conf,
    exit_token, caches')."""
    x = _embed_inputs(params, batch, cfg)
    B = x.shape[0]
    new_caches, confs, toks = [], [], []
    for si, (stage, stage_cache) in enumerate(zip(params["stages"], caches), start=1):
        x, nc = _decode_stage(stage, x, stage_cache, cfg)
        new_caches.append(nc)
        if si in cfg.exit_stages:
            c, t = exit_confidence(params, x, si, cfg)
            confs.append(c)
            toks.append(t)
    _, next_token = final_confidence(params, x, cfg)
    exit_conf = jnp.stack(confs, axis=1) if confs else jnp.zeros((B, 0), jnp.float32)
    exit_tok = jnp.stack(toks, axis=1) if toks else jnp.zeros((B, 0), jnp.int32)
    return next_token, exit_conf, exit_tok, new_caches
