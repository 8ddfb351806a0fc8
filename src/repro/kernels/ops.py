"""Dispatching wrappers around the Pallas kernels.

Each op picks an implementation:
  * "pallas"            — compiled Pallas kernel (TPU).
  * "pallas_interpret"  — kernel body interpreted in Python (CPU validation).
  * "xla"               — pure-jnp path, GSPMD-shardable; what the CPU-hosted
                          dry-run lowers.

Default: pallas on TPU backends, xla elsewhere.  ``set_backend`` overrides
(tests force "pallas_interpret" to exercise the kernel bodies).
"""
from __future__ import annotations

from typing import Literal

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _dec
from repro.kernels import exit_confidence as _exit
from repro.kernels import flash_attention as _flash
from repro.kernels import paged_decode_attention as _paged
from repro.kernels import ref

Backend = Literal["auto", "pallas", "pallas_interpret", "xla"]

_backend: Backend = "auto"


def set_backend(backend: Backend) -> None:
    global _backend
    _backend = backend


def get_backend() -> str:
    if _backend != "auto":
        return _backend
    return "pallas" if jax.default_backend() == "tpu" else "xla"


# ---------------------------------------------------------------------------


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 128,
    block_k: int = 128,
) -> jnp.ndarray:
    be = get_backend()
    if be == "xla":
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    return _flash.flash_attention(
        q,
        k,
        v,
        causal=causal,
        window=window,
        block_q=block_q,
        block_k=block_k,
        interpret=(be == "pallas_interpret"),
    )


def decode_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    block_k: int = 512,
) -> jnp.ndarray:
    be = get_backend()
    if be == "xla":
        return ref.decode_attention_ref(q, k, v, lengths)
    return _dec.decode_attention(
        q, k, v, lengths, block_k=block_k, interpret=(be == "pallas_interpret")
    )


def paged_decode_attention(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    table: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    seq_len: int | None = None,
) -> jnp.ndarray:
    """Flash decode through a block table over a paged KV pool.

    The xla path gathers the row's blocks into a contiguous virtual cache
    sliced to ``seq_len`` — the exact shape of the dense slot path, so paged
    and dense decode stay bitwise identical.  The Pallas path streams pool
    blocks via scalar-prefetched table indices and never materializes the
    gather.
    """
    be = get_backend()
    if be == "xla":
        return ref.paged_decode_attention_ref(
            q, k_pool, v_pool, table, lengths, seq_len=seq_len
        )
    if seq_len is not None:
        # the kernel masks by per-row lengths only; clamping reproduces the
        # oracle's slice-to-seq_len semantics on every backend
        lengths = jnp.minimum(lengths, seq_len)
    return _paged.paged_decode_attention(
        q, k_pool, v_pool, table, lengths, interpret=(be == "pallas_interpret")
    )


def exit_confidence(
    h: jnp.ndarray,
    w: jnp.ndarray,
    *,
    block_b: int = 128,
    block_v: int = 1024,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(top-1 softmax prob, argmax) of ``h @ w`` with the head ``w`` cast to
    the activation dtype, as the oracle casts it: a bf16 head tile is what
    fits the kernel's VMEM.  The serving programs are handed the head in that
    dtype already (``model.serving_params``), so the cast is a no-op there;
    training and the monolithic steps hand it f32 master weights."""
    be = get_backend()
    if be == "xla":
        return ref.exit_confidence_ref(h, w)
    return _exit.exit_confidence(
        h,
        w.astype(h.dtype),
        block_b=block_b,
        block_v=block_v,
        interpret=(be == "pallas_interpret"),
    )
