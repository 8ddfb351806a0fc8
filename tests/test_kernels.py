"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.exit_confidence import exit_confidence
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_decode_attention import paged_decode_attention

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _rand(rng, shape, dtype):
    return jnp.asarray(rng.standard_normal(shape), dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Sq,Sk,Hq,KVH,hd,block",
    [
        (1, 128, 128, 4, 4, 64, 64),  # MHA
        (2, 256, 256, 8, 2, 64, 64),  # GQA 4:1
        (1, 192, 192, 4, 1, 32, 64),  # MQA, ragged seq vs block
        (2, 128, 384, 4, 4, 128, 128),  # cross: kv longer than q
    ],
)
def test_flash_attention_matches_ref(rng, dtype, B, Sq, Sk, Hq, KVH, hd, block):
    q = _rand(rng, (B, Sq, Hq, hd), dtype)
    k = _rand(rng, (B, Sk, KVH, hd), dtype)
    v = _rand(rng, (B, Sk, KVH, hd), dtype)
    out = flash_attention(
        q, k, v, causal=True, block_q=block, block_k=block, interpret=True
    )
    exp = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32), atol=TOL[dtype]
    )


@pytest.mark.parametrize("window", [32, 100, 4096])
def test_flash_attention_sliding_window(rng, window):
    B, S, H, hd = 1, 256, 4, 64
    q = _rand(rng, (B, S, H, hd), jnp.float32)
    k = _rand(rng, (B, S, H, hd), jnp.float32)
    v = _rand(rng, (B, S, H, hd), jnp.float32)
    out = flash_attention(
        q, k, v, causal=True, window=window, block_q=64, block_k=64, interpret=True
    )
    exp = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-5)


def test_flash_attention_non_causal(rng):
    B, S, H, hd = 1, 128, 2, 64
    q = _rand(rng, (B, S, H, hd), jnp.float32)
    k = _rand(rng, (B, S, H, hd), jnp.float32)
    v = _rand(rng, (B, S, H, hd), jnp.float32)
    out = flash_attention(q, k, v, causal=False, block_q=64, block_k=64, interpret=True)
    exp = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,Hq,KVH,hd,block",
    [
        (2, 300, 8, 2, 64, 64),
        (2, 200, 4, 4, 64, 64),  # MHA at hd 64 (stablelm): 2 heads per lane block
        (1, 512, 4, 4, 128, 128),
        (3, 1000, 16, 4, 64, 256),  # ragged lengths below
    ],
)
def test_decode_attention_matches_ref(rng, dtype, B, S, Hq, KVH, hd, block):
    q = _rand(rng, (B, Hq, hd), dtype)
    k = _rand(rng, (B, S, KVH, hd), dtype)
    v = _rand(rng, (B, S, KVH, hd), dtype)
    lengths = jnp.asarray(rng.integers(1, S + 1, size=B), jnp.int32)
    out = decode_attention(q, k, v, lengths, block_k=block, interpret=True)
    exp = ref.decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32), atol=TOL[dtype]
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Hq,KVH,hd,NB,bs,nlog",
    [
        (3, 4, 2, 32, 9, 16, 4),  # GQA 2:1
        (2, 4, 4, 64, 9, 16, 4),  # MHA at hd 64 (stablelm), block size 16
        (2, 2, 2, 16, 5, 1, 7),  # degenerate one-token blocks
        (1, 8, 4, 64, 12, 8, 3),  # single row
    ],
)
def test_paged_decode_attention_matches_oracle(rng, dtype, B, Hq, KVH, hd, NB, bs, nlog):
    """Scalar-prefetch block-table kernel == gather + dense decode oracle."""
    q = _rand(rng, (B, Hq, hd), dtype)
    k_pool = _rand(rng, (NB, bs, KVH, hd), dtype)
    v_pool = _rand(rng, (NB, bs, KVH, hd), dtype)
    table = jnp.asarray(rng.integers(0, NB, (B, nlog)), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, nlog * bs + 1, (B,)), jnp.int32)
    want = ref.paged_decode_attention_ref(q, k_pool, v_pool, table, lengths)
    got = paged_decode_attention(q, k_pool, v_pool, table, lengths, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=TOL[dtype]
    )


def test_paged_dispatch_backends_agree_on_seq_len(rng):
    """ops.paged_decode_attention must honor seq_len identically on the xla
    (gather + slice) and Pallas (length-clamp) paths, including rows whose
    raw length overhangs seq_len."""
    from repro.kernels import ops

    B, Hq, KVH, hd, NB, bs, nlog = 3, 4, 2, 32, 10, 8, 4
    q = _rand(rng, (B, Hq, hd), jnp.float32)
    k_pool = _rand(rng, (NB, bs, KVH, hd), jnp.float32)
    v_pool = _rand(rng, (NB, bs, KVH, hd), jnp.float32)
    table = jnp.asarray(rng.integers(0, NB, (B, nlog)), jnp.int32)
    seq_len = 20  # < nlog * bs
    lengths = jnp.asarray([5, seq_len, nlog * bs], jnp.int32)  # last overhangs
    try:
        ops.set_backend("xla")
        want = ops.paged_decode_attention(
            q, k_pool, v_pool, table, lengths, seq_len=seq_len
        )
        ops.set_backend("pallas_interpret")
        got = ops.paged_decode_attention(
            q, k_pool, v_pool, table, lengths, seq_len=seq_len
        )
    finally:
        ops.set_backend("auto")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_paged_oracle_seq_len_slice_matches_contiguous(rng):
    """A block table laid out contiguously + seq_len slice reproduces the
    dense decode reference on the same rows — the bitwise bridge the paged
    serving path rests on."""
    B, S, KVH, Hq, hd, bs = 2, 20, 2, 4, 32, 8
    nlog = -(-S // bs)
    k = _rand(rng, (B, S, KVH, hd), jnp.float32)
    v = _rand(rng, (B, S, KVH, hd), jnp.float32)
    q = _rand(rng, (B, Hq, hd), jnp.float32)
    lengths = jnp.asarray([S, 13], jnp.int32)
    pad = nlog * bs - S
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # row b's blocks live at pool rows [b*nlog, (b+1)*nlog)
    k_pool = kp.reshape(B * nlog, bs, KVH, hd)
    v_pool = vp.reshape(B * nlog, bs, KVH, hd)
    table = jnp.arange(B * nlog, dtype=jnp.int32).reshape(B, nlog)
    want = ref.decode_attention_ref(q, k, v, lengths)
    got = ref.paged_decode_attention_ref(
        q, k_pool, v_pool, table, lengths, seq_len=S
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_decode_attention_length_zero_rows_are_finite(rng):
    B, S, Hq, KVH, hd = 2, 128, 4, 4, 32
    q = _rand(rng, (B, Hq, hd), jnp.float32)
    k = _rand(rng, (B, S, KVH, hd), jnp.float32)
    v = _rand(rng, (B, S, KVH, hd), jnp.float32)
    lengths = jnp.asarray([0, 64], jnp.int32)
    out = decode_attention(q, k, v, lengths, block_k=64, interpret=True)
    assert bool(jnp.all(jnp.isfinite(out)))
    assert bool(jnp.all(out[0] == 0.0))  # empty cache -> zero output


# ---------------------------------------------------------------------------
# exit confidence
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,d,V,bb,bv",
    [
        (4, 64, 1000, 4, 256),  # ragged vocab
        (8, 128, 2048, 4, 512),
        (3, 32, 513, 8, 128),  # B < block, V % block != 0
        (1, 16, 257, 8, 128),  # single row, ragged vocab tail of 1
        (5, 16, 130, 4, 64),  # batch pad + vocab pad simultaneously
        (7, 32, 64, 2, 64),  # vocab fits one block exactly, batch ragged
        (6, 16, 127, 8, 128),  # vocab < one block (block_v clamps to V)
    ],
)
def test_exit_confidence_matches_ref(rng, dtype, B, d, V, bb, bv):
    h = _rand(rng, (B, d), dtype)
    w = _rand(rng, (d, V), dtype)
    conf, idx = exit_confidence(h, w, block_b=bb, block_v=bv, interpret=True)
    cref, iref = ref.exit_confidence_ref(h, w)
    np.testing.assert_allclose(np.asarray(conf), np.asarray(cref), atol=1e-3)
    assert bool(jnp.all(idx == iref))


def test_exit_confidence_padding_rows_do_not_leak(rng):
    """Padded batch rows must not perturb real rows' (conf, argmax)."""
    h = _rand(rng, (3, 32), jnp.float32)
    w = _rand(rng, (32, 200), jnp.float32)
    conf3, idx3 = exit_confidence(h, w, block_b=8, block_v=64, interpret=True)
    h_pad = jnp.concatenate([h, jnp.zeros((5, 32), jnp.float32)])
    conf8, idx8 = exit_confidence(h_pad, w, block_b=8, block_v=64, interpret=True)
    np.testing.assert_allclose(np.asarray(conf8[:3]), np.asarray(conf3), atol=1e-6)
    assert bool(jnp.all(idx8[:3] == idx3))


def test_exit_confidence_is_valid_probability(rng):
    h = _rand(rng, (16, 64), jnp.bfloat16)
    w = _rand(rng, (64, 777), jnp.bfloat16)
    conf, idx = exit_confidence(h, w, interpret=True)
    assert bool(jnp.all(conf > 0)) and bool(jnp.all(conf <= 1.0))
    assert bool(jnp.all((idx >= 0) & (idx < 777)))


def test_ops_dispatch_xla_matches_interpret(rng):
    from repro.kernels import ops

    h = _rand(rng, (4, 64), jnp.bfloat16)
    w = _rand(rng, (64, 500), jnp.bfloat16)
    try:
        ops.set_backend("xla")
        c_x, i_x = ops.exit_confidence(h, w)
        ops.set_backend("pallas_interpret")
        c_p, i_p = ops.exit_confidence(h, w)
    finally:
        ops.set_backend("auto")
    np.testing.assert_allclose(np.asarray(c_x), np.asarray(c_p), atol=1e-3)
    assert bool(jnp.all(i_x == i_p))
