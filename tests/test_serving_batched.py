"""Micro-batched data plane: batched-vs-sequential equivalence, shape
bucketing, Poisson arrivals, and the fused final head."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.profiles import profile_from_arch
from repro.core.thresholds import synthetic_validation
from repro.core.topology import NetworkSpec, build_edge_network
from repro.core.types import DtoHyperParams
from repro.models import layers, model as model_lib
from repro.serving import CollaborativeEngine, Request, ShapeBucketBatcher
from repro.serving.batching import batch_tokens, padded_batch_size


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("stablelm-1.6b").reduced(vocab_size=128)
    params = model_lib.init_params(jax.random.key(0), cfg)
    profile = profile_from_arch(cfg)
    topo = build_edge_network(
        seed=0, profile=profile, spec=NetworkSpec(num_eds=4, es_per_stage=(2, 2))
    )
    ep = synthetic_validation(seed=1, profile=profile)
    eng = CollaborativeEngine(
        params, cfg, topo, profile, ep, DtoHyperParams(rounds=20), seed=0
    )
    eng.configuration_phase()
    return eng


def _prompts(n, vocab=128, length=12, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=length).astype(np.int32) for _ in range(n)]


# ---------------------------------------------------------------------------
# batched vs sequential engine equivalence
# ---------------------------------------------------------------------------


def _serve(engine, prompts, batch_size, seed=7):
    engine.rng = np.random.default_rng(seed)
    return engine.serve(prompts, arrival_rate=1e5, batch_size=batch_size)


def test_batched_serve_matches_sequential_exits(engine):
    prompts = _prompts(16)
    seq = _serve(engine, prompts, batch_size=1)
    for bs in (4, 8):
        bat = _serve(engine, prompts, batch_size=bs)
        assert bat.by_rid() == seq.by_rid()  # same exits, same tokens per rid
        assert len(bat.delays) == len(prompts)
        assert bat.num_batches < seq.num_batches
        assert all(np.isfinite(bat.delays))


def test_batched_serve_confidences_match(engine):
    prompts = _prompts(12, seed=3)
    seq = _serve(engine, prompts, batch_size=1)
    bat = _serve(engine, prompts, batch_size=8)
    c_seq = {r: c for r, c in zip(seq.rids, seq.confidences)}
    c_bat = {r: c for r, c in zip(bat.rids, bat.confidences)}
    for rid in c_seq:
        assert c_bat[rid] == pytest.approx(c_seq[rid], abs=1e-5)


def test_mixed_prompt_lengths_bucket_by_shape(engine):
    rng = np.random.default_rng(5)
    prompts = [
        rng.integers(0, 128, size=length).astype(np.int32)
        for length in (8, 12, 8, 12, 8, 12, 8, 12)
    ]
    seq = _serve(engine, prompts, batch_size=1)
    bat = _serve(engine, prompts, batch_size=4)
    assert bat.by_rid() == seq.by_rid()
    assert len(bat.delays) == len(prompts)


def test_poisson_arrivals_complete_and_scale_with_rate(engine):
    prompts = _prompts(10)
    engine.rng = np.random.default_rng(11)
    fast = engine.serve(prompts, arrival_rate=1e5, batch_size=2)
    engine.rng = np.random.default_rng(11)
    slow = engine.serve(prompts, arrival_rate=1.0, batch_size=2)
    assert len(fast.delays) == len(slow.delays) == len(prompts)
    # at rate 1e5 every request is queued behind its predecessors; at rate 1
    # the system drains between arrivals, so queueing delay must shrink
    assert np.mean(slow.delays) < np.mean(fast.delays)


# ---------------------------------------------------------------------------
# fused final head == reference softmax head
# ---------------------------------------------------------------------------


def test_fused_final_head_matches_softmax_reference(engine):
    cfg = engine.cfg
    params = engine.programs.params
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((5, 1, cfg.d_model)), cfg.dtype)
    conf, tok = model_lib.final_confidence(params, x, cfg)
    h = layers.apply_norm(cfg.norm, params["final_norm"], x)[:, 0]
    # reference in the head's own dtype: the fused head casts the f32 master
    # weight to the activation dtype (bf16 here) and accumulates in f32, so
    # the reference does too.  (Logits rounded to bf16, as ``lm_logits``
    # returns them, tie near-equal tokens and flip argmaxes.)
    head = params["lm_head"].astype(h.dtype)
    logits = jnp.matmul(h, head, preferred_element_type=jnp.float32)
    ref_conf = jax.nn.softmax(logits, axis=-1).max(axis=-1)
    ref_tok = jnp.argmax(logits, axis=-1)
    # f32 softmax reductions in another order: a few ulps of a prob <= 1
    np.testing.assert_allclose(np.asarray(conf), np.asarray(ref_conf), atol=1e-6)
    assert bool(jnp.all(tok == ref_tok))


# ---------------------------------------------------------------------------
# batching utilities
# ---------------------------------------------------------------------------


def test_shape_bucket_batcher_fifo_across_buckets():
    b = ShapeBucketBatcher(batch_size=2)
    order = [("a", 0), ("b", 1), ("a", 2), ("a", 3), ("b", 4)]
    for key, rid in order:
        b.push(key, Request(rid=rid, tokens=np.arange(3), arrival=float(rid)))
    assert len(b) == 5
    key, batch = b.pop_batch()  # oldest head is rid 0 in bucket "a"
    assert key == "a" and [r.rid for r in batch] == [0, 2]
    key, batch = b.pop_batch()  # now bucket "b"'s head (rid 1) is oldest
    assert key == "b" and [r.rid for r in batch] == [1, 4]
    key, batch = b.pop_batch()
    assert key == "a" and [r.rid for r in batch] == [3]
    assert b.pop_batch() is None and len(b) == 0


def test_padded_batch_size_powers_of_two():
    assert [padded_batch_size(n, 32) for n in (1, 2, 3, 5, 9, 31, 32, 40)] == [
        1, 2, 4, 8, 16, 32, 32, 32,
    ]


def test_batch_tokens_pads_batch_dim():
    reqs = [
        Request(rid=i, tokens=np.arange(4, dtype=np.int32), arrival=0.0)
        for i in range(3)
    ]
    out = batch_tokens(reqs, batch_size=8)
    assert out.shape == (4, 4)  # 3 rows -> next pow2
    assert (out[3] == 0).all()
