"""Host spans and the compile counter (``repro.obs.host``).

Unit layer: span arithmetic under an injected clock, the ring, the
process-wide compile table.  Integration layer: reduced serves on a tiny
real engine — the ``serve.*`` self times tile the call, compiles land on
the program span that caused them, the record reaches the report, and a
CPU profiler trace names the spans.
"""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.profiles import profile_from_arch
from repro.core.thresholds import synthetic_validation
from repro.core.topology import NetworkSpec, build_edge_network
from repro.core.types import DtoHyperParams
from repro.models import model as model_lib
from repro.obs import host
from repro.serving import CollaborativeEngine

PROGRAMS = ("embed", "stage_prefill", "stage_decode", "slot_write", "exit_head", "final_head")


# ---------------------------------------------------------------------------
# unit: spans, records, ring
# ---------------------------------------------------------------------------


class _Clock:
    """Injected ``perf_counter_ns``: each read advances by the next step."""

    def __init__(self, steps):
        self.t = 0
        self.steps = iter(steps)

    def __call__(self):
        self.t += next(self.steps, 0)
        return self.t


def test_nested_self_time_under_injected_clock(monkeypatch):
    # reads: record t0, outer in, inner in, inner out, inner in, inner out,
    # outer out, record t1
    clock = _Clock([0, 10, 5, 7, 3, 11, 4, 2])
    monkeypatch.setattr(host, "perf_counter_ns", clock)
    rec = host.Record()
    with host.span("outer", rec):
        with host.span("inner", rec, stage=1, rows=2):
            pass
        with host.span("inner", rec):
            pass
    rec.batches = 3
    out = rec.close()
    spans = out["spans"]
    assert spans["inner"]["count"] == 2
    assert spans["inner"]["total_s"] == pytest.approx((7 + 11) * 1e-9)
    assert spans["inner"]["self_s"] == spans["inner"]["total_s"]
    # outer ran 5 + 7 + 3 + 11 + 4 = 30 ns, 18 of them in its children
    assert spans["outer"]["total_s"] == pytest.approx(30e-9)
    assert spans["outer"]["self_s"] == pytest.approx(12e-9)
    assert out["t1"] - out["t0"] == pytest.approx(42e-9)
    assert out["batches"] == 3
    assert host.recent()[-1] == out
    # a span left by an exception still closes: the stack is empty again
    with pytest.raises(KeyError):
        with host.span("outer", rec):
            raise KeyError("boom")
    assert host._open == []


def test_report_holds_record_and_ring_is_bounded(served):
    stats = served[0]
    report = stats.report()
    assert report["host"] is stats.host
    assert set(report["host"]) == {"t0", "t1", "batches", "spans", "compiles"}
    json.loads(json.dumps(report))
    assert stats.host in host.recent()
    for _ in range(host.RING + 6):
        host.Record().close()
    ring = host.recent()
    assert len(ring) == host.RING
    assert all(a["t0"] <= b["t0"] for a, b in zip(ring, ring[1:]))


def test_compiles_outside_spans_are_unscoped():
    before = host.compiles()

    def count(name, key):
        return host.compiles().get(name, {}).get(key, 0) - before.get(name, {}).get(key, 0)

    x = jnp.arange(7.0)
    jax.jit(lambda a: a * 3 + 1)(x)
    assert count("unscoped", "compiles") >= 1
    with host.span("outside.record"):  # a span with no record: table only
        jax.jit(lambda a: a - 2)(x)
    assert count("outside.record", "compiles") >= 1
    assert count("outside.record", "traces") >= 1


# ---------------------------------------------------------------------------
# integration: reduced serves on a tiny engine
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("stablelm-1.6b").reduced(
        vocab_size=96, d_model=64, d_ff=128, num_heads=2, num_kv_heads=2,
        head_dim=32,
    )
    params = model_lib.init_params(jax.random.key(3), cfg)
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


def _serve(eng, prompt_len=12, n=12):
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 96, size=prompt_len).astype(np.int32) for _ in range(n)]
    eng.rng = np.random.default_rng(7)
    return eng.serve(
        prompts, arrival_rate=60.0, batch_size=4, gen_len=3, decode_mode="cached"
    )


@pytest.fixture(scope="module")
def served(engine):
    """A warm-up serve, then the serve the tests read."""
    _serve(engine)
    return _serve(engine), engine


def test_engine_configure_is_a_span(served):
    assert "engine.configure" in host.compiles()


def test_serve_spans_tile_the_call(served):
    stats, _ = served
    rec = stats.host
    assert rec["batches"] == stats.num_batches > 0
    spans = rec["spans"]
    assert {"serve.setup", "serve.event", "serve.assemble", "serve.wait",
            "serve.pull", "serve.finish"} <= set(spans)
    assert {"serve." + p for p in PROGRAMS} <= set(spans)
    assert spans["serve.setup"]["count"] == spans["serve.finish"]["count"] == 1
    # one wait and one pull for the residual, one for the heads where a
    # batch has them (every batch has one or both)
    assert spans["serve.wait"]["count"] == spans["serve.pull"]["count"] >= stats.num_batches
    # leaves have no children; every host moment is in exactly one self time
    for name in ("serve.assemble", "serve.wait", "serve.pull"):
        assert spans[name]["self_s"] == spans[name]["total_s"]
    covered = sum(s["self_s"] for s in spans.values())
    assert covered == pytest.approx(rec["t1"] - rec["t0"], rel=0.02)


@pytest.fixture
def no_persistent_cache():
    """Compiles must reach the backend for the counter to see them."""
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def test_compile_counter_charges_the_program(served, no_persistent_cache):
    _, engine = served
    first = _serve(engine, prompt_len=9).host["compiles"]  # a prompt length not yet served
    programs = {n: c for n, c in first.items() if n[len("serve."):] in PROGRAMS}
    assert sum(c["compiles"] for c in programs.values()) >= 1
    assert first["serve.stage_prefill"]["compiles"] >= 1
    assert all(c["compile_s"] > 0 for c in programs.values() if c["compiles"])
    again = _serve(engine, prompt_len=9).host["compiles"]
    assert again == {}


def test_profiler_trace_names_serve_spans(served, tmp_path):
    _, engine = served
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))
    try:
        from benchlib import trace
    finally:
        sys.path.pop(0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(engine)
    finally:
        jax.profiler.stop_trace()
    summary = trace.reduce_xplane(trace.find_xplane(str(tmp_path)), prefix="serve.")
    labels = set(summary.idle_gaps)
    assert {"event", "pull"} <= labels
    assert any(label.startswith("stage_") for label in labels)
    assert summary.busy_s > 0
