"""Observability: span trees, metrics, exporters, attribution, stream.

Unit layer: the tracer/metrics/export/stream primitives driven by hand with
synthetic event sequences (exact expected spans).  Integration layer: one
traced cached-decode serve on a tiny real engine, shared across tests —
span-tree completeness under mid-decode admission, metrics totals, export
validation, and the disabled-path bitwise-identity guarantee.
"""
import json

import numpy as np
import pytest

import jax

from repro.configs import get_config
from repro.core.profiles import profile_from_arch
from repro.core.thresholds import synthetic_validation
from repro.core.topology import NetworkSpec, build_edge_network
from repro.core.types import DtoHyperParams
from repro.models import model as model_lib
from repro.obs import (
    SPAN_KINDS,
    Counter,
    Gauge,
    Histogram,
    MetricsCollector,
    MetricsRegistry,
    NullTracer,
    SpanTracer,
    build_stream,
    chrome_trace,
    decompose,
    validate_chrome_trace,
)
from repro.serving import CollaborativeEngine


# ---------------------------------------------------------------------------
# metrics primitives
# ---------------------------------------------------------------------------


def test_counter_and_gauge_basics():
    c = Counter("c")
    c.inc()
    c.inc(np.float64(2.5))  # numpy scalars must not poison the accumulator
    assert c.value == 3.5
    assert type(c.value) is float

    g = Gauge("g")
    assert np.isnan(g.value) and g.n_samples == 0
    for v in (1.0, np.float64(3.0), 2.0):
        g.set(v)
    assert g.value == 2.0 and type(g.value) is float
    assert g.max_value == 3.0
    assert g.mean == pytest.approx(2.0)
    assert g.snapshot()["n"] == 3


def test_histogram_counts_and_quantiles():
    h = Histogram("h", lo_decade=-3, hi_decade=0, per_decade=8)
    rng = np.random.default_rng(0)
    xs = rng.uniform(1e-3, 1e-1, size=2000)
    for x in xs:
        h.observe(x)
    assert h.n == xs.size
    assert sum(h.counts) == xs.size
    assert h.min == pytest.approx(xs.min())
    assert h.max == pytest.approx(xs.max())
    assert h.mean == pytest.approx(xs.mean())
    # log-bucket quantiles are exact to bucket resolution (~33% per-decade/8)
    for q in (0.5, 0.95, 0.99):
        exact = float(np.quantile(xs, q))
        assert h.quantile(q) == pytest.approx(exact, rel=0.35)
    # quantiles are monotone in q
    assert h.quantile(0.5) <= h.quantile(0.95) <= h.quantile(0.99)


def test_histogram_out_of_range_and_empty():
    h = Histogram("h", lo_decade=-2, hi_decade=0, per_decade=4)
    assert np.isnan(h.quantile(0.5))  # empty
    h.observe(0.0)  # below range (and zero): first bucket
    h.observe(1e5)  # above range: overflow bucket
    assert h.counts[0] == 1 and h.counts[-1] == 1
    assert h.n == 2
    snap = h.snapshot()
    assert snap["n"] == 2 and snap["min"] == 0.0 and snap["max"] == 1e5


def test_registry_get_or_create_and_snapshot():
    r = MetricsRegistry()
    assert r.counter("a") is r.counter("a")
    assert r.gauge("b") is r.gauge("b")
    assert r.histogram("c") is r.histogram("c")
    r.counter("a").inc(2)
    r.gauge("b").set(0.5)
    r.histogram("c").observe(1e-3)
    assert r.names() == ["a", "b", "c"]
    snap = r.snapshot()
    assert snap["a"]["value"] == 2.0
    json.dumps(snap)  # JSON-able


# ---------------------------------------------------------------------------
# span tracer driven by hand (exact expected trees)
# ---------------------------------------------------------------------------


def _emit_one_request(tr, rid=0, base=0.0):
    """Replay the engine's hook sequence for one single-hop request; the
    resulting tree tiles [base, base+0.03] exactly."""
    tr.on_submit(base, rid, ed=0, arrival=base)
    tr.on_transfer(base, base + 0.01, 0.01, src=0, dst=2, rid=rid, mb=1.0)
    tr.on_enqueue(base + 0.01, rid, node=2)
    tr.on_batch(
        base + 0.03, 2, 1.0, 0.02, 0,
        stage=1, rids=(rid,), t_dispatch=base + 0.015, t_start=base + 0.02,
        n_rows=4, is_decode=False,
    )
    tr.on_exit(base + 0.03, rid, stage=1, conf=0.9)


def test_tracer_tiles_one_request_exactly():
    tr = SpanTracer()
    _emit_one_request(tr)
    assert tr.check_tree(0) == []
    assert tr.closed(0)
    comp = tr.components(0)
    assert comp["admission"] == 0.0
    assert comp["transfer"] == pytest.approx(0.01)
    assert comp["queue"] == pytest.approx(0.005)
    assert comp["batch_wait"] == pytest.approx(0.005)
    assert comp["compute"] == pytest.approx(0.01)
    assert sum(comp.values()) == pytest.approx(tr.done[0] - tr.arrival[0])
    assert tr.attempts[0] == 1
    assert [i["kind"] for i in tr.instants] == ["retire"]
    # the replay advanced the injected sim clock to the last event
    assert tr.clock.now == pytest.approx(0.03)


def test_tracer_resubmit_accounts_lost_time():
    tr = SpanTracer()
    tr.on_submit(0.0, 7, ed=0, arrival=0.0)
    tr.on_transfer(0.0, 0.01, 0.01, src=0, dst=2, rid=7, mb=1.0)
    tr.on_enqueue(0.01, 7, node=2)
    tr.on_failure(0.02, node=2)
    tr.on_resubmit(0.02, 7)  # engine re-submits from the ED...
    tr.on_transfer(0.02, 0.03, 0.01, src=0, dst=3, rid=7, mb=1.0)
    tr.on_enqueue(0.03, 7, node=3)
    tr.on_batch(
        0.05, 3, 1.0, 0.015, 0,
        stage=1, rids=(7,), t_dispatch=0.035, t_start=0.04,
        n_rows=1, is_decode=False,
    )
    tr.on_exit(0.05, 7, stage=1, conf=0.8)
    assert tr.check_tree(7) == []
    assert tr.attempts[7] == 2
    lost = [s for s in tr.spans[7] if s.attrs and s.attrs.get("lost")]
    assert len(lost) == 1
    assert lost[0].duration == pytest.approx(0.01)  # the abandoned wait
    kinds = {i["kind"] for i in tr.instants}
    assert kinds == {"failure", "resubmit", "retire"}
    dec = decompose(tr)
    assert dec["reconciles"] and dec["num_with_lost_time"] == 1
    (entry,) = dec["per_request"]
    assert entry["lost"] == pytest.approx(0.01)
    assert entry["total"] == pytest.approx(0.05)


def test_check_tree_flags_violations():
    tr = SpanTracer()
    assert tr.check_tree(0) == ["rid 0: no spans"]
    tr.add_span(1, "queue", 0.0, 0.01, node=2)
    tr.add_span(1, "compute", 0.02, 0.03, node=2)  # gap: 0.01 -> 0.02
    errs = tr.check_tree(1)
    assert any("starts at" in e for e in errs)
    assert any("never closed" in e for e in errs)
    tr2 = SpanTracer()
    tr2.add_span(2, "compute", 0.05, 0.01)  # backwards
    assert any("t1 < t0" in e for e in tr2.check_tree(2))


def test_replay_cache_invalidates_on_new_events():
    tr = SpanTracer()
    _emit_one_request(tr, rid=0, base=0.0)
    assert set(tr.spans) == {0}  # materializes + caches
    _emit_one_request(tr, rid=1, base=0.1)  # event log grew after a read
    assert set(tr.spans) == {0, 1}
    assert tr.check_tree(1) == []
    assert tr.clock.now == pytest.approx(0.13)


def test_decompose_residual_against_reported_delay():
    class FakeStats:
        rids = [0]
        delays = [0.05]  # engine claims 50 ms but the tree only tiles 30

    tr = SpanTracer()
    _emit_one_request(tr)
    dec = decompose(tr, FakeStats())
    assert not dec["reconciles"]
    assert dec["max_residual_s"] == pytest.approx(0.02)


def test_null_tracer_is_inert():
    nt = NullTracer()
    nt.on_batch(0.0, 1, 1.0, 0.1, 0)  # arbitrary hooks absorb anything
    nt.on_exit(0.0, 1, 2, 0.5)
    nt.add_span(0, "queue", 0.0, 1.0)
    with pytest.raises(AttributeError):
        nt.spans


# ---------------------------------------------------------------------------
# instrumentation stream dispatch
# ---------------------------------------------------------------------------


class _ExitCounter:
    def __init__(self):
        self.calls = []

    def on_exit(self, t, rid, stage, conf):
        self.calls.append((t, rid, stage, conf))


def test_build_stream_none_when_no_subscribers():
    assert build_stream() is None
    assert build_stream(None, None) is None


def test_stream_single_subscriber_binds_directly():
    sub = _ExitCounter()
    st = build_stream(sub, None)
    assert st.on_exit == sub.on_exit  # no fan-out indirection
    st.on_exit(1.0, 3, 2, 0.7)
    assert sub.calls == [(1.0, 3, 2, 0.7)]
    # hooks nobody defines are no-ops, not AttributeErrors
    st.on_pool(0.0, 1, 0.5)


def test_stream_fans_out():
    a, b = _ExitCounter(), _ExitCounter()
    st = build_stream(a, b)
    st.on_exit(1.0, 3, 2, 0.7)
    assert a.calls == b.calls == [(1.0, 3, 2, 0.7)]


# ---------------------------------------------------------------------------
# exporter + validator
# ---------------------------------------------------------------------------


def test_chrome_trace_of_synthetic_serve_validates():
    tr = SpanTracer()
    for rid in range(3):
        _emit_one_request(tr, rid=rid, base=0.05 * rid)
    tr.on_pool(0.2, node=2, used_fraction=0.25)
    payload = chrome_trace(tr)
    assert validate_chrome_trace(payload) == []
    json.dumps(payload)
    evs = payload["traceEvents"]
    names = {e.get("name") for e in evs if e.get("ph") == "X"}
    assert set(SPAN_KINDS) - {"batch_wait", "queue"} <= names  # admission has 0 dur but exists
    assert "stage1.prefill" in names  # the node busy track
    assert any(e["ph"] == "C" and e["name"] == "pool_occupancy" for e in evs)
    assert any(e["ph"] == "C" and e["name"] == "queue_depth" for e in evs)


def test_validate_chrome_trace_catches_corruption():
    assert validate_chrome_trace([]) != []
    assert validate_chrome_trace({}) != []
    assert "traceEvents is empty" in validate_chrome_trace({"traceEvents": []})[0]
    bad_dur = {"traceEvents": [
        {"ph": "X", "pid": 1, "tid": 0, "name": "s", "ts": 0.0, "dur": -5.0},
    ]}
    assert any("negative duration" in e for e in validate_chrome_trace(bad_dur))
    no_ts = {"traceEvents": [{"ph": "i", "pid": 1, "tid": 0, "name": "x"}]}
    assert any("ts" in e for e in validate_chrome_trace(no_ts))
    unbalanced = {"traceEvents": [
        {"ph": "E", "pid": 1, "tid": 0, "name": "s", "ts": 1.0},
    ]}
    assert any("E without matching B" in e for e in validate_chrome_trace(unbalanced))
    overlap = {"traceEvents": [
        {"ph": "X", "pid": 1, "tid": 5, "name": "a", "ts": 0.0, "dur": 10.0},
        {"ph": "X", "pid": 1, "tid": 5, "name": "b", "ts": 5.0, "dur": 10.0},
    ]}
    assert any("overlaps" in e for e in validate_chrome_trace(overlap))


# ---------------------------------------------------------------------------
# integration: one traced cached-decode serve on a tiny real engine
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("stablelm-1.6b").reduced(
        vocab_size=128, d_model=64, d_ff=128, num_heads=2, num_kv_heads=2,
        head_dim=32,
    )
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
    # low thresholds: a realistic mix of early exits and full-depth requests
    eng.state.thresholds = np.full_like(eng.state.thresholds, 0.1)
    return eng


def _prompts(n, seed=2):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 128, size=12).astype(np.int32) for _ in range(n)]


def _serve(eng, n=12, seed=7, **kw):
    eng.rng = np.random.default_rng(seed)
    # gen_len > 1 cached decode: prompts are admitted into RUNNING batches at
    # stage boundaries (continuous batching) — the hard case for the tiling
    kw.setdefault("gen_len", 3)
    kw.setdefault("decode_mode", "cached")
    return eng.serve(_prompts(n), arrival_rate=60.0, batch_size=4, **kw)


@pytest.fixture(scope="module")
def traced(engine):
    tracer, metrics = SpanTracer(), MetricsCollector()
    stats = _serve(engine, tracer=tracer, metrics=metrics)
    return stats, tracer, metrics


def test_serve_span_trees_tile_mid_decode_admission(traced):
    stats, tracer, _ = traced
    assert len(stats.delays) == 12
    for rid in stats.rids:
        assert tracer.check_tree(rid) == []
    dec = decompose(tracer, stats)
    assert dec["reconciles"], f"max residual {dec['max_residual_s']}"
    assert dec["num_requests"] == 12
    # components actually exercised: every kind shows up somewhere
    seen = {s.kind for spans in tracer.spans.values() for s in spans}
    assert seen == set(SPAN_KINDS)
    # decode compute spans exist (gen_len=3) and are flagged as such
    assert any(
        s.kind == "compute" and s.attrs and s.attrs.get("decode")
        for spans in tracer.spans.values() for s in spans
    )


def test_serve_metrics_totals_match_stats(traced):
    stats, _, metrics = traced
    r = metrics.registry
    s = stats.summary()
    assert r.counter("requests_submitted").value == 12
    assert r.histogram("delay_s").n == 12
    assert r.counter("batches").value == s["num_batches"]
    assert r.counter("forward_rows").value == s["num_forward_rows"]
    assert r.counter("real_rows").value == s["num_real_rows"]
    assert metrics.padded_row_frac() == pytest.approx(s["padded_row_frac"])
    assert r.histogram("delay_s").mean == pytest.approx(s["mean_delay"], rel=1e-6)
    exit_hist = metrics.realized_exit_histogram()
    assert sum(exit_hist.values()) == 12
    assert exit_hist == {
        stage: count
        for stage, count in zip(*np.unique(
            [v[0] for v in stats.by_rid().values()], return_counts=True
        ))
    }
    json.dumps(metrics.snapshot())


def test_serve_trace_exports_and_validates(traced):
    _, tracer, _ = traced
    payload = chrome_trace(tracer)
    assert validate_chrome_trace(payload) == []
    # both request tracks and node busy tracks are present
    pids = {e["pid"] for e in payload["traceEvents"]}
    assert 1 in pids and any(p >= 1000 for p in pids)


def test_disabled_path_is_bitwise_identical(engine, traced):
    stats_traced, _, _ = traced
    stats_off = _serve(engine)  # same seed/workload, no observers
    assert stats_off.by_rid() == stats_traced.by_rid()
    assert all(
        a == b for a, b in zip(stats_off.delays, stats_traced.delays)
    )
