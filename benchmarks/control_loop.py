"""Closed-loop DTO-EE vs static-once configuration over the LIVE engine.

The paper's Figs. 7–8 claim: in a dynamic environment, re-optimizing the
offloading strategy and thresholds every slot beats a one-shot decision.
This benchmark runs that experiment against the REAL serving data plane:

  * per scenario (arrival burst / node slowdown / link degradation / node
    failure), the same Poisson workload is served twice — once with the
    pre-serve DTO-EE configuration frozen (``static``), once with telemetry
    + a ReconfigController re-optimizing mid-serve (``closed``) — and mean
    delay, delay stddev, p95, and branch-accuracy-weighted expected accuracy
    are compared;
  * a traced serve under the static configuration reports tail latency
    (p50/p95/p99) and the measured queue/compute/comms delay attribution
    against the DTO-EE model terms per node (span sums must reconcile with
    reported delays exactly);
  * the threshold-aware batch policy is A/B'd against FIFO on a cached
    decode workload (padded-row waste, token-identical outputs);
  * the simulator's same-timestamp event harvest is measured before/after
    (tasks/s; results asserted identical).

Results land in ``BENCH_control.json``; ``--smoke`` shrinks everything and
keeps only the structural assertions (CI runs it via ``make bench-smoke``).

    PYTHONPATH=src python benchmarks/control_loop.py [--out BENCH_control.json]
"""
from __future__ import annotations

import argparse
import json
import platform
import time

import numpy as np

import jax

from repro.configs import get_config
from repro.control import (
    ControllerConfig,
    ReconfigController,
    Telemetry,
    TelemetryConfig,
    get_scenario,
)
from repro.core import dto_ee, simulator
from repro.core.profiles import profile_from_arch
from repro.core.thresholds import synthetic_validation
from repro.core.topology import NetworkSpec, build_edge_network
from repro.core.types import DtoHyperParams, RESNET101_PROFILE
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as model_lib
from repro.serving import CollaborativeEngine

SCENARIOS = ("burst", "slowdown", "link", "failure")
# acceptance: the closed loop must beat static on mean AND stddev here
MUST_WIN = ("burst", "slowdown", "failure")


def _cfg():
    return get_config("stablelm-1.6b").reduced(
        vocab_size=128,
        d_model=64,
        d_ff=128,
        num_heads=2,
        num_kv_heads=2,
        head_dim=32,
    )


def build_engine(params, cfg, topo, profile, ep, threshold: float, seed: int = 0):
    """Fresh engine + one converged-enough pre-serve configuration phase —
    the shared starting point of both policies."""
    eng = CollaborativeEngine(
        params, cfg, topo, profile, ep, DtoHyperParams(rounds=20), seed=seed
    )
    eng.configuration_phase()
    # live confidences of the reduced model concentrate low; pin the
    # thresholds into the sensitive range so the workload mixes exits
    eng.state.thresholds = np.full_like(eng.state.thresholds, threshold)
    return eng


def expected_accuracy(profile, exit_hist: dict) -> float:
    """Branch-accuracy-weighted accuracy of a realized exit histogram (the
    engine has no labels; the profile's per-branch accuracies stand in)."""
    total = sum(exit_hist.values())
    if total == 0:
        return float("nan")
    return sum(
        cnt * profile.branch_accuracy[int(stage) - 1]
        for stage, cnt in exit_hist.items()
    ) / total


def bench_closed_loop(
    params, cfg, topo, profile, ep, n_requests: int, rho: float, seed: int,
    rounds: int, threshold: float,
) -> dict:
    rng = np.random.default_rng(seed)
    prompts = [
        rng.integers(0, cfg.vocab_size, size=12).astype(np.int32)
        for _ in range(n_requests)
    ]
    caps = [
        float(sum(topo.mu[v] for v in topo.nodes_at_stage(h))) / profile.alpha[h - 1]
        for h in range(1, profile.num_stages + 1)
    ]
    rate = rho * min(caps)
    span = n_requests / rate

    by_scenario: dict[str, dict] = {}
    for name in SCENARIOS:
        runs: dict[str, dict] = {}
        for policy in ("static", "closed"):
            eng = build_engine(params, cfg, topo, profile, ep, threshold, seed)
            scn = get_scenario(name, eng.topo, p=eng.p, horizon=span, seed=seed)
            tele = Telemetry(eng.topo, TelemetryConfig(window_s=span / 8))
            ctrl = None
            if policy == "closed":
                # adapt_thresholds=False: the controller re-optimizes the
                # OFFLOADING strategy only.  The reduced model's live branch
                # confidences sit far from the synthetic exit profile's, so
                # letting Alg. 3 move thresholds against the synthetic table
                # shifts live exits unpredictably; pinning them also pins
                # accuracy exactly, isolating the routing win.  Calibrating
                # the exit profile from realized (conf, exit) telemetry is
                # recorded as a ROADMAP follow-on.
                ctrl = ReconfigController(
                    tele,
                    ControllerConfig(
                        interval=span / 10,
                        rounds=rounds,
                        drift_deadband=0.08,
                        adapt_thresholds=False,
                    ),
                )
            eng.rng = np.random.default_rng(seed + 7)
            stats = eng.serve(
                prompts,
                arrival_rate=rate,
                batch_size=4,
                gen_len=1,
                scenario=scn,
                controller=ctrl,
                telemetry=tele,
            )
            s = stats.summary()
            runs[policy] = {
                "mean_delay_s": s["mean_delay"],
                "delay_std_s": s["delay_std"],
                "p95_delay_s": s["p95_delay"],
                "num_completed": s["num_completed"],
                "num_reconfigs": s["num_reconfigs"],
                "resubmitted": s["resubmitted"],
                "exit_histogram": s["exit_histogram"],
                "expected_accuracy": expected_accuracy(
                    profile, s["exit_histogram"]
                ),
                "padded_row_frac": s["padded_row_frac"],
            }
            print(
                f"{name:9s} {policy:7s} mean {s['mean_delay']:.3f}s  "
                f"std {s['delay_std']:.3f}s  p95 {s['p95_delay']:.3f}s  "
                f"reconfigs {s['num_reconfigs']:2d}  "
                f"acc {runs[policy]['expected_accuracy']:.4f}"
            )
        st, cl = runs["static"], runs["closed"]
        by_scenario[name] = {
            "by_policy": runs,
            "mean_delay_improvement": st["mean_delay_s"] / cl["mean_delay_s"],
            "delay_std_improvement": st["delay_std_s"] / cl["delay_std_s"],
            "accuracy_delta": cl["expected_accuracy"] - st["expected_accuracy"],
        }
        print(
            f"{name:9s} closed/static: mean {by_scenario[name]['mean_delay_improvement']:.2f}x  "
            f"std {by_scenario[name]['delay_std_improvement']:.2f}x  "
            f"d_acc {by_scenario[name]['accuracy_delta']:+.4f}"
        )
    return {
        "workload": {
            "n_requests": n_requests,
            "arrival_rate": rate,
            "utilization": rho,
            "span_s": span,
            "threshold": threshold,
            "controller_rounds": rounds,
            "stage_capacities_tasks_per_s": caps,
        },
        "by_scenario": by_scenario,
    }


def bench_attribution(
    params, cfg, topo, profile, ep, n_requests: int, rho: float, seed: int,
    threshold: float,
) -> dict:
    """Traced serve under the static configuration: tail latency + measured
    vs DTO-EE-model delay attribution (the gate that the model the optimizer
    minimizes still describes the live engine)."""
    from repro.core.queueing import node_remaining_ratio
    from repro.obs import MetricsCollector, SpanTracer, attribution_report

    rng = np.random.default_rng(seed)
    prompts = [
        rng.integers(0, cfg.vocab_size, size=12).astype(np.int32)
        for _ in range(n_requests)
    ]
    caps = [
        float(sum(topo.mu[v] for v in topo.nodes_at_stage(h))) / profile.alpha[h - 1]
        for h in range(1, profile.num_stages + 1)
    ]
    rate = rho * min(caps)
    eng = build_engine(params, cfg, topo, profile, ep, threshold, seed)
    tracer, metrics = SpanTracer(), MetricsCollector()
    eng.rng = np.random.default_rng(seed + 7)
    stats = eng.serve(
        prompts,
        arrival_rate=rate,
        batch_size=4,
        gen_len=1,
        tracer=tracer,
        metrics=metrics,
    )
    s = stats.summary()
    # the same I_node the optimizer saw: remaining ratios under the live
    # thresholds broadcast onto nodes
    I_node = np.asarray(
        node_remaining_ratio(
            eng.topo,
            np.asarray(ep.evaluate(eng.thresholds).stage_remaining, np.float32),
        )
    )
    rep = attribution_report(
        tracer, eng.p, eng.topo, profile, I_node, stats
    )
    out = {
        "workload": {
            "n_requests": n_requests,
            "arrival_rate": rate,
            "utilization": rho,
            "threshold": threshold,
        },
        "tail_latency_s": {
            "p50": s["p50_delay"],
            "p95": s["p95_delay"],
            "p99": s["p99_delay"],
            "mean": s["mean_delay"],
        },
        "delay_components_s": s["delay_components"],
        "per_stage_components": s["per_stage_components"],
        "attribution": rep,
    }
    mc = rep["measured"]
    md = rep["model"]
    print(
        f"attribution: p50 {s['p50_delay']*1e3:.1f}ms  "
        f"p95 {s['p95_delay']*1e3:.1f}ms  p99 {s['p99_delay']*1e3:.1f}ms  "
        f"reconciles {rep['reconciles']} "
        f"(max residual {rep['max_residual_s']:.2e}s)"
    )
    print(
        f"  measured queue/compute/comms: "
        f"{mc['queue_s']*1e3:.2f}/{mc['compute_s']*1e3:.2f}/"
        f"{mc['comms_s']*1e3:.2f} ms   model: "
        f"{md['queue_s']*1e3:.2f}/{md['compute_s']*1e3:.2f}/"
        f"{md['comms_s']*1e3:.2f} ms"
    )
    for j, e in sorted(rep["per_node"].items()):
        if e["visits"]:
            print(
                f"  node {j}: sojourn measured {e['measured_sojourn_s']*1e3:7.2f}ms  "
                f"model {e['model_sojourn_s']*1e3:7.2f}ms  "
                f"rel_err {e.get('rel_error', float('nan')):+.2f}  "
                f"visits {e['visits']}"
            )
    return out


def bench_packing(
    params, cfg, topo, profile, ep, n_requests: int, gen_len: int, seed: int,
    threshold: float = 0.1,
) -> dict:
    """Threshold-aware packing vs FIFO at closed-loop load (all queued)."""
    rng = np.random.default_rng(seed)
    prompts = [
        rng.integers(0, cfg.vocab_size, size=int(rng.integers(8, 24))).astype(
            np.int32
        )
        for _ in range(n_requests)
    ]
    runs: dict[str, dict] = {}
    seqs: dict[str, dict] = {}
    for policy in ("fifo", "threshold"):
        eng = build_engine(params, cfg, topo, profile, ep, threshold, seed)
        eng.rng = np.random.default_rng(seed + 11)
        stats = eng.serve(
            prompts,
            arrival_rate=1e6,
            batch_size=8,
            gen_len=gen_len,
            decode_mode="cached",
            num_slots=8,
            batch_policy=policy,
        )
        s = stats.summary()
        seqs[policy] = stats.sequences_by_rid()
        runs[policy] = {
            "padded_row_frac": s["padded_row_frac"],
            "num_forward_rows": s["num_forward_rows"],
            "num_real_rows": s["num_real_rows"],
            "num_batches": s["num_batches"],
            "mean_delay_s": s["mean_delay"],
            "exit_histogram": s["exit_histogram"],
        }
        print(
            f"packing {policy:9s}: padded {s['padded_row_frac']*100:.2f}%  "
            f"rows {s['num_real_rows']}/{s['num_forward_rows']}  "
            f"batches {s['num_batches']}"
        )
    identical = seqs["fifo"] == seqs["threshold"]
    print(
        f"packing token-identical: {identical}  waste "
        f"{runs['fifo']['padded_row_frac']*100:.2f}% -> "
        f"{runs['threshold']['padded_row_frac']*100:.2f}%"
    )
    return {
        "workload": {
            "n_requests": n_requests,
            "gen_len": gen_len,
            "batch_size": 8,
            "threshold": threshold,
        },
        "by_policy": runs,
        "tokens_identical": identical,
    }


def bench_simulator(duration: float, arrival_scale: float, repeats: int) -> dict:
    """Same-timestamp event harvest: before/after tasks/s (satellite of the
    1e6 tasks/slot roadmap item; results must be identical)."""
    profile = RESNET101_PROFILE
    topo = build_edge_network(seed=0, profile=profile, arrival_rate_scale=arrival_scale)
    ep = synthetic_validation(seed=1, profile=profile)
    res = dto_ee.run_configuration_phase(topo, profile, ep, DtoHyperParams(rounds=30))
    p, thr = np.asarray(res.state.carry.p), res.state.thresholds
    out: dict[str, dict] = {}
    results = {}
    for label, coalesce in (("before", False), ("after", True)):
        walls = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            sim = simulator.simulate_slot(
                topo, profile, ep, p, thr, duration=duration, seed=3,
                coalesce=coalesce,
            )
            walls.append(time.perf_counter() - t0)
        wall = float(np.min(walls))
        results[label] = sim
        out[label] = {
            "tasks": sim.generated,
            "wall_s": wall,
            "tasks_per_s": sim.generated / wall,
        }
        print(
            f"simulator {label} (coalesce={coalesce}): "
            f"{out[label]['tasks_per_s']:,.0f} tasks/s ({sim.generated} tasks)"
        )
    a, b = results["before"], results["after"]
    identical = (
        a.mean_delay == b.mean_delay
        and a.completed == b.completed
        and np.array_equal(a.exit_fraction, b.exit_fraction)
    )
    print(f"simulator results identical: {identical}")
    return {
        "coalesce": out,
        "results_identical": identical,
        "speedup": out["after"]["tasks_per_s"] / out["before"]["tasks_per_s"],
    }


def validate_schema(payload: dict, smoke: bool) -> None:
    """The contract this benchmark (and ``bench-smoke``) is held to."""
    assert (
        "control" in payload
        and "attribution" in payload
        and "packing" in payload
        and "simulator" in payload
    )
    ctl = payload["control"]["by_scenario"]
    for name in SCENARIOS:
        for policy in ("static", "closed"):
            run = ctl[name]["by_policy"][policy]
            assert run["num_completed"] > 0
            assert np.isfinite(run["mean_delay_s"])
        assert ctl[name]["by_policy"]["closed"]["num_reconfigs"] > 0, (
            f"{name}: the closed loop never reconfigured"
        )
        assert abs(ctl[name]["accuracy_delta"]) <= 0.01, (
            f"{name}: closed-loop accuracy drifted "
            f"{ctl[name]['accuracy_delta']:+.4f} (> 1 point) from static"
        )
    at = payload["attribution"]
    assert at["attribution"]["reconciles"] is True, (
        "span component sums do not reconcile with reported delays "
        f"(max residual {at['attribution']['max_residual_s']:.2e}s)"
    )
    assert (
        at["tail_latency_s"]["p50"]
        <= at["tail_latency_s"]["p95"]
        <= at["tail_latency_s"]["p99"]
    )
    assert at["attribution"]["per_node"], "attribution covered no ES node"
    for comp in ("queue_s", "compute_s", "comms_s", "total_s"):
        assert np.isfinite(at["attribution"]["measured"][comp])
        assert np.isfinite(at["attribution"]["model"][comp])
    pk = payload["packing"]
    assert pk["tokens_identical"] is True, (
        "threshold-aware packing changed emitted tokens"
    )
    assert (
        pk["by_policy"]["threshold"]["padded_row_frac"]
        <= pk["by_policy"]["fifo"]["padded_row_frac"]
    ), "threshold packing increased padded-row waste"
    assert payload["simulator"]["results_identical"] is True
    if smoke:
        return
    # full-size acceptance: closed loop beats static on mean AND stddev
    # under the burst / slowdown / failure scenarios, and packing strictly
    # reduces waste
    for name in MUST_WIN:
        assert ctl[name]["mean_delay_improvement"] > 1.0, (
            f"{name}: closed loop did not improve mean delay "
            f"({ctl[name]['mean_delay_improvement']:.3f}x)"
        )
        assert ctl[name]["delay_std_improvement"] > 1.0, (
            f"{name}: closed loop did not improve delay stddev "
            f"({ctl[name]['delay_std_improvement']:.3f}x)"
        )
    assert (
        pk["by_policy"]["threshold"]["padded_row_frac"]
        < pk["by_policy"]["fifo"]["padded_row_frac"]
    ), "threshold packing did not strictly reduce padded-row waste"


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="BENCH_control.json")
    ap.add_argument("--n-requests", type=int, default=96)
    ap.add_argument(
        "--rho",
        type=float,
        default=0.55,
        help="offered load as a fraction of the bottleneck stage capacity",
    )
    ap.add_argument("--controller-rounds", type=int, default=15)
    ap.add_argument(
        "--threshold",
        type=float,
        default=0.12,
        help="initial exit thresholds (sensitive range of the reduced model)",
    )
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="tiny workload; validate schema + invariants, skip win gates",
    )
    args = ap.parse_args()
    enable_compile_cache()

    if args.smoke:
        args.n_requests = 32
        args.controller_rounds = 8
    sim_kw = (
        dict(duration=0.6, arrival_scale=10.0, repeats=2)
        if args.smoke
        else dict(duration=3.0, arrival_scale=20.0, repeats=3)
    )
    pack_n, pack_gen = (16, 6) if args.smoke else (32, 12)

    cfg = _cfg()
    params = model_lib.init_params(jax.random.key(0), cfg)
    profile = profile_from_arch(cfg)
    # capacity_scale drops Jetson-class service times into the ~10-50 ms
    # band, so slots, decision times (rounds x 2 ms), and telemetry windows
    # sit at the paper's timescale relative to each other
    topo = build_edge_network(
        seed=args.seed,
        profile=profile,
        spec=NetworkSpec(num_eds=4, es_per_stage=(2, 3)),
        capacity_scale=0.005,
    )
    ep = synthetic_validation(seed=args.seed + 1, profile=profile)

    payload = {
        "control": bench_closed_loop(
            params, cfg, topo, profile, ep, args.n_requests, args.rho,
            args.seed, args.controller_rounds, args.threshold,
        ),
        "attribution": bench_attribution(
            params, cfg, topo, profile, ep, args.n_requests, args.rho,
            args.seed, args.threshold,
        ),
        "packing": bench_packing(
            params, cfg, topo, profile, ep, pack_n, pack_gen, args.seed
        ),
        "simulator": bench_simulator(**sim_kw),
        "meta": {
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "platform": platform.platform(),
            "smoke": args.smoke,
        },
    }
    validate_schema(payload, smoke=args.smoke)
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
