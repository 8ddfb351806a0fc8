"""End-to-end serving throughput: micro-batched data plane vs batch-1.

Measures wall-clock tokens/s and simulated mean/p95 response delay of
``CollaborativeEngine.serve`` at micro-batch sizes {1, 8, 32} on one fixed
workload (same prompts, same arrival process, same thresholds), checks that
every batch size makes identical exit decisions, runs a tracing-overhead A/B
(span tracer on vs off, identical seeds: bitwise-identical results, <3%
tokens/s budget), and times the vectorized discrete-event simulator on a
~1e4-task slot.  Results land in ``BENCH_serving.json`` so the perf
trajectory is tracked PR over PR.

    PYTHONPATH=src python benchmarks/serving_throughput.py [--out BENCH_serving.json]
"""
from __future__ import annotations

import argparse
import json
import platform
import time

import numpy as np

import jax

from repro.configs import get_config
from repro.core import simulator
from repro.core.profiles import profile_from_arch
from repro.core.thresholds import synthetic_validation
from repro.core.topology import NetworkSpec, build_edge_network
from repro.core.types import DtoHyperParams, RESNET101_PROFILE
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as model_lib
from repro.serving import CollaborativeEngine


def build_engine(seed: int = 0) -> CollaborativeEngine:
    """A small-but-real staged model: per-dispatch overhead vs per-row compute
    at a ratio representative of a serving host driving an accelerator."""
    cfg = get_config("stablelm-1.6b").reduced(
        vocab_size=128,
        d_model=64,
        d_ff=128,
        num_heads=2,
        num_kv_heads=2,
        head_dim=32,
    )
    params = model_lib.init_params(jax.random.key(0), cfg)
    profile = profile_from_arch(cfg)
    topo = build_edge_network(
        seed=seed, profile=profile, spec=NetworkSpec(num_eds=4, es_per_stage=(2, 2))
    )
    ep = synthetic_validation(seed=1, profile=profile)
    eng = CollaborativeEngine(
        params, cfg, topo, profile, ep, DtoHyperParams(rounds=20), seed=seed
    )
    eng.configuration_phase()
    return eng


def bench_engine(
    eng: CollaborativeEngine,
    batch_sizes: tuple[int, ...],
    n_requests: int,
    prompt_len: int,
    arrival_rate: float,
    serve_seed: int = 123,
    repeats: int = 5,
) -> dict:
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(0, eng.cfg.vocab_size, size=prompt_len).astype(np.int32)
        for _ in range(n_requests)
    ]
    per_bs: dict[str, dict] = {}
    exits: dict[int, dict] = {}
    for bs in batch_sizes:
        eng.rng = np.random.default_rng(serve_seed)
        eng.serve(prompts, arrival_rate=arrival_rate, batch_size=bs)  # warmup/compile
        walls = []
        for _ in range(repeats):
            eng.rng = np.random.default_rng(serve_seed)
            t0 = time.perf_counter()
            stats = eng.serve(prompts, arrival_rate=arrival_rate, batch_size=bs)
            walls.append(time.perf_counter() - t0)
        wall = float(np.median(walls))  # median-of-N: robust to box noise
        s = stats.summary()
        exits[bs] = stats.by_rid()
        per_bs[str(bs)] = {
            "wall_s": wall,
            "tokens_per_s": s["num_completed"] / wall,
            "num_completed": s["num_completed"],
            "mean_delay_s": s["mean_delay"],
            "p95_delay_s": s["p95_delay"],
            "num_batches": s["num_batches"],
            "num_forward_rows": s["num_forward_rows"],
            "num_real_rows": s["num_real_rows"],
            "padded_row_frac": s["padded_row_frac"],
            "sim_tokens_per_s": s["sim_tokens_per_s"],
        }
        print(
            f"batch {bs:3d}: {per_bs[str(bs)]['tokens_per_s']:8.1f} tok/s  "
            f"wall {wall:.3f}s  batches {s['num_batches']:4d}  "
            f"mean delay {s['mean_delay'] * 1e3:7.1f} ms  "
            f"p95 {s['p95_delay'] * 1e3:7.1f} ms  "
            f"padded waste {s['padded_row_frac'] * 100:4.1f}% "
            f"({s['num_forward_rows'] - s['num_real_rows']}/{s['num_forward_rows']} rows)"
        )
    b0 = min(batch_sizes)
    identical = all(exits[bs] == exits[b0] for bs in batch_sizes)
    bmax = max(batch_sizes)
    speedup = (
        per_bs[str(bmax)]["tokens_per_s"] / per_bs[str(b0)]["tokens_per_s"]
    )
    print(f"exit decisions identical across batch sizes: {identical}")
    print(f"speedup batch {bmax} vs {b0}: {speedup:.2f}x")
    return {
        "workload": {
            "n_requests": n_requests,
            "prompt_len": prompt_len,
            "arrival_rate": arrival_rate,
        },
        "by_batch_size": per_bs,
        "exits_identical": identical,
        "speedup_maxbatch_vs_1": speedup,
    }


def bench_tracing(
    eng: CollaborativeEngine,
    n_requests: int,
    prompt_len: int,
    arrival_rate: float,
    batch_size: int = 8,
    serve_seed: int = 123,
    repeats: int = 5,
    budget_frac: float = 0.03,
) -> dict:
    """Tracing-overhead A/B: tracer on vs off, identical seeds.

    With observers disabled ``build_stream`` returns ``None`` and every
    instrumentation site is a single ``is not None`` test, so the disabled
    path must be BITWISE identical to the pre-observability engine — checked
    here on exit decisions and delays.  With the tracer attached the budget
    is <3% tokens/s regression; runs are interleaved and min-of-N (the
    noise-robust wall estimator — medians on a shared box swing more than
    the effect being measured).  The full tracer+metrics stack is recorded
    as an extra row, ungated.

    The default A/B prompt length (32) is deliberately longer than the main
    throughput sweep's: per-event tracing cost is fixed, so the 4-token
    workload — a dispatch-overhead stress test — would measure tracing
    against artificially tiny per-batch compute rather than representative
    stage work.
    """
    from repro.obs import MetricsCollector, SpanTracer

    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(0, eng.cfg.vocab_size, size=prompt_len).astype(np.int32)
        for _ in range(n_requests)
    ]
    modes = ("off", "tracer", "tracer+metrics")

    def run(mode: str):
        eng.rng = np.random.default_rng(serve_seed)
        tracer = SpanTracer() if mode != "off" else None
        metrics = MetricsCollector() if mode == "tracer+metrics" else None
        t0 = time.perf_counter()
        stats = eng.serve(
            prompts,
            arrival_rate=arrival_rate,
            batch_size=batch_size,
            tracer=tracer,
            metrics=metrics,
        )
        return time.perf_counter() - t0, stats

    run("off")  # warmup/compile
    walls: dict[str, list[float]] = {m: [] for m in modes}
    last: dict[str, object] = {}
    for _ in range(repeats):
        for m in modes:  # interleaved: drift hits every mode equally
            w, last[m] = run(m)
            walls[m].append(w)
    wall = {m: float(np.min(walls[m])) for m in modes}
    # disabled path == traced path: same exits, same delays, bit for bit
    identical = all(
        last["off"].by_rid() == last[m].by_rid()
        and all(a == b for a, b in zip(last["off"].delays, last[m].delays))
        for m in modes[1:]
    )
    n_done = last["off"].summary()["num_completed"]
    overhead = {m: wall[m] / wall["off"] - 1.0 for m in modes[1:]}
    res = {
        "workload": {
            "n_requests": n_requests,
            "prompt_len": prompt_len,
            "batch_size": batch_size,
            "repeats": repeats,
        },
        "by_mode": {
            m: {
                "wall_s": wall[m],
                "tokens_per_s": n_done / wall[m],
                "overhead_frac": overhead.get(m, 0.0),
            }
            for m in modes
        },
        "budget_frac": budget_frac,
        "within_budget": overhead["tracer"] <= budget_frac,
        "results_bitwise_identical": identical,
        "spans_recorded": sum(
            len(v) for v in last["tracer"].trace.spans.values()
        ),
    }
    for m in modes:
        print(
            f"tracing A/B {m:15s}: {n_done / wall[m]:8.1f} tok/s  "
            f"overhead {overhead.get(m, 0.0) * 100:+.2f}%"
        )
    print(
        f"tracing A/B: bitwise identical {identical}  "
        f"spans {res['spans_recorded']}"
    )
    assert identical, "traced serve diverged from untraced serve"
    if not res["within_budget"]:
        print(
            f"WARNING: tracer overhead {overhead['tracer'] * 100:.2f}% "
            f"exceeds {budget_frac * 100:.0f}% budget"
        )
    return res


def bench_simulator(arrival_rate_scale: float = 12.0, duration: float = 20.0) -> dict:
    """Vectorized discrete-event simulator on a heavily loaded slot."""
    profile = RESNET101_PROFILE
    topo = build_edge_network(
        seed=0, profile=profile, arrival_rate_scale=arrival_rate_scale
    )
    ep = synthetic_validation(seed=1, profile=profile)
    p = np.ones(topo.num_edges, np.float64)
    thr = np.full(ep.num_early_branches, 0.8)
    t0 = time.perf_counter()
    res = simulator.simulate_slot(
        topo, profile, ep, p, thr, duration=duration, seed=3
    )
    wall = time.perf_counter() - t0
    out = {
        "arrival_rate_scale": arrival_rate_scale,
        "duration_s": duration,
        "generated": res.generated,
        "completed": res.completed,
        "wall_s": wall,
        "tasks_per_s": res.completed / wall,
        "mean_delay_s": res.mean_delay,
    }
    print(
        f"simulator: {res.completed} tasks in {wall:.2f}s "
        f"({out['tasks_per_s']:.0f} tasks/s)"
    )
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="BENCH_serving.json")
    ap.add_argument("--n-requests", type=int, default=256)
    ap.add_argument("--prompt-len", type=int, default=4)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument(
        "--ab-prompt-len",
        type=int,
        default=32,
        help="prompt length for the tracing-overhead A/B (longer than the "
        "throughput sweep's: fixed per-event tracing cost is measured "
        "against representative per-batch compute)",
    )
    ap.add_argument(
        "--batch-sizes", type=int, nargs="+", default=[1, 8, 32]
    )
    ap.add_argument(
        "--arrival-rate",
        type=float,
        default=1e6,
        help="Poisson arrival rate; high = closed-loop (all requests queued)",
    )
    args = ap.parse_args()
    enable_compile_cache()

    eng = build_engine()
    engine_res = bench_engine(
        eng,
        tuple(args.batch_sizes),
        args.n_requests,
        args.prompt_len,
        args.arrival_rate,
        repeats=args.repeats,
    )
    tracing_res = bench_tracing(
        eng,
        args.n_requests,
        args.ab_prompt_len,
        args.arrival_rate,
        repeats=args.repeats,
    )
    sim_res = bench_simulator()
    payload = {
        "engine": engine_res,
        "tracing_overhead": tracing_res,
        "simulator": sim_res,
        "meta": {
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "platform": platform.platform(),
        },
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
