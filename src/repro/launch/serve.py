"""Collaborative serving driver: ``python -m repro.launch.serve --arch <id>``.

Boots the model at its published widths (``--reduced`` selects the tiny
``ArchConfig.reduced()`` sibling for CPU runs), partitions it into stages
over a small edge topology, runs DTO-EE configuration phases between time
slots, and serves Poisson request streams through the REAL model with live
early-exit confidences.  Weights are random, drawn from ``--seed``.

Two control-plane modes:

  * default — the paper's slotted loop: one configuration phase BEFORE each
    slot's serve, capacities re-randomized between slots;
  * ``--reconfig-interval R`` (and/or ``--scenario``) — the ONLINE loop: one
    long serve during which telemetry feeds a ReconfigController that
    re-optimizes p/thresholds every R simulated seconds while a scenario
    perturbs the live environment.

Observability flags (see src/repro/serving/README.md, "Observability"):

  * ``--trace-out trace.json`` — attach a SpanTracer and write the serve as
    Chrome-trace/Perfetto JSON (open at https://ui.perfetto.dev).  Slotted
    mode traces the LAST slot (one trace file, one serve).
  * ``--stats-report report.json`` — write the machine-readable
    ``ServeStats.report()`` (summary + the host-span record: per-span
    count, total and self seconds, stage batches, compiles by span +
    per-request delay decomposition + metrics registry snapshot) of the
    traced serve.
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.configs import get_config
from repro.control import (
    ControllerConfig,
    ReconfigController,
    SCENARIO_NAMES,
    Telemetry,
    TelemetryConfig,
    get_scenario,
)
from repro.core import dto_ee
from repro.core.profiles import profile_from_arch
from repro.core.thresholds import synthetic_validation
from repro.core.topology import build_edge_network, NetworkSpec, with_resampled_capacities
from repro.core.types import DtoHyperParams
from repro.data import RequestConfig, poisson_requests
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as model_lib
from repro.serving import CollaborativeEngine


def _observers(args):
    """(tracer, metrics) when an observability flag asked for them."""
    if args.trace_out is None and args.stats_report is None:
        return None, None
    from repro.obs import MetricsCollector, SpanTracer

    return SpanTracer(), MetricsCollector()


def _write_obs(args, stats) -> None:
    if args.trace_out and stats.trace is not None:
        from repro.obs import write_chrome_trace

        write_chrome_trace(args.trace_out, stats.trace)
        print(f"trace: {args.trace_out}", flush=True)
    if args.stats_report:
        with open(args.stats_report, "w") as f:
            json.dump(stats.report(), f, indent=1)
        print(f"stats report: {args.stats_report}", flush=True)


def build_engine(cfg, seed: int, num_eds: int) -> CollaborativeEngine:
    """Random weights from ``seed`` on a random edge topology of ``num_eds``
    end devices and 3-4 replicas per stage, with a synthetic exit profile."""
    params = jax.jit(model_lib.init_params, static_argnums=1)(
        jax.random.key(seed), cfg
    )
    profile = profile_from_arch(cfg)
    topo = build_edge_network(
        seed=seed,
        profile=profile,
        spec=NetworkSpec(num_eds=num_eds, es_per_stage=(3, 4)),
    )
    exit_profile = synthetic_validation(seed=seed + 1, profile=profile)
    return CollaborativeEngine(
        params, cfg, topo, profile, exit_profile, DtoHyperParams(), seed=seed
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument(
        "--reduced",
        action="store_true",
        help="serve the arch's tiny smoke-test sibling (d_model 128, vocab "
        "512) instead of its published widths — for CPU runs",
    )
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--slot-seconds", type=float, default=5.0)
    ap.add_argument("--requests-per-slot", type=int, default=24)
    ap.add_argument("--num-eds", type=int, default=8)
    ap.add_argument(
        "--batch-size",
        type=int,
        default=8,
        help="per-replica micro-batch width for the data plane",
    )
    ap.add_argument(
        "--gen-len",
        type=int,
        default=1,
        help="tokens decoded per request (1 = single-shot classification)",
    )
    ap.add_argument(
        "--decode-mode",
        choices=("cached", "stateless"),
        default=None,
        help="cached = slot-resident KV caches + continuous batching; "
        "stateless = re-prefill baseline (default: cached iff gen-len > 1)",
    )
    ap.add_argument(
        "--num-slots",
        type=int,
        default=None,
        help="cache slots per replica ring (default: 2 * batch size)",
    )
    ap.add_argument(
        "--cache-layout",
        choices=("dense", "paged"),
        default="dense",
        help="slot-store memory layout: dense worst-case arenas, or paged "
        "block pools with prompt-prefix sharing (token-identical outputs)",
    )
    ap.add_argument(
        "--block-size",
        type=int,
        default=16,
        help="tokens per KV block under --cache-layout paged",
    )
    ap.add_argument(
        "--num-blocks",
        type=int,
        default=None,
        help="KV blocks per replica pool (default: the dense footprint)",
    )
    ap.add_argument(
        "--no-prefix-sharing",
        action="store_true",
        help="disable prompt-prefix block sharing under the paged layout",
    )
    ap.add_argument(
        "--reconfig-interval",
        type=float,
        default=None,
        metavar="SECONDS",
        help="enable the ONLINE control plane: run one long serve and let a "
        "ReconfigController re-optimize p/thresholds from live telemetry "
        "every SECONDS of simulated time (atomic install after the "
        "decision time; hysteresis skips quiet environments)",
    )
    ap.add_argument(
        "--reconfig-rounds",
        type=int,
        default=30,
        help="DTO-EE rounds per online configuration phase (decision time = "
        "rounds x 2 ms)",
    )
    ap.add_argument(
        "--scenario",
        choices=SCENARIO_NAMES,
        default=None,
        help="perturb the live environment mid-serve: 'burst' (a subset of "
        "EDs floods 3x), 'slowdown' (the busiest stage-1 replica throttles "
        "to 15%% of nameplate), 'link' (its uplinks degrade 10x), 'failure' "
        "(it fail-stops; tasks re-execute from their EDs — needs "
        "--gen-len 1).  Implies the online serve mode.",
    )
    ap.add_argument(
        "--batch-policy",
        choices=("fifo", "threshold"),
        default="fifo",
        help="batch formation: 'fifo' (arrival order), or 'threshold' — "
        "threshold-aware packing that groups rows by predicted exit stage "
        "(confidence history vs the live DTO-EE thresholds) and trims "
        "batches to exact padded shapes; token-identical outputs, lower "
        "padded-row waste",
    )
    ap.add_argument(
        "--trace-out",
        default=None,
        metavar="PATH",
        help="write a Chrome-trace/Perfetto JSON of the serve (the last "
        "slot in slotted mode) to PATH",
    )
    ap.add_argument(
        "--stats-report",
        default=None,
        metavar="PATH",
        help="write the machine-readable ServeStats.report() JSON (summary "
        "+ host-span record + delay decomposition + metrics) of the traced "
        "serve to PATH",
    )
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    engine = build_engine(cfg, args.seed, args.num_eds)

    rng = np.random.default_rng(args.seed)
    rcfg = RequestConfig(
        arrival_rate=args.requests_per_slot / args.slot_seconds, seed=args.seed
    )
    serve_kw = dict(
        batch_size=args.batch_size,
        gen_len=args.gen_len,
        decode_mode=args.decode_mode,
        num_slots=args.num_slots,
        cache_layout=args.cache_layout,
        block_size=args.block_size,
        num_blocks=args.num_blocks,
        prefix_sharing=not args.no_prefix_sharing,
        batch_policy=args.batch_policy,
    )

    if args.reconfig_interval is not None or args.scenario is not None:
        # ONLINE mode: one long serve, closed-loop reconfiguration mid-flight
        engine.configuration_phase()
        horizon = args.slots * args.slot_seconds
        reqs = poisson_requests(cfg, rcfg, horizon)
        prompts = [tok for _, tok in reqs][: args.requests_per_slot * args.slots]
        span = len(prompts) / rcfg.arrival_rate
        telemetry = Telemetry(
            engine.topo, TelemetryConfig(window_s=args.slot_seconds / 2)
        )
        controller = None
        if args.reconfig_interval is not None:
            controller = ReconfigController(
                telemetry,
                ControllerConfig(
                    interval=args.reconfig_interval, rounds=args.reconfig_rounds
                ),
            )
        scenario = None
        if args.scenario is not None:
            scenario = get_scenario(
                args.scenario, engine.topo, p=engine.p, horizon=span,
                seed=args.seed,
            )
        tracer, metrics = _observers(args)
        stats = engine.serve(
            prompts,
            duration=horizon,
            arrival_rate=rcfg.arrival_rate,
            scenario=scenario,
            controller=controller,
            telemetry=telemetry,
            tracer=tracer,
            metrics=metrics,
            **serve_kw,
        )
        s = stats.summary()
        cap = ", ".join(
            f"{v}: {mu:.1f}" for v, mu in sorted(s["capacity_estimates"].items())
        )
        print(
            f"online: {s['num_completed']} done  "
            f"mean_delay {s['mean_delay']*1e3:.1f}ms  "
            f"std {s['delay_std']*1e3:.1f}ms  p95 {s['p95_delay']*1e3:.1f}ms  "
            f"reconfigs {s['num_reconfigs']}  resubmitted {s['resubmitted']}  "
            f"padded waste {s['padded_row_frac']*100:.1f}%  "
            f"exits {s['exit_histogram']}",
            flush=True,
        )
        print(f"capacity estimates (GFLOP/s): {cap}")
        _write_obs(args, stats)
        print("done")
        return

    stats = None
    for slot in range(args.slots):
        engine.configuration_phase()
        reqs = poisson_requests(cfg, rcfg, args.slot_seconds)
        prompts = [tok for _, tok in reqs][: args.requests_per_slot]
        # observability rides on the LAST slot only: one trace, one serve
        tracer, metrics = (
            _observers(args) if slot == args.slots - 1 else (None, None)
        )
        stats = engine.serve(
            prompts,
            duration=args.slot_seconds,
            arrival_rate=rcfg.arrival_rate,
            tracer=tracer,
            metrics=metrics,
            **serve_kw,
        )
        s = stats.summary()
        paged_info = (
            f"  blocks {s['block_occupancy_peak']*100:.0f}% peak  "
            f"prefix hits {s['prefix_hit_rate']*100:.0f}%"
            if args.cache_layout == "paged"
            else ""
        )
        print(
            f"slot {slot}: {s['num_completed']} done  "
            f"{s['generated_tokens']} tokens  "
            f"mean_delay {s['mean_delay']*1e3:.1f}ms  "
            f"p95 {s['p95_delay']*1e3:.1f}ms  "
            f"padded waste {s['padded_row_frac']*100:.1f}%  "
            f"exits {s['exit_histogram']}  thresholds {engine.thresholds}"
            f"{paged_info}",
            flush=True,
        )
        # dynamic environment: replicas throttle between slots (paper §4.3)
        engine.update_topology(with_resampled_capacities(engine.topo, rng))

    if stats is not None:
        _write_obs(args, stats)
    print("done")


if __name__ == "__main__":
    main()
