"""One run of one cell: set-up, warm-up, the measured window, the readers,
and the comparison with the reference.

The window drives ``CollaborativeEngine.serve`` as ``repro.launch.serve``
builds it (an edge topology of ``num_eds`` devices and 3-4 replicas per
stage, one DTO-EE configuration phase), with the benchmark's weights.  It
is a sequence of ``serve()`` rounds run back to back; it closes at the end
of the round that crosses ``seconds``, and every metric covers all of its
rounds.
"""
from __future__ import annotations

import contextlib
import gc
import os
import shutil
import sys
from time import perf_counter

import numpy as np

from benchlib import check, recorder as rec_lib, spec, traffic as tr

CACHE_DIR = spec.ROOT / ".jax_cache"  # the program's own checkout default
TRACE_DIR = spec.ROOT / ".bench_trace" / "profile"


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts JAX's traces and backend compiles while ``active``."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.active = False
        self.traces = self.compiles = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if not self.active:
            return
        if event == self.TRACE:
            self.traces += 1
        elif event == self.COMPILE:
            self.compiles += 1
            self.compile_s += duration


def use_compile_cache() -> str:
    """JAX's persistent cache at a fixed directory inside the checkout,
    handed to the program the way it takes one (the environment), with
    every program kept, however fast it compiled."""
    CACHE_DIR.mkdir(parents=True, exist_ok=True)  # JAX writes no entry into a missing one
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return enable_compile_cache()


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax

    devices = jax.devices()
    dev = devices[0]
    info = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    log(f"device: platform={dev.platform} device_kind={dev.device_kind} count={len(devices)}")
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"no TPU: JAX found platform {dev.platform!r}; this benchmark measures only on a TPU")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return info


def build_engine(cfg, params, traffic: dict):
    """The engine as ``repro.launch.serve.build_engine`` builds it, on the
    deployment's topology and with the benchmark's weights."""
    from repro.core.profiles import profile_from_arch
    from repro.core.thresholds import synthetic_validation
    from repro.core.topology import NetworkSpec, build_edge_network
    from repro.core.types import DtoHyperParams
    from repro.serving import CollaborativeEngine

    seed = int(traffic["deployment_seed"])
    profile = profile_from_arch(cfg)
    topo = build_edge_network(
        seed=seed,
        profile=profile,
        spec=NetworkSpec(num_eds=int(traffic["num_eds"]), es_per_stage=tuple(traffic["es_per_stage"])),
    )
    exit_profile = synthetic_validation(seed=seed + 1, profile=profile)
    return CollaborativeEngine(params, cfg, topo, profile, exit_profile, DtoHyperParams(), seed=seed)


def serve_round(engine, recorder, traffic, prompts, stream: int, index: int):
    engine.rng = tr.engine_rng(traffic, stream, index)
    recorder.begin_round(index if stream == tr.MEASURED else -1 - index, prompts)
    return engine.serve(prompts, telemetry=recorder, **tr.serve_kwargs(traffic))


def sweep_shapes(engine, cfg, traffic) -> int:
    """Call every stage program at every (stage, length, batch) shape the
    traffic can make, with the argument types the engine passes: device
    arrays into stage 1 and the heads, host arrays into later stages.
    Covers cached decoding only, the one mode a cell serves."""
    import jax
    import jax.numpy as jnp

    if traffic["decode_mode"] != "cached":
        raise ValueError(f"the shape sweep covers cached decoding, not {traffic['decode_mode']!r}")
    P = engine.programs
    H = cfg.num_stages
    ml = tr.max_len(traffic)
    n_slots = traffic["num_slots"]
    host_dtype = np.asarray(jnp.zeros((1,), cfg.dtype)).dtype
    stores = {h: P.init_slot_caches(h, n_slots + 1, ml) for h in range(1, H + 1)}
    calls = 0

    def heads(h, out):
        nonlocal calls
        if h in cfg.exit_stages:
            P.exit_head(h, out)
            calls += 1
        if h == H:
            P.final_head(out)
            calls += 1

    for B in tr.batch_buckets(int(traffic["batch_size"])):
        slots = np.full((B,), n_slots, np.int32)
        for S, decode in [(S, False) for S in tr.lengths(traffic)] + [(1, True)]:
            x1 = P.embed(np.zeros((B, S), np.int32))
            for h in range(1, H + 1):
                x_in = x1 if h == 1 else np.zeros((B, S, cfg.d_model), host_dtype)
                if decode:
                    out, stores[h] = P.stage_decode(h, x_in, stores[h], slots)
                else:
                    out, caches = P.stage_prefill(h, x_in, ml)
                    stores[h] = P.slot_write(h, stores[h], caches, slots)
                    calls += 1
                calls += 2
                heads(h, out)
            jax.block_until_ready(out)
    jax.block_until_ready(stores)
    return calls


def peak_bytes() -> int | None:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def percentile_ms(values: np.ndarray, q: float) -> float:
    return float(np.percentile(values, q)) * 1e3


def run_rounds(engine, recorder, traffic, cfg, seed, seconds, trace=False, min_requests=0):
    """Measured rounds back to back until ``seconds`` have passed at the end
    of a round (and ``min_requests`` were sent).  Returns ({round: (prompts,
    sequences by rid)}, rounds, window seconds)."""
    import jax

    served = {}
    rounds = 0
    span = jax.profiler.TraceAnnotation("bench.window") if trace else contextlib.nullcontext()
    with span:
        t0 = perf_counter()
        while True:
            prompts = tr.round_prompts(traffic, cfg.vocab_size, seed, tr.MEASURED, rounds)
            with jax.profiler.TraceAnnotation("bench.round") if trace else contextlib.nullcontext():
                stats = serve_round(engine, recorder, traffic, prompts, tr.MEASURED, rounds)
            served[rounds] = (prompts, stats.sequences_by_rid())
            rounds += 1
            sent = rounds * len(prompts)
            if perf_counter() - t0 >= seconds and sent >= min_requests:
                break
        window_s = perf_counter() - t0
    return served, rounds, window_s


def collect_served(served, recorder, cfg, traffic):
    """The window's finished requests as ``check.Served`` by (round, rid),
    their total lengths, and the counts of failed and attempted requests:
    a request fails when it did not finish, or its tokens or head readings
    are missing or out of the vocabulary."""
    by_key, lengths = {}, {}
    failed = attempted = 0
    gen_len = int(traffic["gen_len"])
    for r, (prompts, seqs) in served.items():
        for rid, p in enumerate(prompts):
            attempted += 1
            if rid not in seqs:
                failed += 1
                continue
            stage, toks = seqs[rid]
            heads = recorder.heads.get((r, rid), [])
            ok = (
                (len(toks) == gen_len or (stage < cfg.num_stages and 1 <= len(toks) <= gen_len))
                and len(heads) == len(toks)
                and all(0 <= t < cfg.vocab_size for t in toks)
            )
            if not ok:
                failed += 1
                continue
            by_key[(r, rid)] = check.Served(prompt=p, tokens=toks, exit_stage=stage, heads=heads)
            lengths[(r, rid)] = len(p) + len(toks)
    return by_key, lengths, failed, attempted


def reference_numbers(cell, conf, params, chosen, thresholds) -> dict:
    """The compared numbers of ``chosen`` against the float32 reference."""
    tokens, positions, gather = check.inputs(chosen, conf)
    reading = cell.reference.readings(params, conf, tokens, positions, gather)
    return check.compare(chosen, reading, conf, thresholds, cell.limits)


def control_numbers(cell, conf, params, chosen, thresholds, precision: str) -> dict:
    """The same numbers for the reference computed in ``precision`` (the
    control), read against the float32 reference."""
    tokens, positions, gather = check.inputs(chosen, conf)
    low = cell.reference.readings(params, conf, tokens, positions, gather, precision=precision)
    top = {h: r["argmax"] for h, r in low.items()}
    ref = cell.reference.readings(params, conf, tokens, positions, top)
    return check.control_numbers(chosen, ref, low, conf, thresholds, cell.limits)


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *, t_start: float,
             require_tpu: bool = True, reduced: dict | None = None, wrap_programs=None) -> dict:
    """One run; returns the result line's object.  For tests on the CPU:
    ``reduced`` runs ``cfg.reduced(**reduced)`` instead of the published
    widths, and ``wrap_programs(programs, cfg)`` may replace stage
    programs to plant a fault."""
    import jax

    from benchlib import weights

    device = device_info(cell.chips, require_tpu)
    cache_dir = use_compile_cache()
    log(f"compile cache: {cache_dir}")
    counter = CompileCounter()
    traffic = cell.traffic
    cfg = spec.arch_config(cell.config)
    if reduced is not None:
        cfg = cfg.reduced(**reduced)
    log(f"cell {cell.name}: config {cfg.name} {cfg.num_layers}L d {cfg.d_model} "
        f"{cfg.num_heads}H/{cfg.num_kv_heads}KV hd {cfg.head_dim} d_ff {cfg.d_ff} vocab "
        f"{cfg.vocab_size}; traffic {traffic['name']}; seed {seed}")

    # ---- set-up ---------------------------------------------------------
    counter.active = True
    abstract = weights.abstract_tree(cfg)
    params = jax.block_until_ready(weights.make_params(cfg, seed, abstract))
    engine = build_engine(cfg, params, traffic)
    engine.configuration_phase()
    recorder = rec_lib.Recorder(engine.programs, cfg.num_stages, cfg.exit_stages, annotate=trace)
    if wrap_programs is not None:
        wrap_programs(engine.programs, cfg)
    log(f"thresholds: DTO-EE configuration phase: {list(map(float, engine.thresholds))}")
    warm = tr.warmup_prompts(traffic, cfg.vocab_size, seed)
    serve_round(engine, recorder, traffic, warm, tr.WARMUP, 1)
    calls = sweep_shapes(engine, cfg, traffic)
    setup_s = perf_counter() - t_start
    log(f"setup: {setup_s:.3f} s, {counter.compiles} backend compiles "
        f"({counter.compile_s:.1f} s), {counter.traces} traces; shape sweep {calls} calls")

    # ---- the window -----------------------------------------------------
    counter.active = False
    counter.traces = counter.compiles = 0
    counter.compile_s = 0.0
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    counter.active = True
    served, rounds, window_s = run_rounds(engine, recorder, traffic, cfg, seed, seconds, trace)
    counter.active = False
    if trace:
        jax.profiler.stop_trace()
    memory = peak_bytes()
    device["memory_peak_bytes"] = memory
    thresholds = np.array(engine.thresholds, np.float64)
    in_window = set(range(rounds))
    ttft, itl = recorder.ttft_s(in_window), recorder.itl_s(in_window)
    n_tokens = recorder.tokens(in_window)
    log(f"window: {rounds} rounds, {window_s:.4f} s, {len(recorder.requests(in_window))} requests, "
        f"{n_tokens} tokens; compiles in window: {counter.compiles} backend, {counter.traces} traces")
    log(f"ttft: n={ttft.size} p50 {percentile_ms(ttft, 50):.3f} ms p95 {percentile_ms(ttft, 95):.3f} ms")
    if itl.size:
        log(f"itl: n={itl.size} p50 {percentile_ms(itl, 50):.3f} ms p95 {percentile_ms(itl, 95):.3f} ms")
    log(f"memory_peak_bytes: {memory}")
    e2e = {
        "tokens_per_s": n_tokens / window_s,
        "ttft_p95_ms": percentile_ms(ttft, 95),
        "itl_p95_ms": percentile_ms(itl, 95) if itl.size else None,
        "setup_s": setup_s,
    }

    result = {"correct": False, "attempted": 0, "failed": 0, "metrics": {}, "device": device}
    if trace:
        from benchlib import metrics as metrics_lib
        from benchlib import trace as trace_lib

        t_read = perf_counter()
        summary = trace_lib.reduce_xplane(trace_lib.find_xplane(str(TRACE_DIR)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        recorder.index_decode_positions()
        ctx = metrics_lib.Context(
            cell=cell, recorder=recorder, rounds=in_window, window_s=window_s,
            trace=summary, peaks=metrics_lib.peaks(device["kind"]) if require_tpu else None,
        )
        result["metrics"] = metrics_lib.read_all(cell, ctx)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.top_ops(), "idle_gaps": summary.top_gaps()}
        log(f"trace: read in {perf_counter() - t_read:.1f} s; busy {summary.busy_s:.4f} s of "
            f"{summary.window_s:.4f} s; programs {sorted(summary.module_count.items())[:40]}")
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        result["metrics"] = {
            k: {"value": v, "unit": units[k]} for k, v in e2e.items() if k in units and v is not None
        }

    # ---- correctness: the reference, once the program's state is gone ----
    by_key, lengths, failed, attempted = collect_served(served, recorder, cfg, traffic)
    del engine, params, recorder, served
    gc.collect()
    t_ref = perf_counter()
    keys = check.sample(sorted(by_key), lengths, int(traffic["check_requests"]), seed)
    chosen = [by_key[k] for k in keys]
    conf = cell.config if reduced is None else reduced_conf(cell.config, cfg)
    ref_params = weights.make_params(cfg, seed, abstract)
    numbers = reference_numbers(cell, conf, ref_params, chosen, thresholds)
    del ref_params
    correct = check.verdict(numbers, cell.limits, failed)
    log(f"reference: {len(chosen)} requests, {sum(len(r.tokens) for r in chosen)} served tokens, "
        f"{perf_counter() - t_ref:.1f} s")
    result.update(correct=correct, attempted=attempted, failed=failed)
    result["check"] = {k: {"value": numbers[k], "limit": float(cell.limits[k])} for k in check.NUMBERS}
    for k in check.NUMBERS:
        log(f"check {k}: {numbers[k]!r} limit {float(cell.limits[k])!r}")
    log(f"check failed_requests: {failed} limit 0")
    return result


def reduced_conf(conf: dict, cfg) -> dict:
    """The configuration file with the widths of a ``.reduced()`` config
    (CPU tests only)."""
    out = dict(conf)
    out.update(
        hidden_size=cfg.d_model,
        num_hidden_layers=cfg.num_layers,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads,
        intermediate_size=cfg.d_ff,
        vocab_size=cfg.vocab_size,
    )
    return out
