"""Decode throughput: cache-threaded decode vs stateless re-prefill, and
paged vs dense slot-cache capacity.

Default mode runs ``CollaborativeEngine.serve`` at gen_len in {8, 32} in
both decode modes on one fixed workload (same prompts, same arrival process,
same thresholds), asserts token-identical sequences and exit decisions
between the modes AND against the monolithic ``model.prefill`` +
``model.decode_step`` reference, and measures wall-clock decode tokens/s.
Results land in ``BENCH_decode.json``.

``--cache-layout paged`` instead A/Bs the PAGED slot store against the dense
layout at EQUAL KV bytes (same pool token capacity as the dense arenas) on a
production-shaped workload — mixed prompt lengths plus shared-prefix groups —
asserts bitwise-identical tokens, and records how many more requests the
paged replica holds in flight in the same memory, with prefix-hit and
block-occupancy stats.  Results land in ``BENCH_paged.json``.

    PYTHONPATH=src python benchmarks/decode_throughput.py [--out BENCH_decode.json]
    PYTHONPATH=src python benchmarks/decode_throughput.py --cache-layout paged
    PYTHONPATH=src python benchmarks/decode_throughput.py --smoke   # CI schema check
"""
from __future__ import annotations

import argparse
import json
import platform
import time

import numpy as np

import jax

from repro.configs import get_config
from repro.core.profiles import profile_from_arch
from repro.core.thresholds import synthetic_validation
from repro.core.topology import NetworkSpec, build_edge_network
from repro.core.types import DtoHyperParams
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as model_lib
from repro.serving import CollaborativeEngine, monolithic_generate


def build_engine(seed: int = 0, threshold: float | None = 0.1) -> CollaborativeEngine:
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
    if threshold is not None:
        # a mid-range threshold so the workload mixes early exits (rows
        # retiring mid-batch) with full-length generations
        eng.state.thresholds = np.full_like(eng.state.thresholds, threshold)
    return eng


def bench_decode(
    eng: CollaborativeEngine,
    gen_lens: tuple[int, ...],
    n_requests: int,
    prompt_len: int,
    batch_size: int,
    arrival_rate: float,
    serve_seed: int = 123,
    repeats: int = 2,
    num_slots: int | None = None,
) -> dict:
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(0, eng.cfg.vocab_size, size=prompt_len).astype(np.int32)
        for _ in range(n_requests)
    ]
    by_gen: dict[str, dict] = {}
    for gen_len in gen_lens:
        # monolithic single-host reference: the ground truth both engine
        # modes must reproduce token-for-token
        reference = {}
        for i, p in enumerate(prompts):
            toks, stage = monolithic_generate(
                eng.programs.params, eng.cfg, p, eng.thresholds, gen_len
            )
            reference[i] = (stage, tuple(toks))
        modes: dict[str, dict] = {}
        seqs: dict[str, dict] = {}
        for mode in ("stateless", "cached"):
            eng.rng = np.random.default_rng(serve_seed)
            eng.serve(
                prompts,
                arrival_rate=arrival_rate,
                batch_size=batch_size,
                gen_len=gen_len,
                decode_mode=mode,
                num_slots=num_slots,
            )  # warmup/compile
            walls = []
            for _ in range(repeats):
                eng.rng = np.random.default_rng(serve_seed)
                t0 = time.perf_counter()
                stats = eng.serve(
                    prompts,
                    arrival_rate=arrival_rate,
                    batch_size=batch_size,
                    gen_len=gen_len,
                    decode_mode=mode,
                    num_slots=num_slots,
                )
                walls.append(time.perf_counter() - t0)
            wall = float(np.median(walls))
            s = stats.summary()
            seqs[mode] = stats.sequences_by_rid()
            modes[mode] = {
                "wall_s": wall,
                "tokens_per_s": s["generated_tokens"] / wall,
                "generated_tokens": s["generated_tokens"],
                "num_completed": s["num_completed"],
                "mean_delay_s": s["mean_delay"],
                "p95_delay_s": s["p95_delay"],
                "num_batches": s["num_batches"],
                "padded_row_frac": s["padded_row_frac"],
                "exit_histogram": s["exit_histogram"],
            }
            print(
                f"gen_len {gen_len:3d} {mode:9s}: "
                f"{modes[mode]['tokens_per_s']:8.1f} tok/s  wall {wall:.3f}s  "
                f"batches {s['num_batches']:5d}  exits {s['exit_histogram']}"
            )
        identical = (
            seqs["cached"] == seqs["stateless"] == reference
        )
        speedup = modes["cached"]["tokens_per_s"] / modes["stateless"]["tokens_per_s"]
        print(
            f"gen_len {gen_len:3d}: token-identical (cached == stateless == "
            f"monolithic): {identical}  speedup {speedup:.2f}x"
        )
        by_gen[str(gen_len)] = {
            "by_mode": modes,
            "tokens_identical": identical,
            "speedup_cached_vs_stateless": speedup,
        }
    return {
        "workload": {
            "n_requests": n_requests,
            "prompt_len": prompt_len,
            "batch_size": batch_size,
            "num_slots": num_slots,
            "arrival_rate": arrival_rate,
            "threshold": float(eng.thresholds[0]),
        },
        "by_gen_len": by_gen,
    }


def _kv_token_bytes(cfg, max_len: int) -> list[int]:
    """Per-stage bytes of sequence-dim (pageable) cache leaves per token of
    capacity (stages may hold different period counts)."""
    per_stage = []
    for stage_idx in range(1, cfg.num_stages + 1):
        dense = model_lib.init_stage_slot_caches(cfg, stage_idx, 1, max_len)
        total = 0
        for period in dense:
            for key, leaf in period.items():
                if key in model_lib.PAGED_CACHE_LEAVES:
                    total += leaf.nbytes
        per_stage.append(total // max_len)
    return per_stage


def _paged_prompts(rng, vocab: int, n_groups: int, group: int, n_long: int):
    """Production-shaped mix: groups of short requests sharing a 48-token
    prompt prefix (system-prompt style) plus a few long-context requests.
    Short rows waste most of a dense ``max_len`` arena — the memory the
    paged layout reclaims."""
    prompts = []
    for _ in range(n_groups):
        common = rng.integers(0, vocab, size=48).astype(np.int32)
        for _ in range(group):
            own = rng.integers(0, vocab, size=int(rng.integers(8, 24)))
            prompts.append(np.concatenate([common, own.astype(np.int32)]))
    for _ in range(n_long):
        prompts.append(rng.integers(0, vocab, size=384).astype(np.int32))
    return prompts


def bench_paged(
    eng: CollaborativeEngine,
    gen_len: int,
    block_size: int,
    dense_slots: int,
    arrival_rate: float,
    serve_seed: int = 123,
    n_groups: int = 4,
    group: int = 4,
    n_long: int = 4,
) -> dict:
    rng = np.random.default_rng(0)
    prompts = _paged_prompts(rng, eng.cfg.vocab_size, n_groups, group, n_long)
    max_len = max(int(p.shape[0]) for p in prompts) + gen_len
    # equal KV bytes: the paged pool gets the dense arenas' token capacity
    # (dense_slots * max_len tokens), rounded DOWN to block granularity so
    # the paged run never holds more KV memory; slot rings are bookkeeping
    # rows (pos only for attention configs), so the paged run may hold many
    # more sequences in the same KV memory
    num_blocks = (dense_slots * max_len) // block_size
    paged_slots = 8 * dense_slots

    reference = {}
    for i, p in enumerate(prompts):
        toks, stage = monolithic_generate(
            eng.programs.params, eng.cfg, p, eng.thresholds, gen_len
        )
        reference[i] = (stage, tuple(toks))

    runs: dict[str, dict] = {}
    seqs: dict[str, dict] = {}
    for layout in ("dense", "paged"):
        kw = dict(
            arrival_rate=arrival_rate,
            batch_size=dense_slots,
            gen_len=gen_len,
            decode_mode="cached",
        )
        if layout == "dense":
            kw["num_slots"] = dense_slots
        else:
            kw.update(
                cache_layout="paged",
                block_size=block_size,
                num_slots=paged_slots,
                num_blocks=num_blocks,
            )
        eng.rng = np.random.default_rng(serve_seed)
        eng.serve(prompts, **kw)  # warmup/compile
        eng.rng = np.random.default_rng(serve_seed)
        t0 = time.perf_counter()
        stats = eng.serve(prompts, **kw)
        wall = time.perf_counter() - t0
        s = stats.summary()
        seqs[layout] = stats.sequences_by_rid()
        runs[layout] = {
            "wall_s": wall,
            "tokens_per_s": s["generated_tokens"] / wall,
            "generated_tokens": s["generated_tokens"],
            "num_completed": s["num_completed"],
            "peak_in_flight": s["peak_in_flight"],
            "mean_delay_s": s["mean_delay"],
            "exit_histogram": s["exit_histogram"],
            "kv_token_capacity_per_replica": (
                dense_slots * max_len if layout == "dense" else num_blocks * block_size
            ),
            "prefix_hit_rate": s["prefix_hit_rate"],
            "prefix_hit_blocks": s["prefix_hit_blocks"],
            "prefix_total_blocks": s["prefix_total_blocks"],
            "block_occupancy_mean": s["block_occupancy_mean"],
            "block_occupancy_peak": s["block_occupancy_peak"],
        }
        print(
            f"{layout:5s}: peak_in_flight {s['peak_in_flight']:3d}  "
            f"tok/s {runs[layout]['tokens_per_s']:8.1f}  "
            f"prefix_hits {s['prefix_hit_rate']*100:4.1f}%  "
            f"occupancy peak {s['block_occupancy_peak']*100 if layout == 'paged' else float('nan'):5.1f}%"
        )
    identical = seqs["dense"] == seqs["paged"] == reference
    token_bytes = _kv_token_bytes(eng.cfg, max_len)
    inflight_gain = runs["paged"]["peak_in_flight"] / max(
        runs["dense"]["peak_in_flight"], 1
    )
    print(
        f"token-identical (paged == dense == monolithic): {identical}  "
        f"in-flight gain at equal KV bytes: {inflight_gain:.2f}x"
    )
    return {
        "workload": {
            "n_requests": len(prompts),
            "prompt_lens": sorted(int(p.shape[0]) for p in prompts),
            "gen_len": gen_len,
            "block_size": block_size,
            "dense_slots": dense_slots,
            "paged_slots": paged_slots,
            "num_blocks_per_replica": num_blocks,
            "max_len": max_len,
            "kv_bytes_per_token_by_stage": token_bytes,
            "kv_bytes_per_replica_by_stage": [
                b * dense_slots * max_len for b in token_bytes
            ],
            "arrival_rate": arrival_rate,
            "threshold": float(eng.thresholds[0]),
        },
        "by_layout": runs,
        "tokens_identical": identical,
        "in_flight_gain_at_equal_kv_bytes": inflight_gain,
    }


def validate_paged_schema(payload: dict) -> None:
    """The contract the paged capacity bench is held to."""
    assert "paged" in payload and "meta" in payload
    res = payload["paged"]
    assert res["tokens_identical"] is True, (
        "paged decode diverged from the dense layout / monolithic reference"
    )
    dense, paged = res["by_layout"]["dense"], res["by_layout"]["paged"]
    assert (
        paged["kv_token_capacity_per_replica"]
        <= dense["kv_token_capacity_per_replica"]
    ), "paged run used MORE KV memory than dense"
    assert res["in_flight_gain_at_equal_kv_bytes"] >= 2.0, (
        f"paged layout sustained only "
        f"{res['in_flight_gain_at_equal_kv_bytes']:.2f}x the dense in-flight "
        "requests at equal KV bytes (need >= 2x)"
    )
    assert paged["prefix_hit_blocks"] > 0
    assert 0.0 < paged["block_occupancy_peak"] <= 1.0


def validate_schema(payload: dict) -> None:
    """The contract ``bench-smoke`` (CI) holds this benchmark to."""
    assert "decode" in payload and "meta" in payload
    dec = payload["decode"]
    for key in ("workload", "by_gen_len"):
        assert key in dec, f"missing {key}"
    for gen_len, entry in dec["by_gen_len"].items():
        assert entry["tokens_identical"] is True, (
            f"gen_len {gen_len}: cached decode diverged from the stateless "
            "baseline / monolithic reference"
        )
        assert entry["speedup_cached_vs_stateless"] > 0
        for mode in ("cached", "stateless"):
            m = entry["by_mode"][mode]
            for field in ("wall_s", "tokens_per_s", "generated_tokens", "num_batches"):
                assert np.isfinite(m[field]), f"{mode}.{field} not finite"


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="BENCH_decode.json")
    # decode-dominated workload: long prompts make the stateless baseline's
    # O(prefix) re-compute per token visible against per-dispatch overhead
    ap.add_argument("--n-requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=384)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--num-slots", type=int, default=8)
    ap.add_argument("--gen-lens", type=int, nargs="+", default=[8, 32])
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument(
        "--arrival-rate",
        type=float,
        default=1e6,
        help="Poisson arrival rate; high = closed-loop (all requests queued)",
    )
    ap.add_argument(
        "--cache-layout",
        choices=("dense", "paged"),
        default="dense",
        help="dense: cached-vs-stateless throughput (BENCH_decode.json); "
        "paged: paged-vs-dense capacity at equal KV bytes (BENCH_paged.json)",
    )
    ap.add_argument(
        "--block-size",
        type=int,
        default=16,
        help="tokens per KV block for --cache-layout paged",
    )
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="tiny workload; validate the JSON schema and exit nonzero on drift",
    )
    args = ap.parse_args()
    enable_compile_cache()
    meta = {
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "platform": platform.platform(),
    }

    if args.cache_layout == "paged":
        if args.out == "BENCH_decode.json":
            args.out = "BENCH_paged.json"
        gen_len = 8 if args.smoke else 32
        dense_slots = 2 if args.smoke else 4
        groups = dict(n_groups=3, group=4, n_long=2) if args.smoke else {}
        eng = build_engine(threshold=0.35)
        res = bench_paged(
            eng,
            gen_len=gen_len,
            block_size=args.block_size,
            dense_slots=dense_slots,
            arrival_rate=args.arrival_rate,
            **groups,
        )
        payload = {"paged": res, "meta": meta}
        validate_paged_schema(payload)
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {args.out}")
        return

    if args.smoke:
        args.n_requests, args.prompt_len, args.gen_lens = 6, 8, [4]
        args.batch_size, args.num_slots, args.repeats = 4, 4, 1

    eng = build_engine(threshold=0.35)
    res = bench_decode(
        eng,
        tuple(args.gen_lens),
        args.n_requests,
        args.prompt_len,
        args.batch_size,
        args.arrival_rate,
        repeats=args.repeats,
        num_slots=args.num_slots,
    )
    payload = {"decode": res, "meta": meta}
    validate_schema(payload)
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
