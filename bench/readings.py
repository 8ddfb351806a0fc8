"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/readings.py --workload <name> --seeds 1 2 ... \
        [--control-seeds 1 2 3] [--out readings.jsonl]

In one process (set-up is paid once): for each seed, the benchmark's
weights for that seed go into the engine, the window's rounds run at the
cell's own load until as many requests finished as a run compares, and
the compared numbers are read against the float32 reference (the lower
readings).  For each control seed, the reference computed in float8 (the
precision below the bfloat16 the configurations serve in) is put where the
program was and read the same way (the upper readings).  One JSON line per
seed; the benchmark's own runs never run this.
"""
from __future__ import annotations

from time import perf_counter

T_START = perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
# libtpu logs to /tmp/tpu_logs unless told otherwise: keep them in the checkout
os.environ.setdefault("TPU_LOG_DIR", str(BENCH.parent / ".bench_trace" / "tpu_logs"))

CONTROL_PRECISION = "float8_e4m3fn"


def readings(cell, seeds, control_seeds, *, require_tpu=True, reduced=None, emit=print):
    import jax

    from benchlib import cell as C
    from benchlib import check, recorder as rec_lib, spec, weights

    C.device_info(cell.chips, require_tpu)
    C.log(f"compile cache: {C.use_compile_cache()}")
    traffic = cell.traffic
    cfg = spec.arch_config(cell.config)
    if reduced is not None:
        cfg = cfg.reduced(**reduced)
    conf = cell.config if reduced is None else C.reduced_conf(cell.config, cfg)
    abstract = weights.abstract_tree(cfg)
    engine = recorder = None
    rows = []
    for seed in seeds:
        t0 = perf_counter()
        params = jax.block_until_ready(weights.make_params(cfg, seed, abstract))
        if engine is None:
            engine = C.build_engine(cfg, params, traffic)
            engine.configuration_phase()
            recorder = rec_lib.Recorder(engine.programs, cfg.num_stages, cfg.exit_stages)
        engine.programs.params = params
        recorder.reset()
        served, rounds, window_s = C.run_rounds(
            engine, recorder, traffic, cfg, seed, 0.0, min_requests=int(traffic["check_requests"])
        )
        by_key, lengths, failed, attempted = C.collect_served(served, recorder, cfg, traffic)
        keys = check.sample(sorted(by_key), lengths, int(traffic["check_requests"]), seed)
        chosen = [by_key[k] for k in keys]
        thresholds = list(map(float, engine.thresholds))
        row = {
            "seed": seed,
            "rounds": rounds,
            "window_s": window_s,
            "attempted": attempted,
            "failed": failed,
            "compared_requests": len(chosen),
            "compared_tokens": sum(len(r.tokens) for r in chosen),
            "exit_histogram": _histogram(chosen),
            "thresholds": thresholds,
            "program": C.reference_numbers(cell, conf, params, chosen, thresholds),
        }
        if seed in control_seeds:
            row["control"] = C.control_numbers(cell, conf, params, chosen, thresholds, CONTROL_PRECISION)
        row["seconds"] = perf_counter() - t0
        emit(json.dumps(row))
        rows.append(row)
        del params
    return rows


def _histogram(chosen) -> dict:
    out: dict = {}
    for r in chosen:
        out[str(r.exit_stage)] = out.get(str(r.exit_stage), 0) + 1
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from benchlib import cell as C
    from benchlib import spec

    out = open(args.out, "a") if args.out else None

    def emit(line: str) -> None:
        print(line, flush=True)
        if out is not None:
            out.write(line + "\n")
            out.flush()

    try:
        rows = readings(spec.load_cell(args.workload), args.seeds, set(args.control_seeds), emit=emit)
        for key in ("program", "control"):
            got = [r[key] for r in rows if key in r]
            if got:
                worst = {k: (min if key == "control" else max)(g[k] for g in got) for k in got[0]}
                emit(json.dumps({"workload": args.workload, key: worst, "seeds": len(got),
                                 "read": "largest" if key == "program" else "smallest"}))
    except C.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    finally:
        if out is not None:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
