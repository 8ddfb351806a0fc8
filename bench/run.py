"""One run of one benchmark cell on the chip this process finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (weights from the seed, the DTO-EE configuration phase, a warm-up
round and every shape the traffic uses) counts as ``setup_s``; then
``serve()`` rounds run back to back for ``--seconds``; then the served
tokens and confidences are compared with the plain float32 reference.  The
last line of standard output is the result object; the numbers compared,
each beside its limit, are the last lines of standard error.  Without a TPU
(or with fewer chips than the cell asks for) it prints no result and exits
with 1.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchlib import cell as cell_lib
    from benchlib import spec

    cell = spec.load_cell(args.workload)
    try:
        result = cell_lib.run_cell(
            cell, args.seed, args.seconds, bool(args.trace), t_start=T_START
        )
    except cell_lib.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
