"""Benchmark runner: ``PYTHONPATH=src python -m benchmarks.run [--fast]``.

One section per paper table/figure + the roofline table from dry-run
artifacts.  --fast shrinks slot counts for CI-speed runs.
"""
from __future__ import annotations

import argparse
import time

from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument(
        "--only", default="", help="comma list: table2,fig34,fig56,fig78,fig9,roofline"
    )
    args = ap.parse_args()
    enable_compile_cache()
    only = set(args.only.split(",")) if args.only else None

    def want(name: str) -> bool:
        return only is None or name in only

    sections = []
    if want("table2"):
        from benchmarks import table2_profiles

        sections.append(("Table 2 — sub-model profiles", table2_profiles.run))
    if want("fig34"):
        from benchmarks import fig3_fig4_arrival

        sections.append(
            (
                "Figs. 3-4 — arrival-rate sweep",
                lambda: fig3_fig4_arrival.run(duration=3.0 if args.fast else 5.0),
            )
        )
    if want("fig56"):
        from benchmarks import fig5_fig6_capacity

        sections.append(
            (
                "Figs. 5-6 — capacity sweep",
                lambda: fig5_fig6_capacity.run(duration=3.0 if args.fast else 5.0),
            )
        )
    if want("fig78"):
        from benchmarks import fig7_fig8_dynamic

        sections.append(
            (
                "Figs. 7-8 — dynamic environment",
                lambda: fig7_fig8_dynamic.run(
                    slots=8 if args.fast else 20, group=4 if args.fast else 5
                ),
            )
        )
    if want("fig9"):
        from benchmarks import fig9_threshold

        sections.append(
            (
                "Fig. 9 — dynamic thresholds ablation",
                lambda: fig9_threshold.run(slots=5 if args.fast else 10),
            )
        )
    if want("roofline"):
        from benchmarks import roofline_table

        sections.append(("Roofline table (from dry-run artifacts)", roofline_table.run))

    for title, fn in sections:
        print(f"\n{'=' * 72}\n{title}\n{'=' * 72}", flush=True)
        t0 = time.time()
        for line in fn():
            print(line, flush=True)
        print(f"[{title}: {time.time() - t0:.1f}s]", flush=True)


if __name__ == "__main__":
    main()
