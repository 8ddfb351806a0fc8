"""Figs. 5-6: delay + accuracy vs. average computing resource (0.65x - 1.5x).

The computing mode of every ES is scaled; arrival rates stay fixed.
"""
from __future__ import annotations

from benchmarks.common import ALGOS, decide, fmt_row, run_slot
from repro.core.thresholds import synthetic_validation
from repro.core.topology import build_edge_network, with_capacity_scale
from repro.core.types import BERT_PROFILE, DtoHyperParams, RESNET101_PROFILE
from repro.launch.compile_cache import enable_compile_cache

CAP_SCALES = (0.65, 1.0, 1.5)
ARRIVAL = {"resnet101": 2.5, "bert": 0.65}


def run(seed: int = 0, duration: float = 5.0) -> list[str]:
    hyper = DtoHyperParams()
    lines = []
    for profile in (RESNET101_PROFILE, BERT_PROFILE):
        exit_profile = synthetic_validation(seed=seed + 1, profile=profile)
        base = build_edge_network(
            seed=seed, profile=profile, arrival_rate_scale=ARRIVAL[profile.name]
        )
        for cap in CAP_SCALES:
            topo = with_capacity_scale(base, cap)
            lines.append(f"--- {profile.name} capacity x{cap} ---")
            for algo in ALGOS:
                state = decide(algo, topo, profile, exit_profile, hyper, None, static=True)
                sim = run_slot(
                    topo, profile, exit_profile, state, None, duration, seed + 42
                )
                lines.append(fmt_row(algo, sim))
    return lines


if __name__ == "__main__":
    enable_compile_cache()
    print("\n".join(run()))
