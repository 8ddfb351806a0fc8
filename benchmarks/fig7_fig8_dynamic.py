"""Figs. 7-8: the dynamic environment — arrival rates and computing modes
re-randomized every slot; algorithms warm-start and pay their decision time
(the slow deciders route on stale strategies for the first part of each
slot).  Reports per-group means and the delay standard deviation (the
paper's stability metric).
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import ALGOS, AlgoState, decide, run_slot
from repro.core.thresholds import synthetic_validation
from repro.core.topology import (
    build_edge_network,
    with_arrival_rates,
    with_resampled_capacities,
)
from repro.core.types import BERT_PROFILE, DtoHyperParams, RESNET101_PROFILE
from repro.launch.compile_cache import enable_compile_cache

ARRIVAL = {"resnet101": 3.0, "bert": 0.7}


def run(
    seed: int = 0,
    slots: int = 20,
    group: int = 5,
    duration: float = 5.0,
) -> list[str]:
    hyper = DtoHyperParams()
    lines = []
    for profile in (RESNET101_PROFILE, BERT_PROFILE):
        exit_profile = synthetic_validation(seed=seed + 1, profile=profile)
        rng = np.random.default_rng(seed + 5)
        topo = build_edge_network(
            seed=seed, profile=profile, arrival_rate_scale=ARRIVAL[profile.name]
        )
        lines.append(f"--- {profile.name} dynamic ({slots} slots) ---")
        delays = {a: [] for a in ALGOS}
        accs = {a: [] for a in ALGOS}
        prev: dict[str, AlgoState | None] = {a: None for a in ALGOS}
        for slot in range(slots):
            for algo in ALGOS:
                state = decide(algo, topo, profile, exit_profile, hyper, prev[algo])
                sim = run_slot(
                    topo,
                    profile,
                    exit_profile,
                    state,
                    prev[algo],
                    duration,
                    seed + 100 + slot,
                )
                delays[algo].append(sim.mean_delay)
                accs[algo].append(sim.accuracy)
                prev[algo] = state
            # mutate the environment for the next slot (paper §4.3)
            lo, hi = 0.5 * ARRIVAL[profile.name], 1.5 * ARRIVAL[profile.name]
            topo = with_arrival_rates(topo, rng, lo, hi)
            topo = with_resampled_capacities(topo, rng)
        for algo in ALGOS:
            d = np.asarray(delays[algo])
            a = np.asarray(accs[algo])
            groups = d.reshape(-1, group).mean(axis=1)
            lines.append(
                f"{algo:8s} groups(ms) "
                + " ".join(f"{g*1e3:7.1f}" for g in groups)
                + f"  std {d.std()*1e3:6.1f}ms  acc {a.mean():.4f}"
            )
    return lines


if __name__ == "__main__":
    enable_compile_cache()
    print("\n".join(run()))
