"""Figs. 3-4: delay + accuracy vs. task arrival rate (ResNet101 & BERT).

For each arrival-rate scale, every algorithm gets a configuration phase
(with its own threshold adaptation) and one measured 5 s offloading slot.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import ALGOS, decide, fmt_row, run_slot
from repro.core.thresholds import synthetic_validation
from repro.core.topology import build_edge_network
from repro.core.types import BERT_PROFILE, DtoHyperParams, RESNET101_PROFILE
from repro.launch.compile_cache import enable_compile_cache

SCALES = {
    "resnet101": (2.0, 2.5, 3.0, 3.5),
    "bert": (0.5, 0.65, 0.8, 0.95),
}


def run(seed: int = 0, duration: float = 5.0) -> list[str]:
    hyper = DtoHyperParams()
    lines = []
    results = {}
    for profile in (RESNET101_PROFILE, BERT_PROFILE):
        exit_profile = synthetic_validation(seed=seed + 1, profile=profile)
        for scale in SCALES[profile.name]:
            topo = build_edge_network(
                seed=seed, profile=profile, arrival_rate_scale=scale
            )
            rate = topo.phi_ext.sum()
            lines.append(f"--- {profile.name} arrival {rate:.1f} tasks/s ---")
            for algo in ALGOS:
                state = decide(algo, topo, profile, exit_profile, hyper, None, static=True)
                sim = run_slot(
                    topo, profile, exit_profile, state, None, duration, seed + 42
                )
                results[(profile.name, scale, algo)] = sim
                lines.append(fmt_row(algo, sim))
        # headline: reduction at the highest load
        top = SCALES[profile.name][-1]
        d_dto = results[(profile.name, top, "DTO-EE")].mean_delay
        reds = {
            a: (1 - d_dto / results[(profile.name, top, a)].mean_delay) * 100
            for a in ALGOS
            if a != "DTO-EE"
        }
        accs = {
            a: (
                results[(profile.name, top, "DTO-EE")].accuracy
                - results[(profile.name, top, a)].accuracy
            )
            * 100
            for a in ALGOS
            if a != "DTO-EE"
        }
        lines.append(
            f"[{profile.name}] DTO-EE delay reduction at top load: "
            + ", ".join(f"{a} {v:.0f}%" for a, v in reds.items())
            + "  |  accuracy delta (pts): "
            + ", ".join(f"{a} {v:+.1f}" for a, v in accs.items())
        )
    return lines


if __name__ == "__main__":
    enable_compile_cache()
    print("\n".join(run()))
