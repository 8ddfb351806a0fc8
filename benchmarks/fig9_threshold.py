"""Fig. 9: effect of dynamically adjusting confidence thresholds.

DTO-EE vs DTO w/o AT-x (thresholds fixed at x; offloading still optimized)
on the homogeneous deployment, in the dynamic environment.
"""
from __future__ import annotations

import numpy as np

from repro.core import dto_ee, simulator
from repro.core.thresholds import synthetic_validation
from repro.core.topology import build_uniform_network, with_arrival_rates
from repro.core.types import DtoHyperParams, RESNET101_PROFILE
from repro.launch.compile_cache import enable_compile_cache

FIXED = (1.0, 0.9, 0.8, 0.7)


def run(seed: int = 0, slots: int = 10, duration: float = 5.0) -> list[str]:
    profile = RESNET101_PROFILE
    hyper = DtoHyperParams()
    exit_profile = synthetic_validation(seed=seed + 1, profile=profile)
    rng = np.random.default_rng(seed + 5)

    variants: dict[str, np.ndarray | None] = {"DTO-EE": None}
    for c in FIXED:
        variants[f"w/o AT-{c}"] = np.full(exit_profile.num_early_branches, c)

    delays = {k: [] for k in variants}
    accs = {k: [] for k in variants}
    topo = build_uniform_network(seed=seed, profile=profile, ed_arrival_rate=2.2)
    states: dict[str, dto_ee.DtoState | None] = {k: None for k in variants}
    for slot in range(slots):
        for name, thr in variants.items():
            adapt = thr is None
            if states[name] is None and thr is not None:
                states[name] = dto_ee.init_state(
                    topo, profile, exit_profile, initial_thresholds=thr
                )
            res = dto_ee.run_configuration_phase(
                topo,
                profile,
                exit_profile,
                hyper,
                state=states[name],
                adapt_thresholds=adapt,
            )
            states[name] = res.state
            sim = simulator.simulate_slot(
                topo,
                profile,
                exit_profile,
                np.asarray(res.state.carry.p),
                res.state.thresholds,
                duration=duration,
                seed=seed + 50 + slot,
            )
            delays[name].append(sim.mean_delay)
            accs[name].append(sim.accuracy)
        topo = with_arrival_rates(topo, rng, 1.2, 3.0)

    lines = []
    d_dto = np.mean(delays["DTO-EE"])
    a_dto = np.mean(accs["DTO-EE"])
    for name in variants:
        d, a = np.mean(delays[name]), np.mean(accs[name])
        lines.append(
            f"{name:12s} delay {d*1e3:7.1f}ms  acc {a:.4f}"
            + (
                f"   (DTO-EE: {(1 - d_dto / d) * 100:+.1f}% delay, "
                f"{(a_dto - a) * 100:+.1f} acc pts)"
                if name != "DTO-EE"
                else ""
            )
        )
    return lines


if __name__ == "__main__":
    enable_compile_cache()
    print("\n".join(run()))
