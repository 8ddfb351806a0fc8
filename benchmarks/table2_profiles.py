"""Paper Table 2: per-sub-model cost/accuracy profiles.

Prints the paper's profiles (ResNet101 / BERT) and the derived profiles of
the assigned architectures (core.profiles.profile_from_arch), which feed
every other benchmark.
"""
from __future__ import annotations

from repro.configs import get_config, list_archs
from repro.core.profiles import profile_from_arch
from repro.core.types import BERT_PROFILE, RESNET101_PROFILE
from repro.launch.compile_cache import enable_compile_cache


def run() -> list[str]:
    lines = []
    for prof in (RESNET101_PROFILE, BERT_PROFILE):
        lines.append(
            f"{prof.name}: alpha={prof.alpha} GFLOPs  beta={prof.beta} MB  "
            f"exits@{prof.exit_stages}  acc={prof.branch_accuracy}"
        )
    for arch in list_archs():
        cfg = get_config(arch)
        prof = profile_from_arch(cfg)
        alpha = tuple(round(a, 2) for a in prof.alpha)
        lines.append(
            f"{arch}: H={prof.num_stages} alpha={alpha} GFLOPs/task "
            f"beta[1:]={prof.beta[1]:.3f} MB exits@{prof.exit_stages}"
        )
    return lines


if __name__ == "__main__":
    enable_compile_cache()
    print("\n".join(run()))
