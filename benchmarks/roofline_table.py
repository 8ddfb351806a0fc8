"""Aggregate the dry-run artifacts into the 40-cell roofline table.

Reads experiments/dryrun/*.json (produced by repro.launch.dryrun) and
prints the per-cell three-term roofline, dominant bottleneck, useful-FLOPs
ratio, and a memory-efficiency column for decode cells (ideal bytes =
params + cache read once per token vs HLO bytes).
"""
from __future__ import annotations

import glob
import json
import os

from repro.configs import SHAPES, get_config, list_archs
from repro.configs.base import shape_applicable
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as model_lib

ART = os.path.normpath(
    os.path.join(os.path.dirname(__file__), "../experiments/dryrun")
)


def ideal_decode_bytes(arch: str, shape_name: str) -> float:
    """Minimum HBM traffic for one decode step: read every (active) param
    + the KV/state cache once."""
    import jax
    import numpy as np

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    n_active = cfg.param_count(active_only=cfg.moe is not None)
    param_bytes = n_active * 2  # bf16
    caches = model_lib.cache_specs(cfg, shape.global_batch, shape.seq_len)
    cache_bytes = sum(
        int(np.prod(l.shape)) * l.dtype.itemsize for l in jax.tree.leaves(caches)
    )
    return param_bytes + cache_bytes


def run() -> list[str]:
    rows = []
    for path in sorted(glob.glob(os.path.join(ART, "*.json"))):
        with open(path) as f:
            rows.append(json.load(f))
    by_cell = {r["cell"]: r for r in rows}

    lines = [
        f"{'arch':22s} {'shape':11s} {'mesh':10s} {'comp ms':>8s} {'mem ms':>8s} "
        f"{'coll ms':>8s} {'dom':>6s} {'useful':>7s} {'roofline':>8s} {'mem-eff':>8s}"
    ]
    for arch in list_archs():
        for shape_name in SHAPES:
            cfg = get_config(arch)
            ok, reason = shape_applicable(cfg, SHAPES[shape_name])
            if not ok:
                lines.append(f"{arch:22s} {shape_name:11s} SKIP ({reason.split(':')[0]})")
                continue
            for mesh in ("pod16x16", "pod2x16x16"):
                cell = f"{arch}__{shape_name}__{mesh}"
                r = by_cell.get(cell)
                if r is None:
                    lines.append(f"{arch:22s} {shape_name:11s} {mesh:10s} MISSING")
                    continue
                if "dominant" not in r:
                    lines.append(
                        f"{arch:22s} {shape_name:11s} {mesh:10s} gate-only "
                        f"(compile {r.get('compile_s', '?')}s)"
                    )
                    continue
                mem_eff = ""
                if SHAPES[shape_name].mode == "decode":
                    ideal = ideal_decode_bytes(arch, shape_name)
                    mem_eff = f"{ideal / (r['hlo_gbytes'] * 1e9):8.2f}"
                lines.append(
                    f"{arch:22s} {shape_name:11s} {mesh:10s} "
                    f"{r['compute_ms']:8.2f} {r['memory_ms']:8.2f} "
                    f"{r['collective_ms']:8.2f} {r['dominant'][:6]:>6s} "
                    f"{r['useful_ratio']:7.2f} {r['roofline_fraction']:8.3f} {mem_eff}"
                )
    return lines


if __name__ == "__main__":
    enable_compile_cache()
    print("\n".join(run()))
