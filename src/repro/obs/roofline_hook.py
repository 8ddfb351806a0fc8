"""Roofline join: measured stage-program wall time vs the hardware bound.

The tracer accumulates REAL ``perf_counter`` wall seconds around every
jitted stage-program call the engine makes (``wants_wall_clock``), keyed by
``(stage, phase)`` and carrying the device work actually shipped (padded
rows, device tokens, call count).  This module joins that with the analytic
per-stage FLOP/byte counts in :mod:`repro.roofline.analysis` to report, per
stage and phase, how far measured compute sits from the roofline bound —
turning the ROADMAP's "as fast as the hardware allows" into a measured gap.

The bound uses the published peaks of ``device_kind``
(``roofline/constants.py``); the caller names the device the wall times
were taken on, and a kind without published peaks is an error.
"""
from __future__ import annotations

from repro.roofline.analysis import (
    stage_roofline_bound_s,
    stage_step_bytes,
    stage_step_flops,
)

__all__ = ["roofline_utilization"]


def roofline_utilization(tracer, cfg, device_kind: str) -> dict:
    """Measured-vs-roofline utilization per (stage, phase) of one serve on
    ``device_kind``.

    Returns ``{"stage{h}.{phase}": {...}}`` rows with the measured wall
    time, the analytic FLOP/byte totals for the device work shipped, the
    roofline bound, and ``utilization = bound_s / measured_s``.
    """
    out: dict[str, dict] = {}
    for (stage, phase), cw in sorted(tracer.compute_wall.items()):
        flops = stage_step_flops(cfg, stage, cw.tokens)
        nbytes = stage_step_bytes(cfg, stage, cw.calls, cw.tokens)
        bound_s = stage_roofline_bound_s(flops, nbytes, device_kind)
        row = {
            "stage": stage,
            "phase": phase,
            "calls": cw.calls,
            "device_rows": cw.rows,
            "live_rows": cw.live_rows,
            "device_tokens": cw.tokens,
            "modeled_gflops": cw.gflops,
            "analytic_gflops": flops / 1e9,
            "analytic_gbytes": nbytes / 1e9,
            "bound_s": bound_s,
            "measured_wall_s": cw.wall_s,
            "utilization": bound_s / cw.wall_s if cw.wall_s > 0 else 0.0,
            "padded_row_frac": 1.0 - cw.live_rows / cw.rows if cw.rows else 0.0,
        }
        out[f"stage{stage}.{phase}"] = row
    return out
