"""Per-layer metrics: each is a reader of its own, ``bench/metrics/<name>.py``,
with ``read(ctx) -> float | None``.  A reader that finds nothing to read
returns None and the metric is left out of the line."""
from __future__ import annotations

import dataclasses
import sys
from typing import Any

from benchlib import spec


def peaks(device_kind: str) -> dict:
    table = spec.load_json(spec.BENCH_DIR / "benchlib" / "peaks.json")["kinds"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} (known: {sorted(table)})")
    return table[device_kind]


@dataclasses.dataclass
class Context:
    """What a reader may read: the cell, the recorder's counters for the
    window's rounds, the window's host-clock length, the reduced trace and
    the chip's peaks."""

    cell: Any
    recorder: Any
    rounds: set
    window_s: float
    trace: Any
    peaks: dict | None

    @property
    def conf(self) -> dict:
        return self.cell.config

    def batches(self):
        return [b for b in self.recorder.batches if b.round in self.rounds]

    @staticmethod
    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)


def read_all(cell, ctx: Context) -> dict:
    out = {}
    for m in cell.per_layer:
        reader = spec.load_module(spec.BENCH_DIR / "metrics" / f"{m['name']}.py", m["name"])
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
