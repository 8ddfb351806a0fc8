"""Reduction of a profiler trace to device busy time, op and program time
by name, and the idle gaps labelled by what the host was doing.

Reads the ``.xplane.pb`` the JAX profiler writes, with nothing but
``jax.profiler.ProfileData``.  Device operations are the events of the
``XLA Ops`` lines of ``/device:*`` planes; where there are none (the CPU
backend), they are the host events that carry an ``hlo_op`` statistic.
Each operation belongs to the program it ran in: the ``XLA Modules`` line
where the device has one, otherwise the op's ``hlo_module`` statistic.

Host spans are the profiler annotations whose names start with a prefix
(the harness's ``bench.``).  A ``<prefix>window`` span bounds the window
that busy and idle time are taken over.  Every gap between device
operations inside it is charged to the innermost host span around its
middle, or to ``"none"``.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from collections import defaultdict

NS = 1e-9
STAT_KEYS = ("hlo_op", "long_name", "tf_op", "hlo_category", "name")


@dataclasses.dataclass
class TraceSummary:
    busy_s: float
    window_s: float
    devices: int
    op_time: dict  # op name -> device seconds (summed over devices)
    op_count: dict
    op_text: dict  # op name -> its name and text statistics, for matching
    module_time: dict  # program name -> device seconds
    module_count: dict
    idle_gaps: dict  # host span label -> idle device seconds
    op_short_time: dict  # "program/op" -> device seconds

    def kernel(self, name: str, exclude: str | None = None) -> tuple[float, int]:
        """(seconds, calls) of the custom calls whose text names ``name`` (a
        Pallas kernel's ``name=``) and not ``exclude``: the wrapper's own
        ops (packing, relayout) carry the name too and are not counted."""
        s = n = 0
        for op, text in self.op_text.items():
            if _is_kernel(text, name, exclude):
                s += self.op_time[op]
                n += self.op_count[op]
        return s, n

    def program(self, *names: str) -> tuple[float, int]:
        """(seconds, calls) of the programs whose name holds one of ``names``."""
        s = n = 0
        for mod, t in self.module_time.items():
            if any(x in mod for x in names):
                s += t
                n += self.module_count[mod]
        return s, n

    def top_ops(self, k: int = 10) -> list:
        """The ``k`` costliest ops, as program/op (numbering and shapes
        dropped, so one op of every compiled variant of a program adds up)."""
        return sorted(([n, t] for n, t in self.op_short_time.items()), key=lambda r: -r[1])[:k]

    def matches(self, name: str, exclude: str | None = None, k: int = 5) -> list:
        """The costliest op texts that ``kernel(name, exclude)`` counts."""
        hits = [(t, self.op_count[op], op[:240]) for op, text in self.op_text.items()
                for t in [self.op_time[op]] if _is_kernel(text, name, exclude)]
        return sorted(hits, reverse=True)[:k]

    def top_gaps(self, k: int = 10) -> list:
        return sorted(([n, t] for n, t in self.idle_gaps.items()), key=lambda r: -r[1])[:k]


def _is_kernel(text: str, name: str, exclude: str | None) -> bool:
    return "custom-call" in text and name in text and not (exclude and exclude in text)


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def _stats(event) -> dict:
    try:
        return dict(event.stats)
    except (TypeError, ValueError):
        return {}


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _nest(spans):
    """Spans sorted by start (outer first on ties), each with the index of
    the nearest span that encloses it (-1: none)."""
    spans = sorted(spans, key=lambda r: (r[0], -r[1]))
    parent, stack = [], []
    for i, (s, e, _) in enumerate(spans):
        while stack and spans[stack[-1]][1] < e:
            stack.pop()
        parent.append(stack[-1] if stack else -1)
        stack.append(i)
    return spans, parent


def _innermost(spans, parent, starts, t) -> str:
    j = bisect.bisect_right(starts, t) - 1
    while j >= 0 and spans[j][1] < t:
        j = parent[j]
    return spans[j][2] if j >= 0 else "none"


def _short(name) -> str:
    """``%fusion.33 = bf16[...] fusion(...)`` -> ``fusion``;
    ``jit_stage_decode(1708...)`` -> ``jit_stage_decode``."""
    if not name:
        return "?"
    head = name.split(" = ")[0].split("(")[0].lstrip("%")
    return head.rstrip("0123456789").rstrip(".") or head


def reduce_xplane(path: str, prefix: str = "bench.") -> TraceSummary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    dev_ops = defaultdict(list)  # device -> [(start, end, name, module)]
    modules = defaultdict(list)  # device -> [(start, end, name)]
    host_spans = []  # (start, end, label)
    cpu_ops = []
    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:")
        for line in plane.lines:
            if is_dev and line.name == "XLA Ops":
                for ev in line.events:
                    s = float(ev.start_ns)
                    dev_ops[plane.name].append((s, s + float(ev.duration_ns), ev.name, None, _stats(ev)))
            elif is_dev and line.name == "XLA Modules":
                for ev in line.events:
                    s = float(ev.start_ns)
                    modules[plane.name].append((s, s + float(ev.duration_ns), ev.name))
            elif not is_dev:
                for ev in line.events:
                    name = ev.name
                    s = float(ev.start_ns)
                    if name.startswith(prefix):
                        host_spans.append((s, s + float(ev.duration_ns), name[len(prefix):]))
                        continue
                    st = _stats(ev)
                    if "hlo_op" in st:
                        cpu_ops.append((s, s + float(ev.duration_ns), name, st.get("hlo_module"), st))
    if not dev_ops and cpu_ops:
        dev_ops["cpu"] = cpu_ops
    windows = [(s, e) for s, e, label in host_spans if label == "window"]
    lo = min((s for s, _ in windows), default=None)
    hi = max((e for _, e in windows), default=None)

    op_time, op_count, op_text = defaultdict(float), defaultdict(int), {}
    op_short_time = defaultdict(float)
    module_time, module_count = defaultdict(float), defaultdict(int)
    busy = 0.0
    gaps = defaultdict(float)
    spans, parent = _nest(host_spans)
    starts = [s for s, _, _ in spans]
    for dev, ops in dev_ops.items():
        if lo is not None:
            ops = [o for o in ops if o[1] > lo and o[0] < hi]
        mods = sorted(modules[dev]) if dev in modules else None
        mod_starts = [m[0] for m in mods] if mods else []
        for s, e, name, mod, st in ops:
            op_time[name] += (e - s) * NS
            op_count[name] += 1
            if name not in op_text:
                op_text[name] = " ".join([name] + [str(st[k]) for k in STAT_KEYS if k in st])
            if mods:
                j = bisect.bisect_right(mod_starts, s) - 1
                mod = mods[j][2] if j >= 0 and mods[j][1] >= s else None
            op_short_time[f"{_short(mod)}/{_short(name)}"] += (e - s) * NS
        if mods is not None:
            for s, e, name in mods:
                if lo is None or (e > lo and s < hi):
                    module_time[name] += (e - s) * NS
                    module_count[name] += 1
        else:
            runs = defaultdict(float)
            for s, e, name, mod, st in ops:
                runs[(mod, st.get("run_id"))] += (e - s) * NS
            for (mod, _), t in runs.items():
                module_time[mod] += t
                module_count[mod] += 1
        merged = _union((max(s, lo) if lo is not None else s,
                         min(e, hi) if hi is not None else e) for s, e, *_ in ops)
        busy += sum(e - s for s, e in merged) * NS
        a = lo if lo is not None else (merged[0][0] if merged else 0.0)
        b = hi if hi is not None else (merged[-1][1] if merged else 0.0)
        edges = [a] + [x for iv in merged for x in iv] + [b]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 <= g0:
                continue
            mid = (g0 + g1) / 2
            label = _innermost(spans, parent, starts, mid)
            gaps[label] += (g1 - g0) * NS
    n_dev = max(len(dev_ops), 1)
    if lo is not None:
        window_s = (hi - lo) * NS
    else:
        all_ops = [o for ops in dev_ops.values() for o in ops]
        window_s = (max(o[1] for o in all_ops) - min(o[0] for o in all_ops)) * NS if all_ops else 0.0
    return TraceSummary(
        busy_s=busy / n_dev,
        window_s=window_s,
        devices=len(dev_ops),
        op_time=dict(op_time),
        op_count=dict(op_count),
        op_text=op_text,
        module_time=dict(module_time),
        module_count=dict(module_count),
        idle_gaps={k: v / n_dev for k, v in gaps.items()},
        op_short_time=dict(op_short_time),
    )
