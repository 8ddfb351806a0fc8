"""Host spans of the serving engine: what the host did, on two clocks.

A :class:`span` is a context manager around one piece of host work.  It
opens a ``jax.profiler.TraceAnnotation`` of its name, so a profiler trace
holds the span on the clock its device operations use and can charge each
idle gap of the device to the host work around it.  When the span belongs
to a :class:`Record`, it also adds its count, total and **self** time (the
total less the part its child spans cover) to that record on
``time.perf_counter_ns``, the clock request latencies are taken on.
Spans nest through one stack of the spans open now.

``CollaborativeEngine.serve`` keeps one record per call (``ServeStats.host``,
``ServeStats.report()["host"]``); every closed record also goes into a
process-wide ring of the last :data:`RING` (:func:`recent`).  A record is
aggregates, not events, so it is always kept; the annotations are always
opened and the profiler decides whether to record them.

Compile counter: one ``jax.monitoring`` listener charges every jaxpr trace
and backend compile, with its seconds, to the innermost open span (to its
record, and to the process-wide :func:`compiles`), or to ``"unscoped"``
when no span is open.

Span vocabulary of ``serve()`` (the self times tile the call):

  serve.setup       entry to the first event: arrivals, routing CDF, caches
  serve.event       one heap event from its pop; self time is scheduling
  serve.assemble    one batch's inputs: tokens, slots, hidden states, tables
  serve.<program>   host dispatch of one ``StagePrograms`` call (``embed``,
                    ``stage_prefill``, ``stage_decode``, ``slot_write``,
                    ``paged_slot_write``, ``paged_stage_decode``,
                    ``run_stage``, ``exit_head``, ``final_head``), with
                    ``stage``, ``node`` and ``rows`` as annotation arguments
  serve.wait        blocked until the residual, then the heads, are ready
                    (their copies to the host already requested)
  serve.pull        taking those copies on the host
  serve.emit        instrumentation-stream calls (observer cost)
  serve.finish      end of the event loop to the return
  engine.configure  a DTO-EE configuration phase (also outside ``serve()``)
"""
from __future__ import annotations

from collections import deque
from time import perf_counter_ns

import jax

__all__ = ["RING", "Record", "span", "recent", "compiles"]

#: closed records kept by :func:`recent`
RING = 64
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
NS = 1e-9

_open: list["span"] = []  # spans open now, innermost last
_recent: deque = deque(maxlen=RING)
_compiles: dict[str, list] = {}  # span name -> [compiles, compile_ns, traces]


class span:
    """One host span: a profiler annotation ``name`` carrying ``args`` and,
    under a ``record``, the span's count, total and self time there."""

    __slots__ = ("name", "record", "_ann", "_t0", "_child")

    def __init__(self, name: str, record: "Record | None" = None, **args):
        self.name = name
        self.record = record
        self._ann = jax.profiler.TraceAnnotation(name, **args)

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._child = 0
        _open.append(self)
        self._t0 = perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        dt = perf_counter_ns() - self._t0
        _open.pop()
        if _open:
            _open[-1]._child += dt
        rec = self.record
        if rec is not None:
            a = rec._spans.get(self.name)
            if a is None:
                a = rec._spans[self.name] = [0, 0, 0]
            a[0] += 1
            a[1] += dt
            a[2] += dt - self._child
        self._ann.__exit__(exc_type, exc, tb)


class Record:
    """What the host did in one ``serve()`` call, from its creation to
    :meth:`close`: per span name ``(count, total_s, self_s)``, the stage
    batches run, and the compiles, compile seconds and traces charged to
    each span."""

    def __init__(self):
        self.t0 = perf_counter_ns()
        self.batches = 0
        self._spans: dict[str, list] = {}  # name -> [count, total_ns, self_ns]
        self._compiles: dict[str, list] = {}

    def close(self) -> dict:
        """End the record, put it in the ring and return it as a dict."""
        out = {
            "t0": self.t0 * NS,
            "t1": perf_counter_ns() * NS,
            "batches": self.batches,
            "spans": {
                n: {"count": c, "total_s": t * NS, "self_s": s * NS}
                for n, (c, t, s) in self._spans.items()
            },
            "compiles": _compile_view(self._compiles),
        }
        _recent.append(out)
        return out


def recent() -> list[dict]:
    """The last :data:`RING` closed records, oldest first."""
    return list(_recent)


def compiles() -> dict:
    """Process-wide compiles, compile seconds and traces by the innermost
    span open when each happened (``"unscoped"``: none was)."""
    return _compile_view(_compiles)


def _compile_view(table: dict) -> dict:
    return {
        n: {"compiles": c, "compile_s": ns * NS, "traces": t}
        for n, (c, ns, t) in table.items()
    }


def _charge(table: dict, name: str, compiled: bool, seconds: float) -> None:
    a = table.get(name)
    if a is None:
        a = table[name] = [0, 0, 0]
    if compiled:
        a[0] += 1
        a[1] += int(seconds * 1e9)
    else:
        a[2] += 1


def _on_event(event: str, seconds: float, **_) -> None:
    if event == COMPILE_EVENT:
        compiled = True
    elif event == TRACE_EVENT:
        compiled = False
    else:
        return
    inner = _open[-1] if _open else None
    name = inner.name if inner is not None else "unscoped"
    _charge(_compiles, name, compiled, seconds)
    if inner is not None and inner.record is not None:
        _charge(inner.record._compiles, name, compiled, seconds)


jax.monitoring.register_event_duration_secs_listener(_on_event)
