"""The engine's host-span records (``repro.obs.host``) of the window's
``serve()`` calls, for the readers of host metrics.

A record is kept when its ``[t0, t1]`` holds the first submission of a
window round, so the warm-up and set-up rounds stay out.  A program that
keeps no such records gives None, and its readers stay silent.
"""
from __future__ import annotations


def window_records(ctx) -> list | None:
    try:
        from repro.obs import host
    except ImportError:
        return None
    firsts: dict = {}
    for (r, _), t in ctx.recorder.t_submit.items():
        if r in ctx.rounds and t < firsts.get(r, float("inf")):
            firsts[r] = t
    recs = [rec for rec in host.recent() if any(rec["t0"] <= t <= rec["t1"] for t in firsts.values())]
    if not recs:
        return None
    compiles = {k: sum(c[k] for rec in recs for c in rec["compiles"].values())
                for k in ("compiles", "compile_s", "traces")}
    ctx.log(f"host records: {len(recs)} serve() calls for {len(firsts)} rounds, "
            f"{batches(recs)} stage batches (recorder: {len(ctx.batches())}); in them "
            f"{compiles['compiles']} backend compiles ({compiles['compile_s']:.3f} s), "
            f"{compiles['traces']} traces")
    return recs


def batches(recs) -> int:
    return sum(rec["batches"] for rec in recs)


def seconds(recs, names, key: str = "total_s") -> float:
    """Σ ``key`` (``total_s`` or ``self_s``) of the spans ``names`` over ``recs``."""
    return sum(rec["spans"].get(n, {}).get(key, 0.0) for rec in recs for n in names)
