"""kernels: the fused ``exit_confidence`` head kernel's share of its
roofline: one call per batch at an exit stage or the last stage, over its
real rows against the whole bf16 head (bench/benchlib/counting.py), over
the kernel's device time in the trace."""
from benchlib import counting


def read(ctx):
    seconds, calls = ctx.trace.kernel("exit_confidence")
    if not calls or ctx.peaks is None:
        return None
    s = ctx.conf["serve"]
    heads = set(s["exit_stages"]) | {s["num_stages"]}
    bound = 0.0
    expected = 0
    for b in ctx.batches():
        if b.stage in heads:
            flops, nbytes = counting.exit_confidence_cost(ctx.conf, len(b.rids))
            bound += counting.roofline_s(flops, nbytes, ctx.peaks)
            expected += 1
    ctx.log(f"exit_confidence: {calls} kernel calls in the trace, {expected} counted")
    for t, n, text in ctx.trace.matches("exit_confidence"):
        ctx.log(f"   {n} x {t:.4f} s  {text}")
    return 100.0 * bound / seconds if expected else None
