"""whole step: model operations that the served tokens needed, over the
traced window's wall time times the chip's peak.

Counted per stage batch over its real rows only: a prompt pass over the
prompt's positions, a cached pass over one position against its context,
and one head evaluation per row at an exit stage or the last stage
(bench/benchlib/counting.py)."""
from benchlib import counting


def read(ctx):
    if ctx.peaks is None or ctx.window_s <= 0:
        return None
    s = ctx.conf["serve"]
    heads = set(s["exit_stages"]) | {s["num_stages"]}
    flops = 0.0
    for b in ctx.batches():
        for r in b.rids:
            n = ctx.recorder.context_length(b, r)
            tokens = 1 if b.is_decode else n
            flops += counting.stage_pass_flops(ctx.conf, b.stage, tokens, n)
        if b.stage in heads:
            flops += counting.head_flops(ctx.conf, len(b.rids))
    return 100.0 * flops / (ctx.window_s * ctx.peaks["flops_bf16"])
