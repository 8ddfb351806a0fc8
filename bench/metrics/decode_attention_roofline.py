"""kernels: the ``decode_attention`` Pallas kernel's share of its roofline.

For every cached decode pass in the traced window, one kernel call per
layer of the stage, over the real rows at their valid context lengths; the
least time each call could take (bench/benchlib/counting.py) summed, over
the kernel's device time in the trace."""
from benchlib import counting


def read(ctx):
    seconds, calls = ctx.trace.kernel("decode_attention", exclude="paged_decode_attention")
    if not calls or ctx.peaks is None:
        return None
    layers = counting.stage_layers(ctx.conf)
    bound = 0.0
    expected = 0
    for b in ctx.batches():
        if not b.is_decode:
            continue
        lengths = [ctx.recorder.context_length(b, r) for r in b.rids]
        flops, nbytes = counting.decode_attention_cost(ctx.conf, lengths)
        n = layers[b.stage - 1]
        bound += n * counting.roofline_s(flops, nbytes, ctx.peaks)
        expected += n
    ctx.log(f"decode_attention: {calls} kernel calls in the trace, {expected} counted")
    for t, n, text in ctx.trace.matches("decode_attention", exclude="paged_decode_attention"):
        ctx.log(f"   {n} x {t:.4f} s  {text}")
    return 100.0 * bound / seconds if expected else None
