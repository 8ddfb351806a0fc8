"""stage programs: device milliseconds per cached one-token stage call
(``stage_decode``), from the trace's program events."""


def read(ctx):
    seconds, calls = ctx.trace.program("stage_decode")
    return 1e3 * seconds / calls if calls else None
