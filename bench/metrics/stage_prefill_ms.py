"""stage programs: device milliseconds per prompt pass of a stage
(``stage_prefill``), from the trace's program events."""


def read(ctx):
    seconds, calls = ctx.trace.program("stage_prefill")
    return 1e3 * seconds / calls if calls else None
