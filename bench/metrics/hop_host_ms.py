"""engine host path: host milliseconds per stage batch spent scheduling,
assembling inputs and calling observers: the self time of ``serve.event``,
``serve.assemble`` and ``serve.emit`` in the engine's host-span records of
the window's ``serve()`` calls, over their stage batches."""
from benchlib import hostspans


def read(ctx):
    recs = hostspans.window_records(ctx)
    if not recs or not hostspans.batches(recs):
        return None
    host_s = hostspans.seconds(recs, ("serve.event", "serve.assemble", "serve.emit"), "self_s")
    return 1e3 * host_s / hostspans.batches(recs)
