"""host-device hand-off: host milliseconds per stage batch taking the
copies of the residual and the heads' outputs to the host (``serve.pull``,
after ``serve.wait`` has seen them ready), in the engine's host-span
records of the window's ``serve()`` calls."""
from benchlib import hostspans


def read(ctx):
    recs = hostspans.window_records(ctx)
    if not recs or not hostspans.batches(recs):
        return None
    return 1e3 * hostspans.seconds(recs, ("serve.pull",)) / hostspans.batches(recs)
