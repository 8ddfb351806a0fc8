"""entry: host milliseconds per ``serve()`` call from entry to its first
event (``serve.setup``: arrivals, routing, cache allocation, submissions),
in the engine's host-span records of the window's calls."""
from benchlib import hostspans


def read(ctx):
    recs = hostspans.window_records(ctx)
    if not recs:
        return None
    return 1e3 * hostspans.seconds(recs, ("serve.setup",)) / len(recs)
