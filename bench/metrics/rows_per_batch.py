"""scheduler (serving/engine.py serve loop): real rows per stage batch,
counted at the engine's ``on_batch`` hook (padding excluded)."""


def read(ctx):
    batches = ctx.batches()
    if not batches:
        return None
    return sum(len(b.rids) for b in batches) / len(batches)
