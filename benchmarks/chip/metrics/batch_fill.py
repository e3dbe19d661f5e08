"""batch_fill: mean batch size of the window's dispatches over the pinned
plan's unified batch (data plane: `repro.dataplane.batcher`)."""


def read(ctx):
    sizes = [d.batch_size for d in ctx["dispatches"]]
    return sum(sizes) / len(sizes) / ctx["plan_batch"] if sizes else None
