"""goodput_rps: window requests completed by their deadline, per second of
max(window, wall time of the serve call).  A drop, a shed request or a late
completion is a miss; a serve that takes longer than the window on the
host's clock is charged its wall time."""


def read(ctx):
    ok = sum(1 for r in ctx["requests"]
             if (o := ctx["outcomes"].get(r.req_id)) is not None and o.ok)
    return ok / max(ctx["seconds"], ctx["wall_s"])
