"""serve_wall_ratio: host wall seconds of the window's serve call over the
window's seconds of arrivals.  Under 1 the host replays the window faster
than real time; over 1 the chip and the host loop did not keep pace."""


def read(ctx):
    return ctx["wall_s"] / ctx["seconds"]
