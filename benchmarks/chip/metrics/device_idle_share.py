"""device_idle_share: 1 - (union of the device's op intervals in the
profiler trace) / (wall seconds of the traced serve call)."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr["busy_s"] <= 0:
        return None
    return 1.0 - tr["busy_s"] / tr["window_s"]
