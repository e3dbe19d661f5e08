"""peak_hbm_gib: the device's `peak_bytes_in_use` after the window, in GiB
(weights, compiled programs' buffers, in-flight batches)."""


def read(ctx):
    b = ctx["memory_peak_bytes"]
    return b / 2**30 if b else None
