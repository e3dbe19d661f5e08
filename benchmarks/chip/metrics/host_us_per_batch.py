"""host_us_per_batch: host microseconds the serve call took per window
dispatch, less the time it was blocked on the chip for measured feedback:
(serve wall - the `ppipe.wait` spans' seconds) / dispatches.  The spans
come from the profiler trace (`span_reduce`); where the trace has none of
the program's spans there is nothing to read."""


def read(ctx):
    spans = (ctx.get("spans") or {}).get("spans", {})
    n = len(ctx["dispatches"])
    if "ppipe.submit" not in spans or not n:
        return None
    wait_s = spans.get("ppipe.wait", {"s": 0.0})["s"]
    return 1e6 * (ctx["wall_s"] - wait_s) / n
