"""latency_p50_ms: median over all window requests of completion minus
arrival, on the data plane's clock (stage durations measured on the chip);
a request that was dropped counts as infinitely late."""

from harness import percentile, window_latencies_ms


def read(ctx):
    return percentile(window_latencies_ms(ctx), 50)
