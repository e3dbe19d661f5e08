"""queue_delay_p95_ms: 95th percentile (nearest rank) of the time each
dispatched window request waited in its queue, on the data plane's clock
(`Telemetry.queue_delay_s`, `repro.dataplane.queues`)."""

from harness import percentile


def read(ctx):
    d = ctx["queue_delay_s"]
    return percentile(d, 95) * 1e3 if d else None
