"""From a JAX profiler trace to the device numbers the benchmark reports.

`read_xplane` turns the profiler's `.xplane.pb` into plain event lists:
for each TPU device plane the "XLA Modules" and "XLA Ops" lines, and the
host's TraceMe events.  `summarize` reduces those lists:

* busy seconds: the union of the device's op intervals (each chip alike,
  averaged over the chips);
* stage-program seconds and calls: the "XLA Modules" events whose module
  name starts with `jit_stage`, the serving path's stage programs;
* every device op's seconds and calls, by op name (``ops``), and the ten
  that took most time (``device_ops``);
* the ten longest gaps between busy intervals, each named by the host event
  that overlaps it most ("host python" where no host event does).

The two steps are apart so that `summarize` can be checked on a small
recorded trace (tests/data) without the profiler.
"""

from __future__ import annotations

from pathlib import Path

STAGE_MODULE = "jit_stage"
MODULE_LINE, OPS_LINE = "XLA Modules", "XLA Ops"


def read_xplane(logdir: str | Path) -> dict:
    from jax.profiler import ProfileData

    files = sorted(Path(logdir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = ProfileData.from_file(str(files[-1]))
    devices: dict[str, dict[str, list]] = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = devices.setdefault(plane.name, {})
            for line in plane.lines:
                if line.name in (MODULE_LINE, OPS_LINE):
                    lines[line.name] = [
                        (_op(e.name), float(e.start_ns), float(e.duration_ns))
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events if e.duration_ns > 0)
    return {"devices": {k: v for k, v in devices.items() if v},
            "host": host}


def _union(intervals: list[tuple[float, float]]) -> list[list[float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _op(name: str) -> str:
    """An op's name without its HLO text ("%while.23 = (...) while(...)")."""
    return name.split(" = ")[0]


def _module(name: str) -> str:
    return name.split("(")[0]


def summarize(events: dict, window_s: float) -> dict | None:
    """Device numbers of a traced window; None when no device op ran."""
    devices = events["devices"]
    if not devices:
        return None
    busy_s = stage_s = 0.0
    stage_calls = 0
    ops: dict[str, dict] = {}
    gaps: list[tuple[float, float]] = []
    for lines in devices.values():
        op_events = lines.get(OPS_LINE) or lines.get(MODULE_LINE, [])
        merged = _union([(s, s + d) for _, s, d in op_events])
        busy_s += sum(e - s for s, e in merged) * 1e-9
        gaps += [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
        for name, _, d in op_events:
            op = ops.setdefault(name, {"s": 0.0, "n": 0})
            op["s"] += d * 1e-9
            op["n"] += 1
        for name, _, d in lines.get(MODULE_LINE, []):
            if _module(name).startswith(STAGE_MODULE):
                stage_s += d * 1e-9
                stage_calls += 1
    n = len(devices)
    gaps.sort(key=lambda g: g[0] - g[1])
    ops = {k: {"s": v["s"] / n, "n": v["n"] // n} for k, v in ops.items()}
    return {
        "busy_s": busy_s / n,
        "window_s": window_s,
        "stage_s": stage_s / n,
        "stage_calls": stage_calls // n,
        "ops": ops,
        "device_ops": sorted(([k, v["s"]] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:10],
        "idle_gaps": [[_host_activity(events["host"], s, e), (e - s) * 1e-9]
                      for s, e in gaps[:10]],
    }


def _host_activity(host: list, start: float, end: float) -> str:
    best, best_overlap = "host python", 0.0
    for name, s, d in host:
        overlap = min(end, s + d) - max(start, s)
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best
