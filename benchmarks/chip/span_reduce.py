"""The program's host spans and each stage program's device time, from the
events `trace_reduce.read_xplane` returns.

The serving path marks its host work with `jax.profiler.TraceAnnotation`
spans named `ppipe.*` (PREFIX), on the profiler's host plane, whose clock
the device planes share.  `reduce` gives:

* ``spans``: for each span name with that prefix, its seconds and its
  count, so a span the program adds is counted with no change here;
* ``idle_by_span``: the device's idle seconds, split by the span the host
  was in at each instant, and ``outside spans`` for the rest.  The idle time
  is the window's complement of the union of the device's op intervals, as
  `trace_reduce` counts busy time.  The window is `window_s` long and starts
  at the earlier of the first span and the first device op (the serve
  call's first scheduling round), so the parts sum to window - busy;
  ``idle_s`` is that sum, averaged over the chips as busy time is;
* ``modules``: for each `jit_stage*` module, by name with its
  ``(fingerprint)`` stripped, its device seconds and calls, averaged over
  the chips.  The stage programs are named by block range
  (`jit_stage_b{i}_{j}`), so each stage of a pinned plan reads apart.

A program that has no spans gives no span names, and all its idle time
falls outside spans.  The prefix is spelled here because the benchmark
imports nothing of the program.
"""

from __future__ import annotations

from trace_reduce import MODULE_LINE, OPS_LINE, STAGE_MODULE, _module, _union

PREFIX = "ppipe."
OUTSIDE = "outside spans"


def _flat(intervals: list[tuple[float, float, str]]) -> list:
    """Spans sorted by start and clipped so no two overlap: each instant is
    given to at most one span (the one that started first)."""
    out: list[tuple[float, float, str]] = []
    end = -float("inf")
    for s, e, name in sorted(intervals):
        s = max(s, end)
        if e > s:
            out.append((s, e, name))
            end = e
    return out


def _idle_by_span(gaps: list[tuple[float, float]], spans: list) -> dict:
    """Seconds of each gap covered by each span, and OUTSIDE for the rest;
    both lists sorted by start and neither overlapping itself."""
    out = {OUTSIDE: 0.0}
    j = 0
    for gs, ge in gaps:
        while j < len(spans) and spans[j][1] <= gs:
            j += 1
        covered = 0.0
        k = j
        while k < len(spans) and spans[k][0] < ge:
            s, e, name = spans[k]
            ov = min(ge, e) - max(gs, s)
            if ov > 0:
                out[name] = out.get(name, 0.0) + ov * 1e-9
                covered += ov
            k += 1
        out[OUTSIDE] += (ge - gs - covered) * 1e-9
    return out


def reduce(events: dict, window_s: float) -> dict:
    """Span seconds and counts, device idle seconds by span, and stage
    module seconds and calls of a traced window of `window_s` seconds."""
    spans = [(s, s + d, name) for name, s, d in events["host"]
             if name.startswith(PREFIX)]
    sums: dict[str, dict] = {}
    for s, e, name in spans:
        v = sums.setdefault(name, {"s": 0.0, "n": 0})
        v["s"] += (e - s) * 1e-9
        v["n"] += 1
    flat = _flat(spans)
    devices = events["devices"]
    idle: dict[str, float] = {}
    modules: dict[str, dict] = {}
    for lines in devices.values():
        ops = lines.get(OPS_LINE) or lines.get(MODULE_LINE, [])
        busy = _union([(s, s + d) for _, s, d in ops])
        if not busy:
            continue
        t0 = min(busy[0][0], flat[0][0]) if flat else busy[0][0]
        t1 = t0 + window_s * 1e9
        edges = [t0, *(x for iv in busy for x in iv), t1]
        gaps = [(max(a, t0), min(b, t1)) for a, b in zip(edges[::2], edges[1::2])]
        for name, v in _idle_by_span([g for g in gaps if g[1] > g[0]],
                                     flat).items():
            idle[name] = idle.get(name, 0.0) + v
        for name, _, d in lines.get(MODULE_LINE, []):
            mod = _module(name)
            if mod.startswith(STAGE_MODULE):
                m = modules.setdefault(mod, {"s": 0.0, "n": 0})
                m["s"] += d * 1e-9
                m["n"] += 1
    n = max(1, len(devices))
    return {
        "spans": sums,
        "idle_by_span": {k: v / n for k, v in idle.items()},
        "idle_s": sum(idle.values()) / n,
        "modules": {k: {"s": v["s"] / n, "n": v["n"] // n}
                    for k, v in modules.items()},
    }
