#!/usr/bin/env python3
"""Traced runs of a cell that also read the program's host spans and its
measured-feedback counters.

    python3 benchmarks/chip/host_spans.py --workload stablelm-3b.steady \\
        --seconds 51 --seeds 2147484101 2147484102 2147484103

Each seed is one run of `harness.run_cell` with `--trace 1`, in this process
and one after another.  `run_cell` reduces its trace with
`trace_reduce.summarize` and reads the feedback state with
`harness.feedback_state` (at the window's start, and after it); for the run
this script wraps both:

* at the window's start it resets each stage's scale peak
  (`FeedbackController.reset_peaks`) and takes the counters
  (`measured_wall_s`, `observed`); after the window it keeps their deltas
  and the peaks (`ctx["feedback"]`) and the window's dispatches;
* it reduces the same trace events with `span_reduce` (`ctx["spans"]`).

It then reads `metrics/sched_us_per_dispatch.py`, `host_us_per_batch.py`,
`stage_wall_inflation.py` and `lat_scale_max.py` from that context and
prints, per seed, a `[spans]` line on stderr (per-span seconds and counts,
device idle by span, each stage's measured wall against its device time)
and one JSON line: the run's result with those four metrics added.
`--events-out` also writes the last run's trace events (for tests/data).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))

import harness  # noqa: E402
import span_reduce  # noqa: E402
import trace_reduce  # noqa: E402

# the metrics this script reads, with their units
METRICS = {"sched_us_per_dispatch": "us", "host_us_per_batch": "us",
           "stage_wall_inflation": "ratio", "lat_scale_max": "ratio"}


def feedback_counters(session) -> list[dict] | None:
    """Each plan stage's measured-feedback counters: the sum and number of
    the walls `FeedbackController.observe` received and the highest scale it
    set since `reset_peaks`; None where the program keeps none."""
    dp = session.dataplane
    fb = dp.fb
    if fb is None or not hasattr(fb, "scale_peak"):
        return None
    out = []
    for p in dp.rt.pipelines:
        for si, sp in enumerate(dp.rt.plan.pipelines[p.pipeline_id].stages):
            key = (p.pipeline_id, si)
            out.append({"pipeline": p.pipeline_id, "stage": si,
                        "blocks": [sp.block_start, sp.block_end],
                        "measured_wall_s": fb.measured_wall_s.get(key, 0.0),
                        "observed": fb.observed.get(key, 0),
                        "scale_peak": fb.scale_peak.get(key)})
    return out


def window_feedback(before: list[dict], after: list[dict]) -> list[dict]:
    """The window's counters: `after`'s, less `before`'s sums and counts."""
    return [{**a, "measured_wall_s": a["measured_wall_s"] - b["measured_wall_s"],
             "observed": a["observed"] - b["observed"]}
            for b, a in zip(before, after)]


class _Reader:
    """Wraps `harness.feedback_state` and `trace_reduce.summarize` for one
    run and keeps what the four metrics read."""

    def __init__(self):
        self.ctx: dict = {}
        self._calls = 0
        self._before = None
        self._n_disp = 0

    def feedback_state(self, session):
        state = self._orig_state(session)
        tel = session.telemetry
        if self._calls == 0:  # the window's start
            if hasattr(session.dataplane.fb, "reset_peaks"):
                session.dataplane.fb.reset_peaks()
            self._before = feedback_counters(session)
            self._n_disp = len(tel.dispatches)
        else:
            after = feedback_counters(session)
            self.ctx["feedback"] = (window_feedback(self._before, after)
                                    if after is not None else None)
            self.ctx["dispatches"] = tel.dispatches[self._n_disp:]
        self._calls += 1
        return state

    def summarize(self, events, window_s):
        self.ctx["spans"] = span_reduce.reduce(events, window_s)
        self.ctx["wall_s"] = window_s
        self.ctx["events"] = events
        return self._orig_summarize(events, window_s)

    def __enter__(self):
        self._orig_state = harness.feedback_state
        self._orig_summarize = trace_reduce.summarize
        harness.feedback_state = self.feedback_state
        trace_reduce.summarize = self.summarize
        return self

    def __exit__(self, *exc):
        harness.feedback_state = self._orig_state
        trace_reduce.summarize = self._orig_summarize


def spans_line(ctx: dict) -> str:
    """Per-span seconds and counts, idle by span, and each stage's measured
    wall against its device time."""
    sp = ctx["spans"]
    spans = " ".join(f"{k}={v['s']:.6f}s/{v['n']}"
                     for k, v in sp["spans"].items())
    idle = " ".join(f"{k}={v:.6f}s" for k, v in sorted(
        sp["idle_by_span"].items(), key=lambda kv: -kv[1]))
    stages = []
    for s in ctx.get("feedback") or ():
        name = "jit_stage_b{}_{}".format(*s["blocks"])
        dev = sp["modules"].get(name, {"s": 0.0, "n": 0})
        stages.append(f"{name}: wall {s['measured_wall_s']:.6f}s/"
                      f"{s['observed']} device {dev['s']:.6f}s/{dev['n']} "
                      f"scale peak {s['scale_peak']}")
    return (f"[spans] {spans}; idle {sp['idle_s']:.6f}s: {idle}; "
            f"stages {'; '.join(stages)}")


def run(cell: str, seed: int, seconds: float, *, conf: dict | None = None,
        require_chip: bool = True, log=sys.stderr) -> tuple[dict, dict]:
    """One traced run of `cell`: its result line with the four metrics
    added, and the context they were read from."""
    with _Reader() as reader:
        result = harness.run_cell(cell, seed, seconds, True,
                                  time.perf_counter(), conf=conf,
                                  require_chip=require_chip, log=log)
    ctx = reader.ctx
    if "spans" in ctx:
        print(spans_line(ctx), file=log)
    for name, unit in METRICS.items():
        value = harness.read_metric(name, ctx) if "spans" in ctx else None
        if value is None:
            print(f"[metric] {name}: nothing to read", file=log)
            continue
        result["metrics"][name] = {"value": value, "unit": unit}
    return result, ctx


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--events-out", default=None)
    args = ap.parse_args()
    ctx = {}
    for seed in args.seeds:
        result, ctx = run(args.workload, seed, args.seconds)
        print(json.dumps({"seed": seed, **result}), flush=True)
    if args.events_out and "events" in ctx:
        Path(args.events_out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.events_out).write_text(json.dumps(
            {"window_s": ctx["wall_s"], **ctx["events"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
