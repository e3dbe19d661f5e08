"""The span reduction on events with known answers, the four metrics that
read it on a toy traced run on the CPU, and the reduction of a small trace
recorded on the chip with the program's spans (data/spans_v5e.json: the
first three dispatches of a 2 s window of stablelm-3b.steady on one v5e,
recorded with `--events-out`, an option of `run.py`, and cut to those
dispatches)."""

import json
import sys
import time

import pytest

import harness
import span_reduce
import trace_reduce
from conftest import DATA

MS = 1e6  # ns


def _events(ops, modules, host):
    return {"devices": {"/device:TPU:0": {
        trace_reduce.OPS_LINE: [(n, s * MS, d * MS) for n, s, d in ops],
        trace_reduce.MODULE_LINE: [(n, s * MS, d * MS) for n, s, d in modules],
    }}, "host": [(n, s * MS, d * MS) for n, s, d in host]}


def test_spans_idle_by_span_and_stage_modules():
    # device busy [2,6] and [10,15]; the window starts at the first span (1)
    # and lasts 20 ms, so idle is [1,2] + [6,10] + [15,21] = 11 ms
    ev = _events(
        ops=[("fusion.1", 2, 4), ("dot.2", 10, 5)],
        modules=[("jit_stage_b0_2(42)", 2, 4), ("jit_stage_b2_10(43)", 10, 5),
                 ("jit_stage_b0_2(42)", 10, 0.5)],
        host=[("ppipe.schedule", 1, 0.5), ("ppipe.submit", 1.5, 1),
              ("ppipe.wait", 3, 4), ("ReadSyncFlag", 6, 4),
              ("ppipe.schedule", 8, 1), ("ppipe.wait", 12, 4)])
    r = span_reduce.reduce(ev, window_s=0.020)
    assert r["spans"] == {
        "ppipe.schedule": {"s": pytest.approx(0.0015), "n": 2},
        "ppipe.submit": {"s": pytest.approx(0.001), "n": 1},
        "ppipe.wait": {"s": pytest.approx(0.008), "n": 2}}
    assert r["idle_by_span"] == {
        "ppipe.schedule": pytest.approx(0.0015),  # [1,1.5] and [8,9]
        "ppipe.submit": pytest.approx(0.0005),  # [1.5,2]
        "ppipe.wait": pytest.approx(0.002),  # [6,7] and [15,16]
        span_reduce.OUTSIDE: pytest.approx(0.007)}  # [7,8] [9,10] [16,21]
    assert r["idle_s"] == pytest.approx(0.011)
    assert sum(r["idle_by_span"].values()) == pytest.approx(r["idle_s"])
    assert r["modules"] == {
        "jit_stage_b0_2": {"s": pytest.approx(0.0045), "n": 2},
        "jit_stage_b2_10": {"s": pytest.approx(0.005), "n": 1}}


def test_overlapping_spans_are_not_counted_twice():
    ev = _events(ops=[("a", 0, 1), ("b", 9, 1)], modules=[],
                 host=[("ppipe.wait", 2, 4), ("ppipe.tokens", 3, 5)])
    r = span_reduce.reduce(ev, window_s=0.010)
    assert r["idle_s"] == pytest.approx(0.008)  # [1,9]
    assert r["idle_by_span"]["ppipe.wait"] == pytest.approx(0.004)
    assert r["idle_by_span"]["ppipe.tokens"] == pytest.approx(0.002)
    assert sum(r["idle_by_span"].values()) == pytest.approx(r["idle_s"])


def test_program_without_spans_is_all_outside():
    ev = _events(ops=[("a", 0, 1), ("b", 3, 1)],
                 modules=[("jit_stage(7)", 0, 1), ("jit_stage(8)", 3, 1)],
                 host=[("ReadSyncFlag", 1, 2)])
    r = span_reduce.reduce(ev, window_s=0.005)
    assert r["spans"] == {}
    assert r["idle_by_span"] == {span_reduce.OUTSIDE: pytest.approx(0.003)}
    assert r["modules"] == {"jit_stage": {"s": pytest.approx(0.002), "n": 2}}


def test_every_span_with_the_prefix_is_counted():
    """A span the program adds is counted by its name, with no list of
    names to extend; host events without the prefix are not spans."""
    ev = _events(ops=[("a", 0, 1), ("b", 5, 1)], modules=[],
                 host=[("ppipe.route", 1, 2), ("ppipe.route", 3.5, 1),
                       ("ppipe.wait", 4.5, 0.5), ("pipe.other", 1, 3)])
    r = span_reduce.reduce(ev, window_s=0.006)
    assert r["spans"] == {"ppipe.route": {"s": pytest.approx(0.003), "n": 2},
                          "ppipe.wait": {"s": pytest.approx(0.0005), "n": 1}}
    assert r["idle_by_span"]["ppipe.route"] == pytest.approx(0.003)
    assert r["idle_by_span"]["ppipe.wait"] == pytest.approx(0.0005)


def test_stage_wall_inflation_pairs_calls_with_walls():
    spans = {"modules": {"jit_stage_b0_2": {"s": 1.0, "n": 10},
                         "jit_stage_b2_10": {"s": 5.0, "n": 10}}}
    fb = [{"blocks": [0, 2], "measured_wall_s": 3.0, "observed": 10,
           "scale_peak": 2.5},
          {"blocks": [2, 10], "measured_wall_s": 5.5, "observed": 10,
           "scale_peak": 1.25}]
    ctx = {"spans": spans, "feedback": fb}
    assert harness.read_metric("stage_wall_inflation", ctx) == 3.0
    assert harness.read_metric("lat_scale_max", ctx) == 2.5
    fb[1]["observed"] = 9
    assert harness.read_metric("stage_wall_inflation", ctx) is None
    assert harness.read_metric("lat_scale_max", {"feedback": None}) is None


def test_toy_traced_run_reads_the_host_metrics(conf):
    """A traced toy run on the CPU: `run_cell` puts the program's spans, the
    window's feedback counters and every device op in its context, and
    reads the four metrics from them; with no device plane there is no
    stage time to pair with."""
    r, ctx = harness.run_cell("stablelm-3b.steady", 2**31 + 3, 2.0, True,
                              time.perf_counter(), conf=conf,
                              require_chip=False, log=sys.stderr)
    assert r["correct"] is True
    got = {k: v["value"] for k, v in r["metrics"].items()}
    assert {"sched_us_per_dispatch", "host_us_per_batch",
            "lat_scale_max"} <= set(got)
    assert "stage_wall_inflation" not in got
    n = len(ctx["dispatches"])
    spans = ctx["spans"]["spans"]
    assert spans["ppipe.submit"]["n"] == spans["ppipe.tokens"]["n"] == n
    assert spans["ppipe.wait"]["n"] <= 2 * n
    assert sum(st["observed"] for st in ctx["feedback"]) == 2 * n
    assert got["lat_scale_max"] >= max(st["scale_peak"]
                                       for st in ctx["feedback"])
    assert 0 < got["sched_us_per_dispatch"] < got["host_us_per_batch"]


def _with_stage_plane(read):
    """`read` (`trace_reduce.read_xplane`) with a device plane standing in
    for the chip's: each stage call's host dispatch event
    (`PjitFunction(stage_b{i}_{j})`, the outer of the two nested ones the
    profiler records per call) becomes a module and an op
    `jit_stage_b{i}_{j}` of the same span."""
    def with_plane(logdir):
        ev = read(logdir)
        calls, end = [], {}
        for name, s, d in sorted(ev["host"], key=lambda e: (e[1], -e[2])):
            if name.startswith("PjitFunction(stage_b") and \
                    s >= end.get(name, -1.0):
                calls.append((f"jit_{name[len('PjitFunction('):-1]}(0)", s, d))
                end[name] = s + d
        ev["devices"]["/device:TPU:0"] = {trace_reduce.MODULE_LINE: calls,
                                          trace_reduce.OPS_LINE: calls}
        return ev
    return with_plane


def test_toy_traced_run_reads_all_four_with_a_device_plane(conf, monkeypatch):
    """A traced toy run whose trace has a device plane (standing in for the
    chip's): `run_cell` keeps every op with its calls in
    `ctx["trace"]["ops"]`, the breakdown stays its top ten, and all four
    metrics are read; `stage_wall_inflation` pairs each stage's walls with
    its own module by block range."""
    monkeypatch.setattr(trace_reduce, "read_xplane",
                        _with_stage_plane(trace_reduce.read_xplane))
    r, ctx = harness.run_cell("stablelm-3b.steady", 2**31 + 4, 2.0, True,
                              time.perf_counter(), conf=conf,
                              require_chip=False, log=sys.stderr)
    assert r["correct"] is True
    got = {k: v["value"] for k, v in r["metrics"].items()}
    assert {"sched_us_per_dispatch", "host_us_per_batch",
            "stage_wall_inflation", "lat_scale_max"} <= set(got)
    n = len(ctx["dispatches"])
    ops = ctx["trace"]["ops"]
    assert {k: v["n"] for k, v in ops.items()} == {
        "jit_stage_b0_2(0)": n, "jit_stage_b2_4(0)": n}
    assert r["breakdown"]["device_ops"] == sorted(
        ([k, v["s"]] for k, v in ops.items()), key=lambda kv: -kv[1])
    modules = ctx["spans"]["modules"]
    assert got["stage_wall_inflation"] == max(
        st["measured_wall_s"] / modules["jit_stage_b{}_{}".format(
            *st["blocks"])]["s"] for st in ctx["feedback"])


def test_recorded_chip_trace_with_spans():
    rec = json.loads((DATA / "spans_v5e.json").read_text())
    r = span_reduce.reduce(rec, rec["window_s"])
    t = trace_reduce.summarize(rec, rec["window_s"])
    n = rec["dispatches"]
    spans = r["spans"]
    assert set(spans) == {"ppipe.schedule", "ppipe.tokens", "ppipe.submit",
                          "ppipe.wait"}
    assert spans["ppipe.submit"]["n"] == spans["ppipe.tokens"]["n"] == n
    assert spans["ppipe.wait"]["n"] == 2 * n  # each stage of each batch
    assert spans["ppipe.schedule"]["n"] >= n
    # the two stages of the pinned plan read apart, one call per dispatch
    assert set(r["modules"]) == {"jit_stage_b0_2", "jit_stage_b2_10"}
    assert all(m["n"] == n for m in r["modules"].values())
    assert r["modules"]["jit_stage_b2_10"]["s"] > 4 * r["modules"][
        "jit_stage_b0_2"]["s"]
    # idle by span sums to the window less the device's busy time
    idle = t["window_s"] - t["busy_s"]
    assert r["idle_s"] == pytest.approx(idle, rel=0.01)
    assert sum(r["idle_by_span"].values()) == pytest.approx(idle, rel=0.01)
    assert max(r["idle_by_span"], key=r["idle_by_span"].get) == "ppipe.wait"
    # the trace's longest idle gaps are named by the program's spans
    assert t["idle_gaps"][0][0] == "ppipe.wait"
