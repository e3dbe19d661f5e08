"""The trace reduction on events with known answers, and on a small trace
recorded on the chip (data/trace_v5e.json: a one-second window of
stablelm-3b served on one v5e, reduced by `read_xplane`)."""

import json

import pytest

import trace_reduce
from conftest import DATA

MS = 1e6  # ns


def _events(ops, modules, host=()):
    return {"devices": {"/device:TPU:0": {
        trace_reduce.OPS_LINE: [(n, s * MS, d * MS) for n, s, d in ops],
        trace_reduce.MODULE_LINE: [(n, s * MS, d * MS) for n, s, d in modules],
    }}, "host": [(n, s * MS, d * MS) for n, s, d in host]}


def test_union_gaps_and_stage_programs():
    ev = _events(
        ops=[("fusion.1", 0, 4), ("fusion.2", 2, 4), ("dot.3", 10, 5),
             ("fusion.1", 20, 2)],
        modules=[("jit_stage(42)", 0, 6), ("jit_stage(43)", 10, 5),
                 ("jit__lambda_(7)", 20, 2)],
        host=[("PjitFunction(stage)", 6.5, 3), ("wait", 15, 0.5)])
    s = trace_reduce.summarize(ev, window_s=0.025)
    assert s["busy_s"] == pytest.approx(0.013)  # [0,6] + [10,15] + [20,22]
    assert s["stage_s"] == pytest.approx(0.011) and s["stage_calls"] == 2
    assert s["device_ops"][0] == ["fusion.1", pytest.approx(0.006)]
    assert s["idle_gaps"] == [["wait", pytest.approx(0.005)],
                              ["PjitFunction(stage)", pytest.approx(0.004)]]


def test_gap_without_host_activity_is_host_python():
    ev = _events(ops=[("a", 0, 1), ("b", 3, 1)], modules=[])
    assert trace_reduce.summarize(ev, 0.004)["idle_gaps"] == [
        ["host python", pytest.approx(0.002)]]


def test_no_device_plane_reads_nothing():
    assert trace_reduce.summarize({"devices": {}, "host": []}, 1.0) is None


def test_recorded_chip_trace():
    rec = json.loads((DATA / "trace_v5e.json").read_text())
    s = trace_reduce.summarize(rec, rec["window_s"])
    assert 0 < s["busy_s"] < s["window_s"]
    assert s["stage_calls"] == rec["stage_calls"]
    # nothing but the stage programs ran in these three batches
    assert s["stage_s"] == pytest.approx(s["busy_s"], rel=0.01)
    assert s["device_ops"][0][0] == "%while.23"  # the scan over layers
    assert len(s["device_ops"]) == 10 and len(s["idle_gaps"]) == 10
