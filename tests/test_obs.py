"""repro.obs: decision-identity of the off path, span-tree well-formedness,
windowed-metric conservation, strict-JSON exports, and the api wiring.

The load-bearing property is the first one: attaching an Observer (at any
level) must not change a single scheduling decision — the observer only
*watches* the plane.  The suite proves it on the epoch-lifecycle swap
scenario and on the equivalence suite's randomized synthetic runtimes."""

import json

import pytest

# sibling test modules double as scenario libraries (pytest puts tests/ on
# sys.path): the swap scenario from the epoch-lifecycle suite, randomized
# runtimes/traces from the scheduler decision-equivalence suite
import test_epoch_lifecycle as lifecycle
import test_sched_equivalence as equiv
from test_dataplane import real_pipeline  # noqa: F401  (fixture)
from repro.api import LifecycleError
from repro.core.runtime import build_runtime
from repro.dataplane import DataPlane
from repro.obs import (
    DecisionJournal,
    ObsConfig,
    Observer,
    WindowedMetrics,
    request_trees,
)
from repro.obs.journal import SCHEMA_VERSION as JOURNAL_SCHEMA_VERSION


def _swap_scenario(observer=None, *, horizon=4.0, seed=9, load=0.85,
                   swap_times=(0.5, 1.5, 2.5)):
    """The epoch-lifecycle scenario: two plans, scripted mid-trace swaps."""
    profs, plan_a, plan_b = lifecycle._setup()
    trace = lifecycle._trace(profs, plan_a, horizon, load=load, seed=seed)
    dp = DataPlane(build_runtime(plan_a, profs), observer=observer)
    state = {}
    dp.arrival_hooks.append(lifecycle._swap_script(
        dp, profs, plan_a, plan_b, list(swap_times), state))
    tel = dp.serve(trace)
    return dp, tel, trace


def _outcomes(tel):
    return {o.req_id: o.completion_s for o in tel.outcomes}


# ---------------------------------------------------------------------------
# Decision identity: the observer only watches
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("level", ["aggregate", "trace"])
def test_observer_is_decision_identical_under_swaps(level):
    _, tel_off, trace = _swap_scenario(None)
    _, tel_on, _ = _swap_scenario(Observer(ObsConfig(level=level)))
    assert _outcomes(tel_on) == _outcomes(tel_off)
    assert len(tel_on.outcomes) == len(trace)
    assert tel_on.attainment == tel_off.attainment
    assert tel_on.plan_swaps == tel_off.plan_swaps
    assert [(d.t_s, d.pipeline_id, d.batch_size, d.epoch)
            for d in tel_on.dispatches] == \
           [(d.t_s, d.pipeline_id, d.batch_size, d.epoch)
            for d in tel_off.dispatches]
    assert tel_on.scheduler == tel_off.scheduler


@pytest.mark.parametrize("seed", [3, 17, 41])
def test_observer_is_decision_identical_on_random_runtimes(seed):
    rt_off = equiv._rand_runtime(seed, n_models=2, shared_nodes=True)
    rt_on = equiv._rand_runtime(seed, n_models=2, shared_nodes=True)
    trace = equiv._rand_trace(seed, rt_off, load=1.2, horizon=0.5)
    tel_off = DataPlane(rt_off).serve(list(trace))
    obs = Observer(ObsConfig(level="trace"))
    tel_on = DataPlane(rt_on, observer=obs).serve(list(trace))
    assert _outcomes(tel_on) == _outcomes(tel_off)
    assert tel_on.attainment == tel_off.attainment
    # the journal really observed the run it did not perturb
    assert len(obs.journal.select(kind="batch.dispatch")) == \
        len(tel_on.dispatches)


def test_off_path_is_decision_identical_under_the_profiler(tmp_path):
    """With observability off, an active JAX profiler (which makes the
    plane's host spans record) changes no decision."""
    import jax

    _, tel_off, trace = _swap_scenario(None)
    with jax.profiler.trace(str(tmp_path)):
        _, tel_on, _ = _swap_scenario(None)
    assert _outcomes(tel_on) == _outcomes(tel_off)
    assert len(tel_on.outcomes) == len(trace)
    assert tel_on.plan_swaps == tel_off.plan_swaps
    assert [(d.t_s, d.pipeline_id, d.batch_size, d.epoch)
            for d in tel_on.dispatches] == \
           [(d.t_s, d.pipeline_id, d.batch_size, d.epoch)
            for d in tel_off.dispatches]
    assert tel_on.scheduler == tel_off.scheduler


# ---------------------------------------------------------------------------
# Journal contents and scheduler-stats surfacing
# ---------------------------------------------------------------------------


def test_journal_records_swaps_and_exec_events():
    obs = Observer(ObsConfig(level="trace"))
    _, tel, trace = _swap_scenario(obs)
    kinds = {e["kind"] for e in obs.journal.events}
    assert {"req.arrive", "batch.dispatch", "exec.stage",
            "req.complete", "plan.swap"} <= kinds
    swaps = obs.journal.select(kind="plan.swap")
    assert len(swaps) == tel.plan_swaps
    for i, ev in enumerate(swaps):
        assert ev["epoch_from"] == i and ev["epoch_to"] == i + 1
        assert ev["reason"].startswith("script#")
        assert ev["transient_s"] >= 0.0
    # select() by prefix groups event families
    assert len(obs.journal.select(prefix="req")) == \
        sum(1 for e in obs.journal.events if e["kind"].startswith("req."))
    # every completion references a dispatched batch
    batch_ids = {e["batch_id"]
                 for e in obs.journal.select(kind="batch.dispatch")}
    for ev in obs.journal.select(kind="req.complete"):
        assert ev["batch_id"] in batch_ids


def test_batch_wall_joins_dispatch_on_the_virtual_clock(real_pipeline):
    """Each real batch's `batch.wall` event carries the plane's batch id and
    its virtual dispatch time, so it joins `batch.dispatch` and
    `exec.stage` — also after a swap to a fresh dispatcher, whose own job
    ids restart at 0."""
    from repro.core.runtime import build_runtime
    from repro.data.requests import poisson_trace
    from repro.dataplane import PoolDispatcher

    cfg, prof, plan, executors, seq = real_pipeline
    rt = build_runtime(plan, {cfg.name: prof})
    thr = plan.throughput
    trace = poisson_trace(thr * 0.5, 24 / (thr * 0.5), prof.slo_s, cfg.name,
                          seed=9)
    obs = Observer(ObsConfig(level="trace"))
    dp = DataPlane(rt, dispatcher=PoolDispatcher.from_runtime(rt, executors),
                   seq_len=seq, observer=obs)
    mid = trace[len(trace) // 2].arrival_s

    def swap_once(req, t):
        if dp.epoch == 0 and t > mid:
            dp.swap_plan(plan, {cfg.name: prof}, now=t,
                         dispatcher_factory=lambda new_rt: PoolDispatcher
                         .from_runtime(new_rt, executors))

    dp.arrival_hooks.append(swap_once)
    dp.serve(trace)
    assert dp.epoch == 1
    dispatched = {e["batch_id"]: e
                  for e in obs.journal.select(kind="batch.dispatch")}
    walls = obs.journal.select(kind="batch.wall")
    assert sorted(e["batch_id"] for e in walls) == sorted(dispatched)
    staged = {e["batch_id"] for e in obs.journal.select(kind="exec.stage")}
    assert staged == set(dispatched)
    for e in walls:
        d = dispatched[e["batch_id"]]
        assert (e["t_s"], e["epoch"], e["pipeline_id"]) == \
            (d["t_s"], d["epoch"], d["pipeline_id"])
        assert len(e["stage_wall_s"]) == 2 and e["wall_s"] >= 0.0
    assert {e["epoch"] for e in walls} == {0, 1}


def test_scheduler_stats_surfaced_in_snapshot():
    _, tel, _ = _swap_scenario(None)
    snap = tel.snapshot()
    assert snap["schema_version"] >= 2
    sched = snap["scheduler"]
    assert sched["dispatches"] == len(tel.dispatches)
    assert sched["probe_calls"] > 0
    assert sched["probe_cache_hits"] >= 0
    assert sched["bisect_searches"] >= 0
    # continuity across swaps: counters accumulate, never reset
    assert sched["probe_calls"] >= sched["dispatches"]


def test_aggregate_level_skips_per_request_events():
    obs = Observer(ObsConfig(level="aggregate"))
    _, tel, trace = _swap_scenario(obs)
    kinds = {e["kind"] for e in obs.journal.events}
    assert not any(k.startswith(("req.", "exec.", "batch.dispatch"))
                   for k in kinds)
    assert "plan.swap" in kinds  # control-plane events still flow
    # windows still see everything
    ts = obs.timeseries()
    assert sum(ts["arrivals"]) == len(trace)


# ---------------------------------------------------------------------------
# Windowed metrics: per-window sums == end-of-run aggregates
# ---------------------------------------------------------------------------


def test_windowed_sums_match_run_aggregates():
    obs = Observer(ObsConfig(level="trace", window_s=0.25))
    _, tel, trace = _swap_scenario(obs)
    ts = obs.timeseries()
    assert ts["n_windows"] == len(ts["arrivals"]) == len(ts["t_s"])
    assert sum(ts["arrivals"]) == len(trace)
    assert sum(ts["completions"]) == tel.served
    ok_total = sum(1 for o in tel.outcomes if o.ok)
    assert sum(ts["ok"]) == ok_total
    # goodput series integrates back to the run's goodput
    integrated = sum(g * ts["window_s"] for g in ts["goodput_rps"])
    assert integrated == pytest.approx(ok_total, abs=1e-6)
    assert sum(ts["dispatches"]) == len(tel.dispatches)
    drop_total = sum(sum(v) for v in ts["drops"].values())
    assert drop_total == tel.dropped
    by_cause = {c: sum(v) for c, v in ts["drops"].items()}
    expect = {"admission_reject": tel.admission_rejects,
              "overflow_shed": tel.overflow_sheds,
              "expired": tel.expiry_drops,
              "scheduler": tel.sched_drops}
    for cause, n in expect.items():
        assert by_cause.get(cause, 0) == n, cause
    # busy seconds split at window edges still sum to the exact total the
    # telemetry derived its utilization from (util = busy / (chips * horizon))
    for cls, series in ts["busy_s"].items():
        want = tel.utilization[cls] * lifecycle.CLUSTER.counts[cls] * tel.horizon_s
        assert sum(series) == pytest.approx(want, rel=1e-6)


def test_busy_seconds_conserved_across_window_edges():
    wm = WindowedMetrics(window_s=0.5)
    # one long busy interval spanning 4 windows + one inside a single window
    wm.observe_busy("tpu-hi", 0.3, 1.7)
    wm.observe_busy("tpu-lo", 0.6, 0.2)
    series = wm.series(horizon_s=2.0)["busy_s"]
    assert sum(series["tpu-hi"]) == pytest.approx(1.7)
    assert sum(series["tpu-lo"]) == pytest.approx(0.2)
    # the spanning interval contributes to every window it crosses
    assert all(b > 0 for b in series["tpu-hi"])
    # no window holds more busy time than its width x cluster size (1 chip)
    assert all(b <= 0.5 + 1e-12 for b in series["tpu-hi"])


def test_busy_split_terminates_on_nondyadic_window_edge():
    # Regression: with a non-dyadic window_s, float rounding can make
    # int(t/ws) lag one window when t sits exactly on a computed edge
    # ((idx+1)*ws / ws < idx+1); the old loop recomputed idx from t, got
    # edge == t, part == 0, and never advanced.  ws/idx below is a found
    # lagging pair, so this hung before the index-stepped rewrite.
    ws, idx = 2.5367819512578302, 3982
    t = (idx + 1) * ws
    assert int(t / ws) == idx  # the pathology this test pins
    wm = WindowedMetrics(window_s=ws)
    wm.observe_busy("tpu-hi", t - 0.5, 1.0)
    busy = wm.totals()["busy_s"]["tpu-hi"]
    assert busy == pytest.approx(1.0, rel=1e-12)


def test_windowed_ok_uses_outcome_deadline_epsilon():
    # A completion inside RequestOutcome.ok's 1e-9 grace band must count as
    # ok in the windowed metrics too, or windowed ok-sums drift from the
    # telemetry attainment they are documented to reconcile with.
    from types import SimpleNamespace

    from repro.core.types import RequestOutcome

    obs = Observer(ObsConfig(level="aggregate"))
    deadline = 1.0
    t_done = deadline + 0.5e-9  # late by less than the epsilon
    req = SimpleNamespace(req_id=1, model_name="m", arrival_s=0.0,
                          deadline_s=deadline)
    outcome = RequestOutcome(req_id=1, arrival_s=0.0, deadline_s=deadline,
                             completion_s=t_done)
    assert outcome.ok
    obs.on_complete(req, t_done, batch_id=0)
    assert sum(obs.timeseries()["ok"]) == 1


def test_utilization_series_matches_aggregate_utilization():
    obs = Observer(ObsConfig(level="aggregate", window_s=0.5))
    _, tel, _ = _swap_scenario(obs)
    ts = obs.timeseries()
    for cls, util in tel.utilization.items():
        series = ts["utilization"][cls]
        mean = sum(series) / len(series)
        # window grid covers the horizon exactly, so the mean of per-window
        # utilization equals the aggregate (up to horizon rounding)
        assert mean * (ts["n_windows"] * ts["window_s"]) == pytest.approx(
            util * tel.horizon_s, rel=1e-6)


# ---------------------------------------------------------------------------
# Span trees: rooted, nested, and resource-exclusive
# ---------------------------------------------------------------------------


def _overlap(ivs, eps=1e-9):
    ivs = sorted(ivs)
    return any(b0 + eps < a1 for (a0, a1), (b0, b1) in zip(ivs, ivs[1:]))


def test_request_span_trees_are_wellformed():
    obs = Observer(ObsConfig(level="trace"))
    _, tel, trace = _swap_scenario(obs)
    trees = request_trees(obs.journal.events)
    served = {o.req_id for o in tel.outcomes if o.completion_s is not None}
    completions = {o.req_id: o.completion_s for o in tel.outcomes
                   if o.completion_s is not None}
    assert served <= set(trees)
    n_with_children = 0
    for rid in served:
        tree = trees[rid]
        assert tree["status"] == "served"
        assert tree["end_s"] == completions[rid]
        assert tree["start_s"] <= tree["end_s"]
        for child in tree["children"]:
            # children nest inside the root span
            assert tree["start_s"] - 1e-9 <= child["start_s"]
            assert child["end_s"] <= tree["end_s"] + 1e-9
            assert child["start_s"] <= child["end_s"]
        if tree["children"]:
            n_with_children += 1
            names = [c["name"] for c in tree["children"]]
            assert names[0] == "queue"
            assert any(n.startswith("stage") for n in names)
    assert n_with_children > 0, "scenario must produce full span trees"
    for rid, tree in trees.items():
        if tree["status"].startswith("dropped"):
            assert rid not in served


def test_exec_spans_exclusive_per_resource():
    obs = Observer(ObsConfig(level="trace"))
    _swap_scenario(obs)
    per_vdev: dict = {}
    per_nic: dict = {}
    for ev in obs.journal.select(kind="exec.stage"):
        key = (ev["epoch"], ev["accel_class"], ev["chip_id"], ev["vdev_id"])
        per_vdev.setdefault(key, []).append(
            (ev["start_s"], ev["start_s"] + ev["dur_s"]))
    for ev in obs.journal.select(kind="exec.xfer"):
        iv = (ev["start_s"], ev["start_s"] + ev["dur_s"])
        per_nic.setdefault((tuple(ev["ul"]), "ul", ev["epoch"]), []).append(iv)
        per_nic.setdefault((tuple(ev["dl"]), "dl", ev["epoch"]), []).append(iv)
    assert per_vdev, "scenario must execute stages"
    for key, ivs in per_vdev.items():
        assert not _overlap(ivs), f"vdev double-booked: {key}"
    for key, ivs in per_nic.items():
        assert not _overlap(ivs), f"nic double-booked: {key}"


# ---------------------------------------------------------------------------
# Strict JSON + Perfetto export
# ---------------------------------------------------------------------------


def test_snapshot_and_journal_strict_json_roundtrip():
    obs = Observer(ObsConfig(level="trace"))
    _, tel, _ = _swap_scenario(obs)
    snap = json.loads(json.dumps(tel.snapshot(), allow_nan=False))
    assert snap["schema_version"] == 2
    blob = json.loads(obs.journal.to_json())
    assert blob["schema_version"] == JOURNAL_SCHEMA_VERSION
    assert len(blob["events"]) == len(obs.journal)
    for ev in blob["events"]:
        assert isinstance(ev["t_s"], (int, float)) and "kind" in ev


def test_perfetto_export_loads_and_covers_lifecycle(tmp_path):
    obs = Observer(ObsConfig(level="trace"))
    _, tel, _ = _swap_scenario(obs)
    path = tmp_path / "trace.json"
    obs.export_perfetto(path)
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    names = {e.get("name", "") for e in events}
    # >= 3 lifecycle phases: request roots, queue wait, stage execution —
    # plus the control track's plan swaps
    assert any(n.startswith("request") for n in names)
    assert "queue" in names
    assert any(n.startswith("stage") for n in names)
    assert any(n.startswith("plan.swap") for n in names)
    for e in events:
        assert e["ph"] in ("X", "i", "M")
        if e["ph"] == "X":
            assert e["dur"] >= 0 and e["ts"] >= -1e-6
    # thread metadata exists for the request track
    assert any(e["ph"] == "M" and e["name"] == "process_name"
               for e in events)


def test_journal_jsonifies_tuples():
    j = DecisionJournal()
    j.record(0.0, "exec.xfer", ul=("c", 1), dl=("c", 2), nested={"k": (1, 2)})
    ev = json.loads(j.to_json())["events"][0]
    assert ev["ul"] == ["c", 1] and ev["nested"]["k"] == [1, 2]


# ---------------------------------------------------------------------------
# Sampling and config validation
# ---------------------------------------------------------------------------


def test_span_sampling_is_deterministic_and_partial():
    def run(rate):
        obs = Observer(ObsConfig(level="trace", span_sampling=rate))
        _, tel, trace = _swap_scenario(obs)
        rids = {e["req_id"] for e in obs.journal.select(prefix="req")}
        return obs, tel, trace, rids

    obs_a, tel_a, trace, rids_a = run(0.5)
    _, _, _, rids_b = run(0.5)
    assert rids_a == rids_b, "sampling must be deterministic in req_id"
    assert 0 < len(rids_a) < len(trace), "0.5 must actually subsample"
    # windows are sampling-independent: they still count every request
    assert sum(obs_a.timeseries()["arrivals"]) == len(trace)
    _, _, _, rids_none = run(0.0)
    assert rids_none == set()
    _, _, _, rids_all = run(1.0)
    assert len(rids_all) == len(trace)


def test_obsconfig_validation():
    with pytest.raises(ValueError, match="obs.level"):
        ObsConfig(level="verbose").validate()
    with pytest.raises(ValueError, match="obs.window_s"):
        ObsConfig(window_s=-1.0).validate()
    with pytest.raises(ValueError, match="obs.span_sampling"):
        ObsConfig(span_sampling=2.0).validate()
    assert ObsConfig(level="trace").validate().level == "trace"


# ---------------------------------------------------------------------------
# api wiring: ServeConfig.obs -> Session -> Report
# ---------------------------------------------------------------------------


def _serve_cfg(level):
    from repro.api import ClusterSpec, ModelSpec, ObsConfig as OC, ServeConfig

    return ServeConfig(
        cluster=ClusterSpec(counts={"tpu-hi": 2, "tpu-lo": 4}),
        models=(ModelSpec(arch="stablelm-3b", seq_len=256, n_blocks=5),),
        obs=OC(level=level, window_s=0.5),
    )


def test_session_threads_observer_through_report(tmp_path):
    from repro.api import Session
    from repro.data.requests import poisson_trace

    with Session.from_config(_serve_cfg("trace")) as s:
        s.deploy(mode="sim")
        plan = s.cluster_plan
        prof = next(iter(s.store.profiles.values()))
        trace = poisson_trace(plan.throughput * 0.8, 1.5, prof.slo_s,
                              prof.model_name, seed=4)
        report = s.run(trace)
        ts = report.timeseries()
        assert sum(ts["arrivals"]) == len(trace)
        assert len(ts["t_s"]) == ts["n_windows"]
        assert "utilization" in ts  # cluster counts reached the series
        out = report.as_dict()
        assert out["timeseries"]["n_windows"] == ts["n_windows"]
        json.dumps(out, allow_nan=False)
        path = tmp_path / "api_trace.json"
        report.export_trace(path)
        assert json.loads(path.read_text())["traceEvents"]


def test_session_obs_off_reports_empty_timeseries():
    from repro.api import Session
    from repro.data.requests import poisson_trace

    with Session.from_config(_serve_cfg("off")) as s:
        s.deploy(mode="sim")
        plan = s.cluster_plan
        prof = next(iter(s.store.profiles.values()))
        trace = poisson_trace(plan.throughput * 0.8, 0.5, prof.slo_s,
                              prof.model_name, seed=4)
        report = s.run(trace)
        assert report.obs is None
        assert report.timeseries() == {}
        assert "timeseries" not in report.as_dict()
        with pytest.raises(LifecycleError):
            report.export_trace("/tmp/nope.json")


def test_serveconfig_obs_roundtrips():
    from repro.api import ServeConfig

    cfg = _serve_cfg("aggregate")
    d = cfg.to_dict()
    assert d["obs"]["level"] == "aggregate"
    again = ServeConfig.from_dict(json.loads(json.dumps(d)))
    assert again.obs == cfg.obs
    # pre-obs dicts (no "obs" key) still load, defaulting to off
    legacy = {k: v for k, v in d.items() if k != "obs"}
    assert ServeConfig.from_dict(legacy).obs.level == "off"
