"""repro.api: config validation, Session lifecycle, hand-wired parity,
warm-compiled swaps, and the public-surface snapshot."""

import dataclasses
import inspect
import json

import pytest

from repro.api import (
    AdmissionPolicy,
    ClusterSpec,
    ConfigError,
    LifecycleError,
    ModelSpec,
    Objective,
    ObsConfig,
    PolicyConfig,
    ReplanConfig,
    ServeConfig,
    Session,
)
from repro.core.types import Request, replace

CLUSTER = ClusterSpec(counts={"tpu-hi": 2, "tpu-lo": 4})


def _config(**over):
    base = dict(
        cluster=CLUSTER,
        models=(ModelSpec(arch="stablelm-3b", seq_len=256, n_blocks=5),),
    )
    base.update(over)
    return ServeConfig(**base)


# ---------------------------------------------------------------------------
# Config validation + serialization
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mutation, match", [
    (dict(models=()), "empty"),
    (dict(models=(ModelSpec(arch="no-such-arch"),)), "unknown arch"),
    (dict(models=(ModelSpec(arch="stablelm-3b"),
                  ModelSpec(arch="stablelm-3b"))), "duplicate"),
    (dict(models=(ModelSpec(arch="stablelm-3b", slo_scale=0.0),)), "slo_scale"),
    (dict(models=(ModelSpec(arch="stablelm-3b", n_blocks=1),)), "n_blocks"),
    (dict(backend="magic"), "unknown planner backend"),
    (dict(feedback="psychic"), "feedback"),
    (dict(source="vibes"), "source"),
    (dict(cluster=ClusterSpec(counts={"tpu-quantum": 4})), "accelerator class"),
    (dict(cluster=ClusterSpec(counts={"tpu-hi": 0})), "count"),
    (dict(gc_interval_s=0.0), "gc_interval_s"),
    (dict(max_inflight=0), "max_inflight"),
    (dict(vfracs=()), "vfracs"),
    (dict(obs=ObsConfig(level="verbose")), "obs.level"),
    (dict(obs=ObsConfig(window_s=0.0)), "obs.window_s"),
    (dict(obs=ObsConfig(span_sampling=1.5)), "obs.span_sampling"),
])
def test_config_validation_rejects(mutation, match):
    with pytest.raises(ConfigError, match=match):
        _config(**mutation).validate()


def test_config_dict_round_trip_is_lossless_and_json_safe():
    cfg = _config(
        objective=Objective(slo_margin=0.3, weights={"stablelm-3b": 2.0}),
        admission=AdmissionPolicy(max_depth=16),
        replan=ReplanConfig(window_s=1.0, source="measured"),
        replan_policy=PolicyConfig(cooldown_s=2.0),
        vfracs=(1, 2),
        batch_sizes=(1, 4),
    )
    blob = json.dumps(cfg.to_dict())  # must be strict-JSON serializable
    back = ServeConfig.from_dict(json.loads(blob))
    assert back == cfg


def test_config_from_dict_rejects_malformed():
    d = _config().to_dict()
    d["objective"]["no_such_knob"] = 1
    with pytest.raises(ConfigError, match="malformed"):
        ServeConfig.from_dict(d)
    # missing required sections are malformed too, not bare KeyErrors
    with pytest.raises(ConfigError, match="malformed"):
        ServeConfig.from_dict({})
    incomplete = _config().to_dict()
    del incomplete["replan"]
    with pytest.raises(ConfigError, match="malformed"):
        ServeConfig.from_dict(incomplete)


# ---------------------------------------------------------------------------
# Lifecycle misuse
# ---------------------------------------------------------------------------


def test_lifecycle_misuse_raises():
    session = Session.from_config(_config())
    req = Request(arrival_s=0.0, req_id=0, model_name="stablelm-3b",
                  deadline_s=1.0)
    with pytest.raises(LifecycleError, match="deploy"):
        session.submit(req)
    with pytest.raises(LifecycleError, match="deploy"):
        session.run([req])
    with pytest.raises(LifecycleError, match="deploy"):
        session.swap()
    with pytest.raises(LifecycleError, match="deploy"):
        session.enable_replanning()
    with pytest.raises(LifecycleError, match="profile"):
        session.store  # noqa: B018 — the property access IS the test
    session.deploy(mode="sim")  # auto-chains profile() + plan()
    with pytest.raises(LifecycleError, match="twice"):
        session.deploy(mode="sim")
    with pytest.raises(LifecycleError, match="swap"):
        session.plan()  # plan changes on a live session go through swap()
    session.shutdown()
    session.shutdown()  # idempotent
    with pytest.raises(LifecycleError, match="closed"):
        session.run([req])
    with pytest.raises(LifecycleError, match="closed"):
        session.profile()


def test_measured_feedback_requires_real_deploy():
    session = Session.from_config(_config(feedback="measured"))
    with pytest.raises(LifecycleError, match="real"):
        session.deploy(mode="sim")


def test_duplicate_pending_req_id_rejected():
    session = Session.from_config(_config()).deploy(mode="sim")
    req = Request(arrival_s=0.0, req_id=7, model_name="stablelm-3b",
                  deadline_s=1.0)
    session.submit(req)
    with pytest.raises(ConfigError, match="duplicate"):
        session.submit(req)


# ---------------------------------------------------------------------------
# Parity: Session.run == the hand-wired DataPlane path, float for float
# ---------------------------------------------------------------------------


def test_run_matches_handwired_dataplane_float_identically():
    """The facade must not perturb serving by a single ULP: same profile,
    same plan, same DataPlane defaults -> identical outcomes AND identical
    aggregate telemetry vs the pre-facade hand-wired chain."""
    from repro.core.runtime import build_runtime
    from repro.data.requests import poisson_trace
    from repro.dataplane.plane import serve_trace

    cfg = _config()
    session = Session.from_config(cfg)
    store = session.profile()
    plan = session.plan()
    session.deploy(mode="sim")
    prof = store.profiles["stablelm-3b"]
    trace = poisson_trace(plan.throughput * 0.9, 1.5, prof.slo_s,
                          "stablelm-3b", seed=3)
    report = session.run(trace)

    tel = serve_trace(build_runtime(plan, dict(store.profiles)), trace)
    got = {o.req_id: (o.completion_s, o.pipeline_id)
           for o in report.telemetry.outcomes}
    want = {o.req_id: (o.completion_s, o.pipeline_id) for o in tel.outcomes}
    assert got == want  # exact, not approx
    assert report.attainment == tel.attainment
    assert report.goodput_rps == tel.goodput_rps
    assert report.utilization == tel.utilization
    assert report.telemetry.probes_per_dispatch == tel.probes_per_dispatch


def test_handles_resolve_with_run_and_drain():
    from repro.data.requests import poisson_trace

    cfg = _config()
    session = Session.from_config(cfg)
    plan = session.plan()
    session.deploy(mode="sim")
    prof = session.store.profiles["stablelm-3b"]
    trace = poisson_trace(plan.throughput * 0.5, 0.5, prof.slo_s,
                          "stablelm-3b", seed=4)
    handles = [session.submit(r) for r in trace]
    assert not any(h.done for h in handles)
    # result() on a pending handle drains the whole batch
    out = handles[0].result()
    assert out is handles[0].outcome
    assert all(h.done for h in handles)
    served = [h for h in handles if h.latency_s is not None]
    assert served and all(h.latency_s > 0 for h in served)
    assert all(h.deadline_s == h.request.deadline_s for h in handles)
    # drained report covers exactly the submitted requests
    assert len(session.report().telemetry.outcomes) == len(trace)


def test_drain_rejects_arrivals_behind_served_horizon():
    """One session, one monotonic virtual clock: replaying a second trace
    that restarts at t=0 would queue behind the first trace's residual
    reservations and silently mass-miss SLOs — drain() must refuse."""
    from repro.data.requests import poisson_trace

    session = Session.from_config(_config())
    plan = session.plan()
    session.deploy(mode="sim")
    prof = session.store.profiles["stablelm-3b"]
    trace = poisson_trace(plan.throughput * 0.5, 0.5, prof.slo_s,
                          "stablelm-3b", seed=6)
    session.run(trace)
    horizon = session.telemetry.horizon_s
    session.submit(Request(arrival_s=0.0, req_id=10**15,
                           model_name="stablelm-3b", deadline_s=prof.slo_s))
    with pytest.raises(LifecycleError, match="horizon"):
        session.drain()
    # arrivals at/after the served horizon continue the same clock fine
    session._pending.clear()
    session._open.clear()
    cont = [replace(r, arrival_s=r.arrival_s + horizon,
                    deadline_s=r.deadline_s + horizon,
                    req_id=r.req_id + 10**9) for r in trace]
    rep = session.run(cont)
    assert len(rep.telemetry.outcomes) == 2 * len(trace)


def test_serve_trace_package_alias_warns():
    import repro.dataplane as dp
    from repro.dataplane.plane import serve_trace as direct

    with pytest.warns(DeprecationWarning, match="Session"):
        assert dp.serve_trace is direct


# ---------------------------------------------------------------------------
# Real execution: warm-compiled swap to a different partitioning
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def real_session_setup():
    """A tiny real deployment + two hand-pinned plans with disjoint block
    ranges (the re-partitioning swap scenario)."""
    from repro.core import costmodel as cm
    from repro.core.plan import ClusterPlan, PipelinePlan, StagePlan

    cluster = ClusterSpec(counts={"tpu-hi": 1, "tpu-lo": 2})
    cfg = ServeConfig(
        cluster=cluster,
        models=(ModelSpec(arch="stablelm-3b",
                          reduced=dict(n_layers=4, d_model=128, d_ff=256,
                                       n_heads=4, kv_heads=4, vocab=512),
                          n_blocks=4, seq_len=16, slo_scale=8.0),),
        serve_seq_len=16,
    )
    session = Session.from_config(cfg)
    store = session.profile()
    prof = store.profiles["stablelm-3b"]
    tbl = store.analytic_table("stablelm-3b")
    n = prof.n_blocks

    def staged(cut, bs=4):
        return ClusterPlan(cluster=cluster, pipelines=[PipelinePlan(
            model_name="stablelm-3b", batch_size=bs,
            stages=(
                StagePlan(0, cut, "tpu-lo", 1, 2,
                          tbl.partition(0, cut, "tpu-lo", 1, bs)),
                StagePlan(cut, n, "tpu-hi", 1, 1,
                          tbl.partition(cut, n, "tpu-hi", 1, bs)),
            ),
            xfer_latency_s=(cm.transfer_latency(prof, cluster, "tpu-lo",
                                                "tpu-hi", cut, bs),),
        )])

    plan_a, plan_b = staged(n // 2), staged(n // 2 + 1)
    session.use_plan(plan_a)
    session.deploy(mode="real")
    yield session, plan_a, plan_b, prof
    session.shutdown()


def test_swap_to_new_partitioning_warm_compiles_before_install(
        real_session_setup):
    from repro.data.requests import poisson_trace

    session, plan_a, plan_b, prof = real_session_setup
    a_ranges = {(s.block_start, s.block_end)
                for pp in plan_a.pipelines for s in pp.stages}
    b_ranges = {(s.block_start, s.block_end)
                for pp in plan_b.pipelines for s in pp.stages}
    assert not (a_ranges & b_ranges), "fixture must re-partition"
    missing = session.missing_ranges(plan_b)
    assert len(missing) == len(b_ranges)

    rate = plan_a.throughput * 0.5
    trace = poisson_trace(rate, 24 / rate, prof.slo_s, "stablelm-3b", seed=5)
    mid = trace[len(trace) // 2].arrival_s
    state = {}

    def hook(req, t):
        if "prep" not in state and t > mid:
            state["prep"] = session.prepare_swap(plan_b)
        elif "rec" not in state and "prep" in state:
            # install on the next arrival: swap() waits out any residual
            # background compile, and the live swap reuses the result
            state["rec"] = session.swap(plan_b, now=t, reason="repartition")

    session.on_arrival(hook)
    report = session.run(trace)
    rec = state["rec"]
    assert rec.prepared, "the prepared background compile was not consumed"
    assert sorted(rec.new_ranges) == missing
    assert rec.reused_executors == 0  # disjoint ranges: nothing shared
    # the executors exist in the cache now, and serving continued: every
    # request of the trace has an outcome and the swap is on record
    assert session.missing_ranges(plan_b) == []
    assert report.telemetry.plan_swaps == 1
    assert len(report.telemetry.outcomes) == len(trace)
    assert report.swaps == (rec,)


def test_enable_replanning_wires_recalibration_and_warm_factory(
        real_session_setup):
    """Loop-driven swaps bypass Session.swap, so the session must hand the
    ReplanLoop its recalibration closure and a dispatcher factory that
    warm-compiles — otherwise a drift-installed runtime would serve real
    execution on analytic latencies and pay XLA compiles mid-trace."""
    from repro.core.runtime import build_runtime

    fixture_session, plan_a, plan_b, prof = real_session_setup
    cfg = dataclasses.replace(fixture_session.config, feedback="measured")
    with Session.from_config(cfg, store=fixture_session.store) as session:
        session.use_plan(plan_a)
        session.deploy(mode="real")
        loop = session.enable_replanning()
        assert loop.dispatcher_factory == session._dispatcher_factory
        assert loop.runtime_setup is not None  # calibrated real deployment
        # what the loop runs on a drift-installed runtime: recalibration
        # (wall-clock latencies, not analytic µs) ...
        new_rt = build_runtime(plan_b, dict(session.store.profiles))
        loop.runtime_setup(new_rt)
        assert all(s.latency(1) > 1e-4
                   for p in new_rt.pipelines for s in p.stages)
        # ... and a factory that leaves no block range uncompiled
        disp = loop.dispatcher_factory(new_rt)
        assert session.missing_ranges(plan_b) == []
        assert disp.executors


def test_real_deploy_leaves_no_compilation_inside_run(real_session_setup):
    """deploy(mode="real") warms every batch bucket even with planned
    feedback (no calibration pass), so serving compiles nothing."""
    from repro.api.compilation import count_compiles
    from repro.data.requests import poisson_trace

    fixture_session, plan_a, _, prof = real_session_setup
    assert fixture_session.config.feedback == "planned"
    with Session.from_config(fixture_session.config,
                             store=fixture_session.store) as session:
        session.use_plan(plan_a)
        session.deploy(mode="real")
        rate = plan_a.throughput * 0.5
        trace = poisson_trace(rate, 16 / rate, prof.slo_s, "stablelm-3b",
                              seed=9)
        with count_compiles() as compiles:
            report = session.run(trace)
        assert report.telemetry.dispatches  # batches really executed
        assert compiles.n == 0


def test_swap_back_reuses_cached_executors(real_session_setup):
    session, plan_a, plan_b, prof = real_session_setup
    # both partitionings are compiled by now: swapping back must compile
    # nothing and reuse every stage executor
    rec = session.swap(plan_a, now=session._vnow + 1.0, reason="back")
    assert rec.new_ranges == ()
    assert rec.reused_executors == sum(
        len(pp.stages) for pp in plan_a.pipelines)
    assert not rec.prepared


# ---------------------------------------------------------------------------
# Public-surface snapshot: future PRs must not drift this silently
# ---------------------------------------------------------------------------

EXPECTED_ALL = [
    "AdmissionPolicy",
    "ClusterSpec",
    "ConfigError",
    "FaultConfig",
    "FaultEvent",
    "FaultSchedule",
    "LifecycleError",
    "ModelSpec",
    "Objective",
    "ObsConfig",
    "PolicyConfig",
    "ReplanConfig",
    "Report",
    "RequestHandle",
    "ServeConfig",
    "Session",
    "SourceConfig",
    "SwapRecord",
    "build_profile_store",
    "profile_model",
]

EXPECTED_SIGNATURES = {
    "Session.from_config": "(config: 'ServeConfig', *, store: 'ProfileStore | None' = None) -> 'Session'",
    "Session.profile": "(self) -> 'ProfileStore'",
    "Session.solve": "(self, backend: 'str | None' = None, objective: 'Objective | None' = None) -> 'ClusterPlan'",
    "Session.plan": "(self, objective: 'Objective | None' = None) -> 'ClusterPlan'",
    "Session.use_plan": "(self, plan: 'ClusterPlan', slo_margin: 'float' = 0.0) -> 'ClusterPlan'",
    "Session.deploy": "(self, mode: 'str' = 'sim') -> 'Session'",
    "Session.submit": "(self, req: 'Request') -> 'RequestHandle'",
    "Session.run": "(self, trace) -> 'Report'",
    "Session.serve": "(self, source=None, horizon_s: 'float | None' = None) -> 'Report'",
    "Session.drain": "(self) -> 'Report'",
    "Session.report": "(self) -> 'Report'",
    "Session.swap": "(self, plan: 'ClusterPlan | None' = None, *, now: 'float | None' = None, reason: 'str | None' = None, objective: 'Objective | None' = None, slo_margin: 'float | None' = None) -> 'SwapRecord'",
    "Session.resize": "(self, cluster_delta: 'dict[str, int]', *, now: 'float | None' = None, reason: 'str' = 'resize') -> 'SwapRecord'",
    "Session.prepare_swap": "(self, plan: 'ClusterPlan') -> '_PreparedSwap'",
    "Session.enable_replanning": "(self, baseline_rates: 'dict[str, float] | None' = None) -> 'ReplanLoop'",
    "Session.shutdown": "(self) -> 'None'",
    "profile_model": "(spec: 'ModelSpec', cluster: 'ClusterSpec') -> 'ModelProfile'",
    "build_profile_store": "(cluster: 'ClusterSpec', specs, vfracs=(1, 2, 3, 4), batch_sizes=(1, 2, 4, 8, 16)) -> 'ProfileStore'",
}


def test_public_api_snapshot():
    import repro.api as api

    assert sorted(api.__all__) == EXPECTED_ALL
    for name in api.__all__:
        assert hasattr(api, name), f"__all__ names missing symbol {name}"
    for dotted, want in EXPECTED_SIGNATURES.items():
        obj = api
        for part in dotted.split("."):
            obj = getattr(obj, part)
        assert str(inspect.signature(obj)) == want, dotted


def test_config_field_surface_snapshot():
    """Renaming/removing declarative knobs is a breaking change; adding is
    fine but must update this snapshot deliberately."""
    assert [f.name for f in dataclasses.fields(ModelSpec)] == [
        "arch", "slo_scale", "slo_s", "seq_len", "n_blocks", "reduced",
        "weight"]
    assert [f.name for f in dataclasses.fields(ServeConfig)] == [
        "cluster", "models", "backend", "objective", "source", "feedback",
        "admission", "replan", "replan_policy", "gc_interval_s", "obs",
        "stream", "faults", "vfracs", "batch_sizes", "serve_seq_len",
        "max_inflight", "quantize_boundary", "calibrate", "seed", "token_fn"]
