"""Deterministic fault injection (repro.faults) and node-loss recovery:
schedule/config round-trips, bounded retry-with-hedging, abrupt host loss
with certified re-admission, and the keystone outcome-uniqueness property —
under random fault schedules interleaved with plan swaps, every admitted
request resolves to exactly one of {completed, dropped(cause)}."""

from collections import Counter

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st  # seeded sampler without hypothesis

from repro.controlplane import (
    Objective,
    Planner,
    PolicyConfig,
    ProfileStore,
    ReplanConfig,
    ReplanLoop,
    ReplanPolicy,
)
from repro.core import blocks, costmodel as cm
from repro.core.runtime import build_runtime
from repro.core.types import ClusterSpec, replace
from repro.data.requests import multi_model_trace
from repro.dataplane import DataPlane
from repro.dataplane.dispatcher import PoolDispatcher
from repro.faults import (
    FAULT_KINDS,
    FailureInjector,
    FaultConfig,
    FaultEvent,
    FaultInjector,
    FaultSchedule,
)
from repro.obs import ObsConfig, Observer
from repro.serving.engine import StageExecutor

CLUSTER = ClusterSpec(counts={"tpu-hi": 2, "tpu-lo": 4})


def _profile(n_layers=8, n_blocks=4, slo=0.03, seed=0, seq=256, name="m"):
    rng = np.random.default_rng(seed)
    layers = [cm.embed_cost(seq, 1024, 32000)]
    for i in range(n_layers):
        layers.append(cm.layer_sequence_cost(f"l{i}", [
            cm.attention_cost(seq, 1024, 16, 4),
            cm.mlp_cost(seq, 1024, int(rng.uniform(2048, 8192))),
        ]))
    layers.append(cm.head_cost(seq, 1024, 32000))
    return blocks.build_profile(name, layers, slo, n_blocks=n_blocks)


def _store(profs, cluster=CLUSTER):
    store = ProfileStore(cluster, vfracs=(1, 2), batch_sizes=(1, 2))
    for p in profs.values():
        store.add(p, cm.build_latency_table(p, cluster, vfracs=(1, 2),
                                            batch_sizes=(1, 2)))
    return store


def _two_plan_setup():
    """Two models, two alternating plans (m0-heavy / m1-heavy), SLOs pinned
    so plans must partition — same shape as the epoch-lifecycle suite."""
    profs = {f"m{i}": _profile(seed=i, name=f"m{i}", n_layers=6, n_blocks=3)
             for i in range(2)}
    hi, lo = CLUSTER.accel("tpu-hi"), CLUSTER.accel("tpu-lo")
    for name, p in profs.items():
        whole_hi = sum(cm.block_latency(b, hi, 1, 1) for b in p.blocks)
        whole_lo = sum(cm.block_latency(b, lo, 1, 1) for b in p.blocks)
        profs[name] = replace(p, slo_s=(whole_hi * 1.4 + whole_lo * 0.6) / 2 / 0.6)
    planner = Planner(objective=Objective(slo_margin=0.4, max_partitions=2))
    store = _store(profs)
    plan_a = planner.plan(profs, store.tables(), CLUSTER,
                          objective=planner.objective.with_weights(
                              {"m0": 0.9, "m1": 0.1}))
    plan_b = planner.plan(profs, store.tables(), CLUSTER,
                          objective=planner.objective.with_weights(
                              {"m0": 0.1, "m1": 0.9}))
    return profs, store, planner, plan_a, plan_b


def _trace(profs, plan, horizon_s, load=0.7, seed=0):
    rates = {m: max(plan.throughput_of(m), 1.0) * load for m in profs}
    slos = {m: p.slo_s for m, p in profs.items()}
    return multi_model_trace(rates, horizon_s, slos, seed=seed)


def _outcome_uniqueness(journal):
    """Every arrived req_id resolves to exactly one complete-or-drop."""
    c = Counter(e["req_id"] for e in journal.select("req.complete"))
    c += Counter(e["req_id"] for e in journal.select("req.drop"))
    arrived = {e["req_id"] for e in journal.select("req.arrive")}
    dups = {k: n for k, n in c.items() if n > 1}
    missing = arrived - set(c)
    return dups, missing


# ---------------------------------------------------------------------------
# FaultEvent / FaultSchedule / FaultConfig data model
# ---------------------------------------------------------------------------


def test_fault_event_validation():
    FaultEvent(1.0, "node_loss", accel_class="tpu-lo").validate()
    FaultEvent(0.0, "exec_fault", count=3).validate()
    with pytest.raises(ValueError):
        FaultEvent(1.0, "meteor_strike").validate()
    with pytest.raises(ValueError):
        FaultEvent(-1.0, "exec_fault").validate()
    with pytest.raises(ValueError):
        FaultEvent(1.0, "node_loss").validate()  # host kinds need a class
    with pytest.raises(ValueError):
        FaultEvent(1.0, "chip_slowdown", accel_class="tpu-hi",
                   factor=0.5).validate()  # a speed-UP is not a fault
    with pytest.raises(ValueError):
        FaultEvent(1.0, "exec_fault", count=0).validate()


def test_fault_event_dict_round_trip_drops_none_fields():
    ev = FaultEvent(2.5, "chip_slowdown", accel_class="tpu-hi", chip_id=3,
                    factor=2.0)
    d = ev.as_dict()
    assert "host_id" not in d  # None fields are omitted
    assert FaultEvent.from_dict(d) == ev


def test_schedule_orders_consumes_and_refuses_rewrites():
    sched = FaultSchedule([FaultEvent(3.0, "exec_fault"),
                           FaultEvent(1.0, "exec_fault")])
    assert [e.t_s for e in sched.events] == [1.0, 3.0]
    assert [e.t_s for e in sched.due(2.0)] == [1.0]
    assert sched.remaining == 1
    # inserting behind the consumption cursor would rewrite history
    with pytest.raises(ValueError):
        sched.add(FaultEvent(0.5, "exec_fault"))
    sched.add(FaultEvent(2.5, "exec_fault"))
    assert [e.t_s for e in sched.due(10.0)] == [2.5, 3.0]
    sched.reset()
    assert sched.remaining == 3


def test_schedule_from_seed_is_replayable_and_tail_stable():
    counts = {"tpu-hi": 2, "tpu-lo": 8}
    a = FaultSchedule.from_seed(7, 8.0, counts, n_events=6,
                                kinds=FAULT_KINDS)
    b = FaultSchedule.from_seed(7, 8.0, counts, n_events=6,
                                kinds=FAULT_KINDS)
    assert a.events == b.events
    for ev in a.events:
        ev.validate()
        if ev.kind in ("node_join", "node_drain", "node_loss"):
            # host events only target multi-host classes, at the tail host
            assert ev.accel_class == "tpu-lo"
            assert ev.host_id == counts["tpu-lo"] // 4 - 1


def test_fault_config_round_trip():
    cfg = FaultConfig(seed=9, exec_fault_rate=0.05, max_retries=1, schedule=(
        FaultEvent(1.0, "node_loss", accel_class="tpu-lo"),
        FaultEvent(2.0, "exec_fault", count=2)))
    assert FaultConfig.from_dict(cfg.to_dict()) == cfg
    with pytest.raises(ValueError):
        FaultConfig(exec_fault_rate=1.5).validate()
    with pytest.raises(ValueError):
        FaultConfig(max_retries=-1).validate()


def test_training_failure_injector_reexport():
    from repro.training.elastic import FailureInjector as TrainingInjector

    assert TrainingInjector is FailureInjector
    inj = FailureInjector({2})
    inj.check(1)
    with pytest.raises(RuntimeError):
        inj.check(2)
    inj.check(2)  # one-shot: the step fails once, then recovers
    assert inj.failures == [2]


# ---------------------------------------------------------------------------
# Transient exec faults: bounded retry-with-hedging
# ---------------------------------------------------------------------------


def test_exec_fault_retries_within_budget_then_drops():
    profs, _, _, plan_a, _ = _two_plan_setup()
    trace = _trace(profs, plan_a, 2.0, load=0.5, seed=4)

    dp = DataPlane(build_runtime(plan_a, profs),
                   observer=Observer(ObsConfig(level="trace")))
    FaultInjector(FaultSchedule([FaultEvent(0.5, "exec_fault", count=2)]),
                  max_retries=2).attach(dp)
    tel = dp.serve(trace)

    assert tel.faults_injected == 1
    assert tel.exec_failures == 2  # both forced faults fired
    assert tel.retries >= 1  # the victims re-entered the EDF queue
    dups, missing = _outcome_uniqueness(dp.obs.journal)
    assert not dups and not missing
    # a retried request served by the SECOND attempt proves re-admission
    retried = dp.obs.journal.select("retry.attempt")
    assert retried and all(e["readmitted"] >= 1 for e in retried)


def test_exec_fault_budget_zero_reproduces_legacy_drop():
    profs, _, _, plan_a, _ = _two_plan_setup()
    trace = _trace(profs, plan_a, 2.0, load=0.5, seed=4)

    dp = DataPlane(build_runtime(plan_a, profs),
                   observer=Observer(ObsConfig(level="trace")))
    FaultInjector(FaultSchedule([FaultEvent(0.5, "exec_fault", count=1)]),
                  max_retries=0).attach(dp)
    tel = dp.serve(trace)

    assert tel.exec_failures == 1 and tel.retries == 0
    assert tel.retry_exhausted >= 1
    drops = dp.obs.journal.select("req.drop")
    assert any(e["cause"] == "exec_failure" for e in drops)
    dups, missing = _outcome_uniqueness(dp.obs.journal)
    assert not dups and not missing


def _failing_dispatcher(plan):
    """A real PoolDispatcher whose stage programs fail the way a compile
    error or a device out-of-memory does: the executor call raises."""
    def broken(params, carry):
        raise RuntimeError("stage program failed to compile")

    ex = StageExecutor(stage_fn=broken, params={})
    return PoolDispatcher({pid: [ex] * len(pp.stages)
                           for pid, pp in enumerate(plan.pipelines)})


def test_executor_error_propagates_while_injected_faults_retry():
    profs, _, _, plan_a, _ = _two_plan_setup()
    trace = _trace(profs, plan_a, 2.0, load=0.5, seed=4)

    # an executor error is not a transient fault: serve raises it instead
    # of turning it into retries and drops
    dp = DataPlane(build_runtime(plan_a, profs),
                   dispatcher=_failing_dispatcher(plan_a))
    with pytest.raises(RuntimeError, match="failed to compile"):
        dp.serve(trace)
    assert dp.tel.exec_failures == 0 and dp.tel.retries == 0

    # injected faults fire before the dispatcher is reached and keep their
    # bounded retry; the first real submit then surfaces the error
    dp = DataPlane(build_runtime(plan_a, profs),
                   dispatcher=_failing_dispatcher(plan_a))
    inj = FaultInjector(FaultSchedule([FaultEvent(0.0, "exec_fault", count=2)]),
                        max_retries=2).attach(dp)
    inj.poll(0.0)  # arm both faults before the first dispatch
    with pytest.raises(RuntimeError, match="failed to compile"):
        dp.serve(trace)
    assert dp.tel.exec_failures == 2 and dp.tel.retries >= 1


def test_chip_slowdown_stretches_stage_durations():
    profs, _, _, plan_a, _ = _two_plan_setup()
    trace = _trace(profs, plan_a, 2.0, load=0.4, seed=2)

    def durs(factor):
        dp = DataPlane(build_runtime(plan_a, profs),
                       observer=Observer(ObsConfig(level="trace")))
        if factor is not None:
            FaultInjector(FaultSchedule([FaultEvent(
                0.0, "chip_slowdown", accel_class="tpu-hi",
                factor=factor)])).attach(dp)
        dp.serve(trace)
        # batch ids diverge between runs once scheduling differs, so key by
        # (pipeline, stage) over batch-size-1 executions — whose planned
        # duration is deterministic — and compare the per-key minimum
        out = {}
        for e in dp.obs.journal.select("exec.stage"):
            if e["accel_class"] == "tpu-hi" and e["batch_size"] == 1:
                k = (e["pipeline_id"], e["stage_idx"])
                out[k] = min(out.get(k, float("inf")), e["dur_s"])
        return out

    base, slow = durs(None), durs(3.0)
    shared = set(base) & set(slow)
    assert shared
    for k in shared:
        assert slow[k] == pytest.approx(3.0 * base[k], rel=1e-9)


# ---------------------------------------------------------------------------
# Abrupt node loss: cancel, release, re-admit-or-drop
# ---------------------------------------------------------------------------


def test_node_loss_readmits_or_drops_every_inflight_request():
    profs, _, _, plan_a, _ = _two_plan_setup()
    trace = _trace(profs, plan_a, 4.0, load=0.8, seed=6)

    dp = DataPlane(build_runtime(plan_a, profs),
                   observer=Observer(ObsConfig(level="trace")))
    state = {}

    def hook(req, now):
        if "res" not in state and now >= 1.5:
            state["res"] = dp.fail_host("tpu-lo", now=now)

    dp.arrival_hooks.append(hook)
    tel = dp.serve(trace)

    res = state["res"]
    assert res["inflight_failed"] >= 0
    assert tel.node_losses == 1
    drains = dp.obs.journal.select("pool.drain")
    assert len(drains) == 1 and drains[0]["accel_class"] == "tpu-lo"
    assert drains[0]["readmitted"] == res["readmitted"]
    assert drains[0]["dropped"] == res["dropped"]
    # node_loss drops carry the explicit cause
    assert tel.node_loss_drops == res["dropped"]
    dups, missing = _outcome_uniqueness(dp.obs.journal)
    assert not dups and not missing


def test_node_loss_with_replan_loop_installs_mandatory_plan():
    profs, store, planner, plan_a, _ = _two_plan_setup()
    trace = _trace(profs, plan_a, 5.0, load=0.6, seed=8)

    dp = DataPlane(build_runtime(plan_a, profs),
                   observer=Observer(ObsConfig(level="trace")))
    loop = ReplanLoop(
        planner=planner, store=store, cluster=CLUSTER, dataplane=dp,
        config=ReplanConfig(window_s=0.4, check_interval_s=0.2,
                            min_requests=8),
        policy=ReplanPolicy(PolicyConfig(cooldown_s=2.0)),
    ).attach()
    loop.set_baseline({m: plan_a.throughput_of(m) for m in profs})
    state = {}

    def hook(req, now):
        if "t" not in state and now >= 2.0:
            state["t"] = now
            dp.fail_host("tpu-lo", now=now)

    dp.arrival_hooks.append(hook)
    tel = dp.serve(trace)

    # the loss hook shrank the planning inventory and swapped immediately
    assert loop.cluster.counts == {"tpu-hi": 2}
    assert tel.plan_swaps >= 1
    t_loss = state["t"]
    swaps = dp.obs.journal.select("plan.swap")
    loss_swaps = [s for s in swaps if s["reason"].startswith("node_loss@")]
    assert loss_swaps and loss_swaps[0]["t_s"] == pytest.approx(t_loss)
    mand = [d for d in tel.replan_decisions
            if d["reason"].startswith("mandatory:")]
    assert mand and mand[0]["accepted"]
    dups, missing = _outcome_uniqueness(dp.obs.journal)
    assert not dups and not missing


# ---------------------------------------------------------------------------
# Keystone property: outcome uniqueness under random fault schedules
# interleaved with plan swaps
# ---------------------------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000),
       fault_seed=st.integers(0, 10_000),
       swap_offsets=st.lists(st.floats(0.4, 3.2), min_size=0, max_size=2),
       with_loss=st.booleans())
def test_property_every_request_resolves_exactly_once(seed, fault_seed,
                                                      swap_offsets,
                                                      with_loss):
    profs, _, _, plan_a, plan_b = _two_plan_setup()
    horizon = 4.0
    trace = _trace(profs, plan_a, horizon, load=0.7, seed=seed)

    kinds = FAULT_KINDS if with_loss else ("chip_slowdown", "exec_fault")
    sched = FaultSchedule.from_seed(fault_seed, horizon, CLUSTER.counts,
                                    chips_per_host=CLUSTER.chips_per_host,
                                    n_events=3, kinds=kinds)
    dp = DataPlane(build_runtime(plan_a, profs),
                   observer=Observer(ObsConfig(level="trace")))
    FaultInjector(sched, seed=fault_seed, max_retries=1).attach(dp)

    swap_times = sorted(swap_offsets)
    state = {"i": 0}

    def swapper(req, now):
        if state["i"] < len(swap_times) and now >= swap_times[state["i"]]:
            nxt = plan_b if state["i"] % 2 == 0 else plan_a
            state["i"] += 1
            dp.swap_plan(nxt, profs, now,
                         reason=f"swap{state['i']}@{now:.3f}s")

    dp.arrival_hooks.append(swapper)
    tel = dp.serve(trace)

    dups, missing = _outcome_uniqueness(dp.obs.journal)
    assert not dups, f"requests with multiple outcomes: {dups}"
    assert not missing, f"requests with no outcome: {missing}"
    # telemetry agrees with the journal's event counts
    assert tel.served + tel.dropped == len(tel.outcomes)
    # every drop names a known cause
    causes = {e["cause"] for e in dp.obs.journal.select("req.drop")}
    assert causes <= {"admission_reject", "backpressure_reject",
                      "overflow_shed", "expired", "scheduler",
                      "exec_failure", "node_loss"}
