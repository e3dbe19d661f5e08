"""repro.dataplane: admission control, adaptive batching, feedback
correction, overlapped dispatch, and simulator parity."""

import numpy as np
import pytest

from repro.core import blocks, costmodel as cm
from repro.core import plan_cluster
from repro.core.reservation import probe
from repro.core.runtime import build_runtime
from repro.core.simulator import run_simulation
from repro.core.types import ClusterSpec, Request
from repro.data.requests import bursty_trace, poisson_trace
from repro.dataplane import (
    AdmissionPolicy,
    DataPlane,
    FeedbackController,
)
from repro.dataplane.batcher import unloaded_latency_s
from repro.dataplane.plane import serve_trace  # package-level alias is deprecated


def _setup(slo=0.03, n_layers=8, counts=None, n_blocks=5):
    counts = counts or {"tpu-hi": 2, "tpu-lo": 4}
    layers = [cm.embed_cost(256, 1024, 32000)]
    for i in range(n_layers):
        layers.append(cm.layer_sequence_cost(f"l{i}", [
            cm.attention_cost(256, 1024, 16, 4), cm.mlp_cost(256, 1024, 4096)]))
    layers.append(cm.head_cost(256, 1024, 32000))
    prof = blocks.build_profile("m", layers, slo, n_blocks=n_blocks)
    cluster = ClusterSpec(counts=counts)
    tbl = cm.build_latency_table(prof, cluster)
    res = plan_cluster({"m": prof}, {"m": tbl}, cluster, slo_margin=0.4)
    return prof, cluster, res.plan


PROF, CLUSTER, PLAN = _setup()


def _runtime():
    return build_runtime(PLAN, {"m": PROF})


# ---------------------------------------------------------------------------
# Simulator parity: one Algorithm 1 implementation drives both worlds
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bursty", [False, True])
def test_parity_with_simulator(bursty):
    """With a permissive admission policy, planned feedback and zero noise,
    the data plane's virtual execution must match the discrete-event
    simulator outcome-for-outcome — same drops, same completion times."""
    gen = bursty_trace if bursty else poisson_trace
    trace = gen(PLAN.throughput * 0.9, 1.5, PROF.slo_s, "m", seed=3)
    sim = run_simulation(_runtime(), trace, noise_sigma=0.0)
    tel = serve_trace(_runtime(), trace, policy=AdmissionPolicy.permissive())
    smap = {o.req_id: o.completion_s for o in sim.outcomes}
    dmap = {o.req_id: o.completion_s for o in tel.outcomes}
    assert set(smap) == set(dmap)
    for rid, sc in smap.items():
        dc = dmap[rid]
        if sc is None:
            assert dc is None
        else:
            assert dc == pytest.approx(sc, abs=1e-9)
    assert tel.attainment == pytest.approx(sim.attainment, abs=1e-12)
    for c, u in sim.utilization.items():
        assert tel.utilization[c] == pytest.approx(u, abs=1e-6)


def test_parity_holds_with_heterogeneous_slos():
    """FIFO order under the permissive policy keeps parity even when SLOs
    differ per request (where EDF and arrival order genuinely diverge)."""
    base = poisson_trace(PLAN.throughput * 0.9, 1.0, PROF.slo_s, "m", seed=8)
    trace = [Request(arrival_s=r.arrival_s, req_id=r.req_id, model_name="m",
                     deadline_s=r.arrival_s + PROF.slo_s * (1 + 2 * (r.req_id % 2)))
             for r in base]
    sim = run_simulation(_runtime(), trace, noise_sigma=0.0)
    tel = serve_trace(_runtime(), trace, policy=AdmissionPolicy.permissive())
    smap = {o.req_id: o.completion_s for o in sim.outcomes}
    dmap = {o.req_id: o.completion_s for o in tel.outcomes}
    assert smap.keys() == dmap.keys()
    for rid, sc in smap.items():
        assert (sc is None) == (dmap[rid] is None)
        if sc is not None:
            assert dmap[rid] == pytest.approx(sc, abs=1e-9)


# ---------------------------------------------------------------------------
# Admission control / drop policy
# ---------------------------------------------------------------------------


def test_admission_rejects_infeasible_deadlines():
    """Requests whose SLO is below the unloaded batch-1 pipeline latency are
    refused at arrival, not queued and probed to death."""
    rt = _runtime()
    floor = min(unloaded_latency_s(p) for p in rt.pipelines)
    trace = poisson_trace(200.0, 0.5, floor * 0.5, "m", seed=0)
    tel = serve_trace(rt, trace)
    assert tel.admission_rejects == len(trace)
    assert len(tel.outcomes) == len(trace)
    assert all(o.completion_s is None for o in tel.outcomes)


def test_unknown_model_rejected_not_swallowed():
    """A request for a model no pipeline serves must produce a dropped
    outcome via admission, not vanish into an unserviced queue."""
    rt = _runtime()
    trace = [Request(arrival_s=0.0, req_id=0, model_name="ghost", deadline_s=1.0)]
    tel = serve_trace(rt, trace)
    assert tel.admission_rejects == 1
    assert len(tel.outcomes) == 1
    assert tel.outcomes[0].completion_s is None


def test_overflow_sheds_in_deadline_order():
    """A bounded queue sheds from the head (earliest deadline) on overflow,
    and every request still gets exactly one outcome."""
    rt = _runtime()
    # a single burst far above capacity, generous SLO so admission passes
    trace = [Request(arrival_s=1e-6 * i, req_id=i, model_name="m",
                     deadline_s=1e-6 * i + 1.0) for i in range(64)]
    tel = serve_trace(rt, trace, policy=AdmissionPolicy(max_depth=8))
    assert tel.overflow_sheds > 0
    assert len(tel.outcomes) == len(trace)
    shed_ids = {o.req_id for o in tel.outcomes if o.completion_s is None}
    served_ids = {o.req_id for o in tel.outcomes if o.completion_s is not None}
    assert shed_ids and served_ids
    # deadline order == arrival order here: every shed request must be older
    # than the youngest served one (heads are shed, tails survive)
    assert min(served_ids) < max(shed_ids) or max(shed_ids) < min(served_ids)


def test_expiry_prune_drops_unreachable_heads():
    rt = _runtime()
    floor = min(unloaded_latency_s(p) for p in rt.pipelines)
    # feasible at arrival, but a huge backlog makes tails expire in queue
    trace = poisson_trace(PLAN.throughput * 6.0, 0.4, max(PROF.slo_s, floor * 1.4),
                          "m", seed=1)
    tel = serve_trace(rt, trace)
    assert len(tel.outcomes) == len(trace)
    assert tel.expiry_drops + tel.sched_drops > 0


# ---------------------------------------------------------------------------
# Adaptive batching (Algorithm 1 behaviour through the data plane)
# ---------------------------------------------------------------------------


def test_dispatches_meet_oldest_deadline_and_batch_bound():
    rt = _runtime()
    trace = poisson_trace(PLAN.throughput * 0.8, 1.0, PROF.slo_s, "m", seed=2)
    tel = serve_trace(rt, trace)
    unified = {p.pipeline_id: p.unified_batch for p in rt.pipelines}
    assert tel.dispatches
    for d in tel.dispatches:
        assert d.batch_size <= unified[d.pipeline_id]
        assert d.planned_finish_s <= d.oldest_deadline_s + 1e-9


def test_batch_size_adapts_to_slo():
    """Looser SLOs leave room to accumulate bigger batches."""
    def mean_bs(slo_mult):
        rt = _runtime()
        trace = poisson_trace(PLAN.throughput * 0.5, 1.0,
                              PROF.slo_s * slo_mult, "m", seed=4)
        return serve_trace(rt, trace, policy=AdmissionPolicy.permissive()
                           ).mean_batch_size

    assert mean_bs(4.0) >= mean_bs(1.0) - 0.25


# ---------------------------------------------------------------------------
# Feedback correction
# ---------------------------------------------------------------------------


def test_feedback_scale_converges_to_real_slowdown():
    """If measured stage time is consistently 2x the plan, the EWMA folds the
    drift into StageRuntime.lat_scale and future probes price it in."""
    rt = _runtime()
    fb = FeedbackController(rt, alpha=0.4, adapt_latency=True)
    p = rt.pipelines[0]
    stage = p.stages[0]
    base = stage.latency(4)
    # calibration observation: wall == planned (ratio pinned at 1)
    fb.observe(p.pipeline_id, 0, stage.latency(4), stage.latency(4))
    for _ in range(25):
        planned = stage.latency(4)  # shrinks the error as lat_scale adapts
        fb.observe(p.pipeline_id, 0, planned, 2.0 * base)
    assert stage.lat_scale == pytest.approx(2.0, rel=0.05)
    assert stage.latency(4) == pytest.approx(2.0 * base, rel=0.05)
    # probe() must now see the corrected latency
    r = probe(p, 4, now=1e9)
    assert r.stage_durs[0] == pytest.approx(stage.latency(4), rel=1e-9)


def test_feedback_noise_does_not_drift():
    """Zero-mean noise around the plan leaves the scale near 1."""
    rng = np.random.default_rng(0)
    rt = _runtime()
    fb = FeedbackController(rt, alpha=0.3, adapt_latency=True)
    p = rt.pipelines[0]
    stage = p.stages[0]
    base = stage._base_latency(4)
    fb.observe(p.pipeline_id, 0, stage.latency(4), base)
    for _ in range(60):
        fb.observe(p.pipeline_id, 0, stage.latency(4),
                   base * float(np.exp(rng.normal(0.0, 0.05))))
    assert 0.8 < stage.lat_scale < 1.25


def test_feedback_counters_sum_walls_and_hold_the_peak():
    """Per stage, the controller sums the walls it was fed, counts them, and
    keeps the highest scale it set since `reset_peaks`."""
    rt = _runtime()
    fb = FeedbackController(rt, alpha=0.4, adapt_latency=True)
    p = rt.pipelines[0]
    stage = p.stages[0]
    key = (p.pipeline_id, 0)
    planned = stage.latency(4)
    walls = [planned, 3.0 * planned, 0.5 * planned, 0.5 * planned]
    scales = []
    for w in walls:
        fb.observe(p.pipeline_id, 0, planned, w)
        scales.append(stage.lat_scale)
    assert fb.measured_wall_s[key] == pytest.approx(sum(walls))
    assert fb.observed[key] == len(walls) == fb.observations
    assert fb.scale_peak[key] == max(scales) > stage.lat_scale
    fb.reset_peaks()
    # every stage starts the new window at its current scale, observed or not
    assert fb.scale_peak == {(q.pipeline_id, si): s.lat_scale
                             for q in rt.pipelines
                             for si, s in enumerate(q.stages)}
    fb.observe(p.pipeline_id, 0, planned, 0.25 * planned)  # the scale falls
    assert stage.lat_scale < scales[-1] == fb.scale_peak[key]
    fb.observe(p.pipeline_id, 0, planned, 8.0 * planned)  # and rises again
    assert fb.scale_peak[key] == stage.lat_scale > scales[-1]
    assert fb.observed[key] == len(walls) + 2


# ---------------------------------------------------------------------------
# Real JAX execution: overlapped pool dispatch on a 2-stage pooled pipeline
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def real_pipeline():
    import jax

    from repro.configs import get_config
    from repro.core.plan import ClusterPlan, PipelinePlan, StagePlan
    from repro.core.types import replace
    from repro.dataplane import build_executors
    from repro.models.model_zoo import layer_costs
    from repro.serving.engine import layer_block_map_from_profile

    seq = 16
    cfg = get_config("stablelm-3b").reduced(n_layers=4, d_model=128, d_ff=256,
                                            n_heads=4, kv_heads=4, vocab=512)
    costs = layer_costs(cfg, seq)
    cluster = ClusterSpec(counts={"tpu-hi": 1, "tpu-lo": 2})
    prof0 = blocks.build_profile(cfg.name, costs, slo_s=1.0, n_blocks=4,
                                 accel=cluster.accel("tpu-hi"))
    base = sum(cm.block_latency(b, cluster.accel("tpu-hi"), 1, 1)
               for b in prof0.blocks)
    prof = replace(prof0, slo_s=base * 6.0)
    tbl = cm.build_latency_table(prof, cluster)
    cut, n, bs = prof.n_blocks // 2, prof.n_blocks, 4
    plan = ClusterPlan(cluster=cluster, pipelines=[PipelinePlan(
        model_name=cfg.name, batch_size=bs,
        stages=(
            StagePlan(0, cut, "tpu-lo", 1, 2,
                      tbl.partition(0, cut, "tpu-lo", 1, bs)),
            StagePlan(cut, n, "tpu-hi", 1, 1,
                      tbl.partition(cut, n, "tpu-hi", 1, bs)),
        ),
        xfer_latency_s=(cm.transfer_latency(prof, cluster, "tpu-lo", "tpu-hi",
                                            cut, bs),),
    )])
    lbm = layer_block_map_from_profile(prof, cfg.n_layers)
    executors = build_executors(cfg, plan, lbm, jax.random.PRNGKey(0))
    return cfg, prof, plan, executors, seq


def test_real_dispatcher_keeps_batches_in_flight(real_pipeline):
    """Acceptance: >1 batch in flight across stages — batch i+1 is submitted
    (and enqueued on the device stream) before batch i's last stage is
    observed complete."""
    import jax.numpy as jnp

    from repro.dataplane import PoolDispatcher

    cfg, prof, plan, executors, seq = real_pipeline
    disp = PoolDispatcher(executors, max_inflight=4)
    tokens = jnp.ones((4, seq), jnp.int32)
    for _ in range(3):
        disp.submit_chain(0, tokens)
    assert disp.inflight == 3
    assert disp.inflight_hwm == 3
    done = disp.drain_all()
    assert len(done) == 3
    by_id = {c.job_id: c for c in done}
    first, second = by_id[0], by_id[1]
    # overlap: the second batch entered the pipeline before the first left it
    assert second.submit_wall < first.done_wall
    for c in done:
        assert len(c.stage_wall_s) == 2
        assert c.total_wall_s > 0


def test_real_dataplane_serves_trace_with_overlap(real_pipeline):
    from repro.core.runtime import build_runtime as _br

    from repro.dataplane import PoolDispatcher

    cfg, prof, plan, executors, seq = real_pipeline
    rt = _br(plan, {cfg.name: prof})
    thr = plan.throughput
    trace = poisson_trace(thr * 0.5, 24 / (thr * 0.5), prof.slo_s, cfg.name,
                          seed=5)
    disp = PoolDispatcher.from_runtime(rt, executors, max_inflight=4)
    tel = DataPlane(rt, dispatcher=disp, feedback="planned", seq_len=seq
                    ).serve(trace)
    assert len(tel.outcomes) == len(trace)
    assert tel.inflight_hwm > 1  # overlap actually happened
    # real execution measured for both stages of the pipeline (epoch 0)
    assert (0, 0, 0) in tel.stage_wall_s and (0, 0, 1) in tel.stage_wall_s
    assert all(w >= 0 for ws in tel.stage_wall_s.values() for w in ws)
    assert tel.attainment > 0.9  # low virtual load on a valid plan


def test_stage_walls_bucket_by_submit_epoch_on_shared_dispatcher(real_pipeline):
    """A swap_plan factory may return the SAME dispatcher (shared compiled
    executors).  Batches must still bucket their measured stage walls under
    the plan epoch that SUBMITTED them — pipeline ids restart at 0 per
    epoch, so blending epochs would corrupt the percentile telemetry."""
    from repro.core.runtime import build_runtime as _br

    from repro.dataplane import PoolDispatcher

    cfg, prof, plan, executors, seq = real_pipeline
    rt = _br(plan, {cfg.name: prof})
    thr = plan.throughput
    trace = poisson_trace(thr * 0.5, 24 / (thr * 0.5), prof.slo_s, cfg.name,
                          seed=9)
    disp = PoolDispatcher.from_runtime(rt, executors, max_inflight=4)
    dp = DataPlane(rt, dispatcher=disp, feedback="planned", seq_len=seq)
    mid = trace[len(trace) // 2].arrival_s
    fired = []

    def hook(req, t):
        if not fired and t > mid:
            fired.append(t)
            dp.swap_plan(plan, {cfg.name: prof}, now=t,
                         dispatcher_factory=lambda _rt: disp)

    dp.arrival_hooks.append(hook)
    tel = dp.serve(trace)
    assert tel.plan_swaps == 1
    epochs = {k[0] for k in tel.stage_wall_s}
    assert epochs == {0, 1}  # both epochs measured, neither blended away
    n_batches = sum(len(ws) for (e, p, si), ws in tel.stage_wall_s.items()
                    if si == 0)
    assert n_batches == len(tel.dispatches)


def test_real_measured_feedback_end_to_end(real_pipeline):
    from repro.core.runtime import build_runtime as _br

    from repro.dataplane import PoolDispatcher, calibrate_runtime

    cfg, prof, plan, executors, seq = real_pipeline
    rt = _br(plan, {cfg.name: prof})
    measured = calibrate_runtime(rt, executors, seq)
    assert measured  # profiler produced entries
    p0 = rt.pipelines[0]
    e2e = sum(s.latency(1) for s in p0.stages)
    assert e2e > 1e-4  # wall-clock scale now, not cost-model scale
    thr = min(len(s.vdevs) * p0.unified_batch / s.latency(p0.unified_batch)
              for s in p0.stages)
    trace = bursty_trace(thr * 0.4, 16 / (thr * 0.4), e2e * 8, cfg.name, seed=6)
    disp = PoolDispatcher.from_runtime(rt, executors, max_inflight=4)
    dp = DataPlane(rt, dispatcher=disp, feedback="measured", seq_len=seq)
    tel = dp.serve(trace)
    assert len(tel.outcomes) == len(trace)
    assert dp.fb.observations > 0  # feedback loop actually closed
    assert 0.0 <= tel.attainment <= 1.0


def test_transfer_skips_integer_and_same_device(real_pipeline):
    import jax.numpy as jnp

    cfg, prof, plan, executors, seq = real_pipeline
    ex = executors[0][1]  # second stage: the one receiving a boundary tensor
    tokens = jnp.arange(8, dtype=jnp.int32).reshape(2, 4)
    assert ex.transfer(tokens) is tokens  # integer carries are never quantized
    h = jnp.linspace(-1.0, 1.0, 2 * 4 * 8, dtype=jnp.bfloat16).reshape(2, 4, 8)
    # single host: sender and receiver share the device -> identity, no
    # quantize->dequantize round trip
    assert ex.transfer(h) is h


def _trace_reduce():
    """The chip benchmark's trace reader (benchmarks/chip/trace_reduce.py)."""
    import importlib.util
    from pathlib import Path

    path = (Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
            / "trace_reduce.py")
    spec = importlib.util.spec_from_file_location("trace_reduce", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_host_spans_on_the_profiler_clock(real_pipeline, tmp_path):
    """Measured feedback served under the JAX profiler: the plane's host
    spans are on the profiler's host plane, flat (none inside another), one
    `ppipe.submit` and one `ppipe.tokens` per dispatch, and at most one
    `ppipe.wait` per stage of a dispatch."""
    import jax

    from repro.core.runtime import build_runtime as _br
    from repro.dataplane import PoolDispatcher, calibrate_runtime
    from repro.obs.host import SCHEDULE, SPANS, SUBMIT, TOKENS, WAIT

    cfg, prof, plan, executors, seq = real_pipeline
    rt = _br(plan, {cfg.name: prof})
    calibrate_runtime(rt, executors, seq)
    p0 = rt.pipelines[0]
    e2e = sum(s.latency(1) for s in p0.stages)
    thr = min(len(s.vdevs) * p0.unified_batch / s.latency(p0.unified_batch)
              for s in p0.stages)
    trace = poisson_trace(thr * 0.4, 16 / (thr * 0.4), e2e * 8, cfg.name,
                          seed=11)
    dp = DataPlane(rt, dispatcher=PoolDispatcher.from_runtime(rt, executors),
                   feedback="measured", seq_len=seq)
    with jax.profiler.trace(str(tmp_path)):
        tel = dp.serve(trace)
    host = _trace_reduce().read_xplane(tmp_path)["host"]
    spans = sorted((s, s + d, name) for name, s, d in host if name in SPANS)
    counts = {name: sum(1 for *_, n in spans if n == name) for name in SPANS}
    n = len(tel.dispatches)
    assert n > 0 and dp.fb.observations == n * len(p0.stages)
    assert counts[SUBMIT] == counts[TOKENS] == n
    assert 0 < counts[WAIT] <= n * len(p0.stages)
    assert counts[SCHEDULE] >= n
    for (_, end, _), (start, _, _) in zip(spans, spans[1:]):
        assert start >= end  # flat: no ppipe span encloses another
