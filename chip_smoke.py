#!/usr/bin/env python3
"""Chip smoke run: the real serving path at full width on one TPU.

    python chip_smoke.py                              # one TPU chip
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny     # CPU rehearsal only

Everything runs in this one process (a chip belongs to one process at a
time).  Each phase prints one line; a failing phase exits non-zero and the
final JSON line is never printed.

  device     platform, device_kind and device count; anything but a TPU fails
  kernel     boundary_quant quantize/dequantize at (8*seq, d_model) bf16 on
             the chip against the jnp oracle; the compiled wrapper must hold
             a `tpu_custom_call` (the Pallas kernel, not interpret mode)
  serve      stablelm-3b at its published width (32 layers, d_model 2560,
             bf16, seq 256, random weights from --seed) through the public
             path: Session -> plan()/use_plan() -> deploy(mode="real") with
             measured feedback -> run() of a Poisson trace.  Fails on any
             drop, any executor retry, or any compilation inside run()
  reference  one batch of 8 through the deployed plan's chained stage
             executors against `model.forward` on the same parameters

Last line on success: {"ok": true, "device": {"platform", "kind", "count"}}.

`--tiny` shrinks the model for a CPU rehearsal of the control flow; it
accepts any platform, skips the `tpu_custom_call` check off the TPU, and
never prints the JSON line.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "stablelm-3b"
SEQ = 256  # benchmarks/common.py SERVE_SEQ
BATCH = 8  # kernel rows = BATCH * seq; reference batch
N_REQUESTS = 64
LOAD = 0.5  # offered rate / calibrated one-chip throughput
SLO_FACTOR = 8.0  # trace SLO / calibrated pipeline latency: a drop is a fault
REF_TOL = 2e-2  # max |staged - forward| / max |forward|, bf16 logits
INVENTORY = {"tpu-hi": 1, "tpu-lo": 8}  # examples/serve_pipeline.py
TINY = dict(n_layers=4, d_model=256, d_ff=512, n_heads=4, kv_heads=4,
            vocab=2048)
TINY_SEQ = 32


def phase_device(ctx: dict) -> str:
    import jax

    devs = jax.devices()
    ctx["device"] = {"platform": devs[0].platform,
                     "kind": devs[0].device_kind, "count": len(devs)}
    line = (f"platform={devs[0].platform} kind={devs[0].device_kind} "
            f"count={len(devs)}")
    if devs[0].platform != "tpu" and not ctx["tiny"]:
        raise RuntimeError(f"no TPU: JAX found {line}")
    return line


def phase_kernel(ctx: dict) -> str:
    import jax
    import jax.numpy as jnp

    from repro.kernels.boundary_quant import ops, ref

    rows, width = BATCH * ctx["seq"], ctx["model_cfg"].d_model
    kx, ks = jax.random.split(jax.random.PRNGKey(ctx["seed"]))
    # per-row magnitudes spread over ~3 decades so per-row scales differ
    x = (jax.random.normal(kx, (rows, width), jnp.float32)
         * jnp.exp(2.0 * jax.random.normal(ks, (rows, 1)))).astype(jnp.bfloat16)
    q, s = ops.quantize(x)
    y = ops.dequantize(q, s, jnp.bfloat16)
    q_ref, s_ref = ref.quantize_ref(x)
    x32, y32 = x.astype(jnp.float32), y.astype(jnp.float32)
    scale_err = float(jnp.max(jnp.abs(s - s_ref) / s_ref))
    q_diff = int(jnp.max(jnp.abs(q.astype(jnp.int32) - q_ref.astype(jnp.int32))))
    # half a quantization step, plus the bf16 rounding of the dequantized value
    excess = float(jnp.max(jnp.abs(x32 - y32) - s / 2 - jnp.abs(x32) * 2.0**-8))
    if scale_err > 1e-6 or q_diff > 1 or excess > 1e-6:
        raise AssertionError(f"quantization off the oracle: scale_err="
                             f"{scale_err} q_diff={q_diff} excess={excess}")
    custom = all("tpu_custom_call" in fn.lower(*args).compile().as_text()
                 for fn, args in ((ops.quantize, (x,)),
                                  (ops.dequantize, (q, s))))
    if ctx["device"]["platform"] == "tpu" and not custom:
        raise AssertionError("boundary_quant compiled without tpu_custom_call")
    return (f"shape=({rows},{width}) bf16 scale_rel_err={scale_err:.3g} "
            f"max_q_diff={q_diff} bound_excess={excess:.3g} "
            f"tpu_custom_call={custom}")


def _partitioned_plan(session):
    """The MILP plan when it partitions; else a pinned 2-stage pipeline
    (low-class pool feeding the high-class chip), so that a stage boundary
    exists either way."""
    from repro.core import costmodel as cm
    from repro.core.plan import ClusterPlan, PipelinePlan, StagePlan

    plan = session.plan()
    if any(len(pp.stages) > 1 for pp in plan.pipelines):
        return plan, "milp"
    prof = session.store.profiles[ARCH]
    tbl = session.store.analytic_table(ARCH)
    cluster = session.config.cluster
    n, cut, bs = prof.n_blocks, prof.n_blocks // 2, 4
    pinned = ClusterPlan(cluster=cluster, pipelines=[PipelinePlan(
        model_name=ARCH, batch_size=bs,
        stages=(StagePlan(0, cut, "tpu-lo", 1, 3,
                          tbl.partition(0, cut, "tpu-lo", 1, bs)),
                StagePlan(cut, n, "tpu-hi", 1, 1,
                          tbl.partition(cut, n, "tpu-hi", 1, bs))),
        xfer_latency_s=(cm.transfer_latency(prof, cluster, "tpu-lo",
                                            "tpu-hi", cut, bs),),
    )])
    session.use_plan(pinned)
    return pinned, "pinned"


def phase_serve(ctx: dict) -> str:
    from repro.api import ClusterSpec, ModelSpec, ServeConfig, Session
    from repro.api.compilation import count_compiles
    from repro.data.requests import poisson_trace

    cfg = ServeConfig(
        cluster=ClusterSpec(counts=dict(INVENTORY)),
        models=(ModelSpec(arch=ARCH, seq_len=ctx["seq"],
                          reduced=TINY if ctx["tiny"] else None),),
        feedback="measured",
        serve_seq_len=ctx["seq"],
        seed=ctx["seed"],
    )
    session = Session.from_config(cfg)
    ctx["session"] = session
    plan, how = _partitioned_plan(session)
    t0 = time.perf_counter()
    with count_compiles() as setup_compiles:
        session.deploy(mode="real")  # init + warm compile + calibrate
    setup_s = time.perf_counter() - t0

    # every pool of the plan shares the one chip, so the chip's throughput is
    # one request per (sum of calibrated stage latencies / unified batch)
    chip_s_per_req, e2e_s = 0.0, 0.0
    for p in session.runtime.pipelines:
        lat = sum(s.latency(p.unified_batch) for s in p.stages)
        chip_s_per_req = max(chip_s_per_req, lat / p.unified_batch)
        e2e_s = max(e2e_s, lat)
    rate = LOAD / chip_s_per_req
    trace = poisson_trace(rate, N_REQUESTS / rate, SLO_FACTOR * e2e_s, ARCH,
                          seed=ctx["seed"])
    t1 = time.perf_counter()
    with count_compiles() as run_compiles:
        report = session.run(trace)
    run_wall_s = time.perf_counter() - t1
    tel = report.telemetry
    # measured feedback: stage durations on this clock are calibrated
    # wall-clock measurements of the chip, so latencies are wall seconds
    lats = sorted(o.completion_s - o.arrival_s for o in tel.outcomes
                  if o.completion_s is not None)
    p50 = lats[len(lats) // 2] * 1e3 if lats else float("nan")
    p99 = lats[min(len(lats) - 1, int(0.99 * len(lats)))] * 1e3 if lats \
        else float("nan")
    # per pipeline: batch, then each stage's blocks, class and pool size
    pipelines = ",".join(
        f"bs{pp.batch_size}:" + "|".join(
            f"[{s.block_start},{s.block_end}){s.accel_class}*{s.n_vdev}"
            for s in pp.stages)
        for pp in plan.pipelines)
    line = (f"plan={how} pipelines={pipelines} sent={len(trace)} "
            f"served={tel.served} dropped={tel.dropped} "
            f"attainment={tel.attainment:.4f} p50_ms={p50:.3f} "
            f"p99_ms={p99:.3f} rate_rps={rate:.2f} slo_ms={SLO_FACTOR*e2e_s*1e3:.3f} "
            f"setup_s={setup_s:.2f} setup_compiles={setup_compiles.n} "
            f"run_wall_s={run_wall_s:.2f} run_compiles={run_compiles.n} "
            f"exec_failures={tel.exec_failures} retries={tel.retries}")
    if (tel.served < len(trace) or tel.exec_failures or tel.retries
            or run_compiles.n):
        raise AssertionError(line)
    return line


def phase_reference(ctx: dict) -> str:
    import jax
    import jax.numpy as jnp

    from repro.models import build_model

    session = ctx["session"]
    executors = session.dataplane.dispatcher.executors
    pid = max(executors, key=lambda k: len(executors[k]))
    chain = executors[pid]
    params = chain[0].params
    tokens = jax.random.randint(jax.random.PRNGKey(ctx["seed"] + 1),
                                (BATCH, ctx["seq"]), 0, ctx["model_cfg"].vocab,
                                jnp.int32)
    carry = tokens
    for si, ex in enumerate(chain):
        if si > 0:
            carry = ex.transfer(carry)
        carry = ex(carry)
    model = build_model(ctx["model_cfg"])
    full = jax.jit(model.forward)(params, {"tokens": tokens})
    staged = carry.astype(jnp.float32)
    full = full.astype(jnp.float32)
    max_abs = float(jnp.max(jnp.abs(staged - full)))
    max_rel = max_abs / float(jnp.max(jnp.abs(full)))
    line = (f"stages={len(chain)} logits={tuple(full.shape)} "
            f"max_abs_err={max_abs:.4g} max_rel_err={max_rel:.4g} "
            f"tol={REF_TOL}")
    if not (jnp.isfinite(staged).all() and max_rel <= REF_TOL):
        raise AssertionError(line)
    return line


PHASES = (("device", phase_device), ("kernel", phase_kernel),
          ("serve", phase_serve), ("reference", phase_reference))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="reduced model for a CPU rehearsal (no JSON line)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, kernel input, trace and tokens")
    args = ap.parse_args()

    from repro.api.compilation import enable_compile_cache
    from repro.configs import get_config

    enable_compile_cache()
    model_cfg = get_config(ARCH)
    if args.tiny:
        model_cfg = model_cfg.reduced(**TINY)
    ctx = {"tiny": args.tiny, "seed": args.seed, "model_cfg": model_cfg,
           "seq": TINY_SEQ if args.tiny else SEQ}
    for name, fn in PHASES:
        t0 = time.perf_counter()
        try:
            line = fn(ctx)
        except Exception as exc:  # noqa: BLE001 — report the phase, then fail
            traceback.print_exc()
            print(f"[{name}] FAILED: {exc}", flush=True)
            return 1
        print(f"[{name}] {line} ({time.perf_counter() - t0:.1f}s)", flush=True)
    if args.tiny:
        print("tiny rehearsal passed; no result line off the full-size run")
        return 0
    print(json.dumps({"ok": True, "device": ctx["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
