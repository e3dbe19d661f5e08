#!/usr/bin/env python3
"""Find a configuration's SLO and knee on the chip, once; the cells then fix
both in its file.

    python3 benchmarks/chip/sweep.py --config stablelm-3b --seconds 6 \\
        --loads 0.5 0.7 0.9 1.1

Deploys the configuration as a run does, then prints one JSON line each:

* ``calibration``: the calibrated stage walls (ms) at each batch bucket, and
  the SLO by the paper's rule (section 7.1): 5 x the batch-1 latency of the
  whole model, the sum of the batch-1 stage walls, unless `--slo-ms` fixes it;
* ``load``: for each load (a multiple of the chip's serial capacity at the
  plan's batch, batch / sum of the stage walls at that batch), one window of
  `--seconds` of Poisson arrivals at the SLO: attainment, goodput, p50/p95,
  the serve call's wall time, and queue delay in the window's first and last
  thirds (a backlog that grows shows as a later third that waits longer).

The knee is the highest rate with attainment >= 0.99 and no growing backlog.
`--trace-out` also writes the reduced profiler trace of a short window, the
kind of recording tests/data keeps.
"""

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))

import numpy as np  # noqa: E402

import harness  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--loads", type=float, nargs="+", required=True)
    ap.add_argument("--slo-ms", type=float, default=None)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--trace-out", default=None)
    args = ap.parse_args()

    device = harness.check_device(1)
    harness.enable_compile_cache()
    from repro.stream import TraceSource

    conf = harness.load_config(harness.load_benchmark(), args.config)
    conf["slo_ms"] = 1e6  # provisional: the plan validates, deadlines are ours
    t0 = time.perf_counter()
    capture = harness.Capture(args.seed, conf["model"]["vocab"])
    session, cfg = harness.deploy(conf, args.seed, capture)
    stages = session.runtime.pipelines[0].stages
    lat = [{b: s.latency_by_batch[b] * 1e3 for b in sorted(s.latency_by_batch)}
           for s in stages]
    slo_ms = args.slo_ms or 5 * sum(s[1] for s in lat)
    batch = conf["plan"]["batch"]
    capacity = batch / sum(s[batch] for s in lat) * 1e3
    print(json.dumps({"calibration": lat, "slo_ms_rule": 5 * sum(
        s[1] for s in lat), "slo_ms": slo_ms, "capacity_rps": capacity,
        "setup_s": time.perf_counter() - t0, "device": device}), flush=True)

    mix = {"process": "poisson"}
    rng = np.random.default_rng(args.seed)
    tel = session.telemetry
    first_id = 0
    for load in args.loads:
        rate = load * capacity
        offset = float(np.ceil(tel.horizon_s)) + 1.0
        reqs = harness.requests(traffic.arrivals(mix, rate, args.seconds, rng),
                                offset, first_id, cfg.name, slo_ms / 1e3)
        first_id += len(reqs)
        n_qd, n_disp = len(tel.queue_delay_s), len(tel.dispatches)
        t1 = time.perf_counter()
        session.serve(TraceSource(reqs))
        wall = time.perf_counter() - t1
        ids = {r.req_id for r in reqs}
        outs = {o.req_id: o for o in tel.outcomes if o.req_id in ids}
        ctx = {"requests": reqs, "outcomes": outs}
        lats = harness.window_latencies_ms(ctx)
        ok = sum(1 for o in outs.values() if o.ok)
        qd = tel.queue_delay_s[n_qd:]
        third = max(1, len(qd) // 3)
        print(json.dumps({
            "load": load, "rate_rps": rate, "n": len(reqs),
            "attainment": ok / len(reqs),
            "goodput_rps": ok / max(args.seconds, wall), "wall_s": wall,
            "p50_ms": harness.percentile(lats, 50),
            "p95_ms": harness.percentile(lats, 95),
            "queue_ms_first_third": 1e3 * float(np.mean(qd[:third])),
            "queue_ms_last_third": 1e3 * float(np.mean(qd[-third:])),
            "mean_batch": float(np.mean([d.batch_size for d in
                                         tel.dispatches[n_disp:]])),
        }), flush=True)

    if args.trace_out:
        import tempfile

        import jax

        reqs = harness.requests(
            traffic.arrivals(mix, 0.5 * capacity, 1.0, rng),
            float(np.ceil(tel.horizon_s)) + 1.0, first_id, cfg.name,
            slo_ms / 1e3)
        logdir = tempfile.mkdtemp(prefix="chipbench-sweep-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(logdir, profiler_options=opts)
        t1 = time.perf_counter()
        session.serve(TraceSource(reqs))
        wall = time.perf_counter() - t1
        jax.profiler.stop_trace()
        events = trace_reduce.read_xplane(logdir)
        from jax.profiler import ProfileData

        pd = ProfileData.from_file(str(next(Path(logdir).rglob("*.xplane.pb"))))
        layout = {p.name: {ln.name: len(list(ln.events)) for ln in p.lines}
                  for p in pd.planes}
        Path(args.trace_out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.trace_out).write_text(json.dumps(
            {"window_s": wall, "requests": len(reqs), "layout": layout,
             **events}))
        print(json.dumps({"trace": trace_reduce.summarize(events, wall),
                          "layout": layout}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
