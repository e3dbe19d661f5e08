"""One run of one benchmark cell on the chip, as `run.py` drives it.

A cell names a configuration file (`configs/<config>.json`) and a traffic
mix (`traffic/<mix>.json`); the file's model family (`families/<family>.py`)
gives the widths to check, the plain reference and the costs; metrics are
readers under `metrics/`, found by the names in BENCHMARK.json.  A run:

1. checks that JAX sees a TPU with as many chips as the cell asks for;
2. builds a `Session` from the configuration file: the published widths
   (every width of the family's `KEYS`, checked against the program),
   the pinned plan, the SLO, the program's weights from the seed, and a
   seeded token stream (`Capture.token_fn`) in place of the all-ones default;
3. deploys (`deploy(mode="real")`, measured feedback: warm compile of every
   batch bucket, then calibration) and serves a warm-up trace of its own;
   everything up to here is `setup_s`;
4. serves the window: round(rate x seconds) arrivals of the mix, offset
   past the warm-up on the session's one clock.  Nothing may compile here.
   At its start the measured-feedback counters are taken and each stage's
   scale peak reset; after it their deltas are kept (`ctx["feedback"]`);
5. reads the metrics (with `--trace 1` under the JAX profiler, and then the
   per-layer metrics instead of the end-to-end ones: the trace gives
   `ctx["trace"]`, with every device op's seconds and calls, and
   `ctx["spans"]`, the program's host spans);
6. frees the program and compares what the window served against the
   family's plain reference: a seeded reservoir of the window's batches
   keeps each one's tokens and the top-1 token the program served at every
   position, and the reference scores those tokens;
7. prints the checks on stderr and one JSON line on stdout.
"""

from __future__ import annotations

import dataclasses
import gc
import hashlib
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

import costs
import families
import reference
import span_reduce
import trace_reduce
import traffic

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]
WARMUP_S = 2.0  # virtual seconds of warm-up traffic, served before the window
SAMPLE_BATCHES = 6  # window batches kept for the comparison (reservoir)
# the reading `correct` holds to its limit: the mean top-1 gap of the worst
# sampled request (PERF.md says why not the widest gap of one position)
CHECKED = "top1_gap_worst_row"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ------------------------------------------------------------------ loading
def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_config(bench: dict, name: str) -> dict:
    entry = next(c for c in bench["configs"] if c["name"] == name)
    return json.loads((ROOT / entry["file"]).read_text())


def cell_metrics(bench: dict, kind: str, cell: str) -> list[dict]:
    """The `kind` ("end_to_end" | "per_layer") metrics a cell reports."""
    e2e = {m["name"] for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])}
    out = []
    for m in bench[kind]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out


def read_metric(name: str, ctx: dict):
    """Run the reader `metrics/<name>.py` on the run's context."""
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", BENCH_DIR / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def derive(seed: int, tag: str) -> int:
    """A 31-bit seed for one use (`tag`) of the run's seed."""
    h = hashlib.sha256(f"{seed}:{tag}".encode()).digest()
    return int.from_bytes(h[:4], "little") & 0x7FFFFFFF


# ------------------------------------------------------------------ device
def describe_device() -> dict:
    """The device as JAX reports it, in the result line's terms.  Built with
    dict(): the repo's journal linter reads a literal "kind" key as a
    journal event, and this is not one."""
    import jax

    devs = jax.devices()
    return dict(platform=devs[0].platform, kind=devs[0].device_kind,
                count=len(devs))


def check_device(chips: int) -> dict:
    device = describe_device()
    if device["platform"] != "tpu" or device["count"] < chips:
        raise NoChip(f"need {chips} TPU chip(s); JAX found {device}")
    return device


def enable_compile_cache() -> None:
    """The program's persistent cache (`repro.api.compilation`: the directory
    JAX_COMPILATION_CACHE_DIR names, else `.jax_cache` at the checkout's
    root), with every program kept, so that the second run of a cell
    compiles nothing."""
    import jax

    from repro.api.compilation import enable_compile_cache as enable

    enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# ----------------------------------------------------------------- capture
def bucket(n: int) -> int:
    """The power-of-two batch a program runs `n` requests at."""
    b = 1
    while b < n:
        b *= 2
    return b


class _Tap:
    """A stage executor whose outputs are also handed to `on_output`."""

    def __init__(self, inner, on_output):
        self._inner, self._on_output = inner, on_output

    def __call__(self, carry):
        out = self._inner(carry)
        self._on_output(out)
        return out

    def __getattr__(self, name):
        return getattr(self._inner, name)


class Capture:
    """Seeded tokens in, served top-1 tokens out, for a reservoir sample of
    the window's batches.

    `token_fn` is the session's `ServeConfig.token_fn`: it pads each batch
    to the next power of two (as the default does) and fills it with seeded
    token ids.  The tap on each pipeline's last stage sees that batch's
    logits; while `active`, batch i enters a reservoir of `k` with
    probability k/(i+1) (algorithm R), and only then is its top-1 token per
    position computed on the device, so the window pays for about
    k(1 + ln(N/k)) small argmax programs out of N batches."""

    def __init__(self, seed: int, vocab: int, k: int = SAMPLE_BATCHES):
        self.vocab, self.k = vocab, k
        self.tokens = np.random.default_rng(derive(seed, "tokens"))
        self.pick = np.random.default_rng(derive(seed, "sample"))
        self.active = False
        self.batches = 0
        self.reservoir: dict[int, tuple] = {}
        self._pending: list[tuple[int, np.ndarray]] = []
        self._argmax = None

    def token_fn(self, n: int, seq_len: int):
        import jax.numpy as jnp

        toks = self.tokens.integers(0, self.vocab, (bucket(n), seq_len),
                                    dtype=np.int32)
        if self.active:
            self._pending.append((n, toks))
        return jnp.asarray(toks)

    def install(self, session) -> None:
        import jax
        import jax.numpy as jnp

        self._argmax = jax.jit(lambda x: jnp.argmax(x, axis=-1)
                               .astype(jnp.int32))
        for chain in session.dataplane.dispatcher.executors.values():
            chain[-1] = _Tap(chain[-1], self._on_output)

    def warm(self, buckets, seq_len: int, padded_vocab: int) -> None:
        """Compile the argmax for each bucket's bf16 logits before the window."""
        import jax.numpy as jnp

        for b in buckets:
            x = jnp.zeros((b, seq_len, padded_vocab), jnp.bfloat16)
            self._argmax(x).block_until_ready()

    def _on_output(self, logits) -> None:
        if not self.active:
            return
        if len(self._pending) != 1:
            raise RuntimeError(
                f"{len(self._pending)} token batches pending at a stage "
                "output: the served batches no longer pair with their tokens")
        n, toks = self._pending.pop()
        i, self.batches = self.batches, self.batches + 1
        slot = i if i < self.k else int(self.pick.integers(0, i + 1))
        if slot < self.k:
            self.reservoir[slot] = (n, toks, self._argmax(logits))

    def samples(self) -> list[tuple[int, np.ndarray, np.ndarray]]:
        return [(n, toks, np.asarray(top1))
                for n, toks, top1 in self.reservoir.values()]


# ----------------------------------------------------------------- session
def model_overrides(conf: dict) -> dict | None:
    """`ModelSpec.reduced` overrides that give the program the file's widths:
    each width of the family's `KEYS` that `ModelConfig` has a field for is
    restated (`ModelConfig.reduced` shrinks what is not); a width read
    through a property is set by the field its file key names.  None where
    the program's own configuration already has every width."""
    from repro.configs import get_config

    keys, m = families.of(conf).KEYS, conf["model"]
    missing = sorted(set(keys) - set(m))
    if missing:
        raise ValueError(f"{conf['name']}: the file restates no {missing}; "
                         "its family's KEYS name every width to restate")
    base = get_config(conf["arch"])
    if all(getattr(base, attr) == m[key] for key, attr in keys.items()):
        return None
    fields = {f.name for f in dataclasses.fields(base)}
    return {attr if attr in fields else key: m[key]
            for key, attr in keys.items() if attr in fields or key in fields}


def program_model(conf: dict):
    """The program's ModelConfig for the file, checked against its widths
    and its family's structure."""
    from repro.configs import get_config

    fam, m = families.of(conf), conf["model"]
    over = model_overrides(conf)
    cfg = get_config(conf["arch"])
    if over:
        cfg = cfg.reduced(**over)
    got = {key: getattr(cfg, attr) for key, attr in fam.KEYS.items()}
    want = {key: m[key] for key in fam.KEYS}
    if got != want or not fam.check(cfg):
        raise ValueError(f"program's {conf['arch']} is {got}, file says {want}")
    return cfg


def pinned_plan(session, conf: dict, model_name: str):
    from repro.core import costmodel as cm
    from repro.core.plan import ClusterPlan, PipelinePlan, StagePlan

    plan = conf["plan"]
    cluster = session.config.cluster
    prof = session.store.profiles[model_name]
    stages = tuple(StagePlan(s["blocks"][0], s["blocks"][1], s["class"], 1,
                             s["members"], s["latency_ms"] / 1e3)
                   for s in plan["stages"])
    xfer = tuple(cm.transfer_latency(prof, cluster, a.accel_class,
                                     b.accel_class, a.block_end,
                                     plan["batch"])
                 for a, b in zip(stages, stages[1:]))
    return ClusterPlan(cluster=cluster, pipelines=[PipelinePlan(
        model_name=model_name, batch_size=plan["batch"], stages=stages,
        xfer_latency_s=xfer)])


def stage_layers(session, conf: dict, model_name: str, n_layers: int):
    """Each pinned stage's (layer_start, layer_end), embed, unembed — from
    the program's profile, checked against the file.  A profile block spans
    cost units [layer_start, layer_end): unit 0 is the embedding, unit k + 1
    model layer k, the last unit the head."""
    blocks = session.store.profiles[model_name].blocks
    out = []
    for s in conf["plan"]["stages"]:
        b0, b1 = s["blocks"]
        layers = (max(0, blocks[b0].layer_start - 1),
                  min(n_layers, blocks[b1 - 1].layer_end - 1))
        if list(layers) != s["layers"]:
            raise ValueError(f"stage blocks {s['blocks']} hold layers "
                             f"{layers}, file says {s['layers']}")
        out.append((layers, b0 == 0, b1 == len(blocks)))
    return out


def deploy(conf: dict, seed: int, capture: Capture):
    """A deployed Session for the configuration file, with its pinned plan."""
    from repro.api import ClusterSpec, ModelSpec, ServeConfig, Session

    cfg = program_model(conf)
    spec = ModelSpec(arch=conf["arch"], slo_s=conf["slo_ms"] / 1e3,
                     seq_len=conf["seq_len"],
                     n_blocks=conf["plan"]["n_blocks"],
                     reduced=model_overrides(conf))
    scfg = ServeConfig(cluster=ClusterSpec(counts=dict(conf["inventory"])),
                       models=(spec,), feedback="measured",
                       serve_seq_len=conf["seq_len"],
                       seed=derive(seed, "weights"),
                       token_fn=capture.token_fn)
    session = Session.from_config(scfg)
    session.profile()
    session.use_plan(pinned_plan(session, conf, cfg.name))
    session.deploy(mode="real")
    capture.install(session)
    return session, cfg


def requests(times: np.ndarray, offset: float, first_id: int,
             model_name: str, slo_s: float) -> list:
    from repro.core.types import Request

    return [Request(arrival_s=offset + float(t), req_id=first_id + i,
                    model_name=model_name,
                    deadline_s=offset + float(t) + slo_s)
            for i, t in enumerate(times)]


def feedback_state(session) -> dict:
    """The data plane's measured-feedback state and drop counters so far
    (stderr only: they explain a window, they are not metrics)."""
    dp, tel = session.dataplane, session.telemetry
    return {"lat_scale": [round(s.lat_scale, 4)
                          for p in dp.rt.pipelines for s in p.stages],
            "calib": {str(k): round(v, 4) for k, v in dp.fb.calib.items()},
            "drops": {k: getattr(tel, k) for k in (
                "admission_rejects", "backpressure_rejects", "overflow_sheds",
                "expiry_drops", "sched_drops")}}


def feedback_counters(session) -> list[dict] | None:
    """Each plan stage's measured-feedback counters: the sum and number of
    the walls `FeedbackController.observe` received and the highest scale it
    set since `reset_peaks`; None where the program keeps none.  Reading
    them changes no state of the program."""
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


def spans_line(ctx: dict) -> str:
    """Per-span seconds and counts, device idle by span, and each stage's
    measured wall against its device time."""
    sp = ctx["spans"]
    spans = " ".join(f"{k}={v['s']:.6f}s/{v['n']}"
                     for k, v in sp["spans"].items())
    idle = " ".join(f"{k}={v:.6f}s" for k, v in sorted(
        sp["idle_by_span"].items(), key=lambda kv: -kv[1]))
    stages = []
    for s in ctx["feedback"] or ():
        name = "jit_stage_b{}_{}".format(*s["blocks"])
        dev = sp["modules"].get(name, {"s": 0.0, "n": 0})
        stages.append(f"{name}: wall {s['measured_wall_s']:.6f}s/"
                      f"{s['observed']} device {dev['s']:.6f}s/{dev['n']} "
                      f"scale peak {s['scale_peak']}")
    return (f"[spans] {spans}; idle {sp['idle_s']:.6f}s: {idle}; "
            f"stages {'; '.join(stages)}")


# -------------------------------------------------------------- comparison
def gap_stats(gaps: np.ndarray) -> dict:
    """Readings of the top-1 gaps, one row per request and one column per
    position; `top1_gap_worst_row` is the largest of the rows' means."""
    return {"top1_gap_worst_row": float(gaps.mean(axis=1).max()),
            "top1_gap_mean": float(gaps.mean()),
            "top1_gap": float(gaps.max()),
            "top1_disagree": float(np.mean(gaps > 0))}


def compare(samples, fam, m: dict, seed: int,
            control: str | None = None) -> dict:
    """Top-1 gaps of the served tokens under the family `fam`'s float32
    reference.

    With `control` ("bf16", "fp8") the reference computed in that precision
    takes the program's place: its top-1 tokens are scored instead of the
    served ones, at the same requests and positions."""
    import jax.numpy as jnp

    w = fam.weights(m, derive(seed, "weights"))
    gaps = []
    for n, toks, top1 in samples:
        rows = jnp.asarray(toks[:n])
        ref = fam.logits(w, m, rows)
        if control:
            chosen = jnp.argmax(fam.logits(w, m, rows, precision=control),
                                axis=-1)
        else:
            chosen = jnp.asarray(top1[:n])
        gaps.append(np.asarray(reference.top1_gaps(ref, chosen)))
    return {**gap_stats(np.concatenate(gaps)),
            "rows": sum(n for n, _, _ in samples)}


# --------------------------------------------------------------------- run
def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile (q in [0, 100]) of `values`."""
    v = sorted(values)
    return v[max(0, math.ceil(q / 100 * len(v)) - 1)]


def window_latencies_ms(ctx: dict) -> list[float]:
    """Completion minus arrival of each window request; inf if dropped."""
    out = []
    for r in ctx["requests"]:
        o = ctx["outcomes"].get(r.req_id)
        done = o is not None and o.completion_s is not None
        out.append((o.completion_s - r.arrival_s) * 1e3 if done else math.inf)
    return out


def run_cell(cell: str, seed: int, seconds: float, trace: bool, t0: float,
             *, conf: dict | None = None, require_chip: bool = True,
             control: str | None = None, events_out: str | None = None,
             log=sys.stderr) -> tuple[dict, dict]:
    """One run of `cell`: its result line, and the context its metrics were
    read from.  `conf` and `require_chip=False` are for tests on the CPU (a
    toy configuration, no look for a chip); `control` puts the reference in
    a lower precision in the program's place for the comparison (`compare`),
    as `control.py` runs it; `events_out` also writes a traced window's
    events (`trace_reduce.read_xplane`) there, the recording tests/data
    keeps."""
    bench = load_benchmark()
    wl = next(w for w in bench["workloads"] if w["name"] == cell)
    conf = conf or load_config(bench, wl["config"])
    fam = families.of(conf)
    mix = traffic.load_mix(wl["traffic"])
    device = check_device(wl["chips"]) if require_chip else describe_device()
    enable_compile_cache()
    import jax

    from repro.api.compilation import count_compiles
    from repro.stream import TraceSource

    m = conf["model"]
    capture = Capture(seed, m["vocab"])
    t_deploy = time.perf_counter()
    session, cfg = deploy(conf, seed, capture)
    t_warm = time.perf_counter()
    layers = stage_layers(session, conf, cfg.name, m["n_layers"])
    slo_s = conf["slo_ms"] / 1e3
    rate = mix["load"] * conf["knee_rps"]
    rng = np.random.default_rng(derive(seed, "arrivals"))
    warm = requests(traffic.arrivals(mix, rate, WARMUP_S, rng), 0.0, 0,
                    cfg.name, slo_s)
    session.serve(TraceSource(warm))
    # every power-of-two bucket up to the plan's batch, as the program warms
    capture.warm([2 ** i for i in range(bucket(conf["plan"]["batch"])
                                        .bit_length())],
                 conf["seq_len"], m["padded_vocab"])
    tel = session.telemetry
    offset = math.ceil(tel.horizon_s) + 1.0
    window = requests(traffic.arrivals(mix, rate, seconds, rng), offset,
                      len(warm), cfg.name, slo_s)
    n_disp, n_qd = len(tel.dispatches), len(tel.queue_delay_s)
    before = feedback_state(session)
    if hasattr(session.dataplane.fb, "reset_peaks"):
        session.dataplane.fb.reset_peaks()
    counters = feedback_counters(session)
    setup_s = time.perf_counter() - t0

    logdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(logdir, profiler_options=opts)
    capture.active = True
    with count_compiles() as compiles:
        t1 = time.perf_counter()
        session.serve(TraceSource(window))
        wall_s = time.perf_counter() - t1
    capture.active = False
    if trace:
        jax.profiler.stop_trace()
    if compiles.n:
        raise RuntimeError(f"{compiles.n} compilation(s) inside the window")

    ids = {r.req_id for r in window}
    outcomes = {o.req_id: o for o in tel.outcomes if o.req_id in ids}
    dispatches = tel.dispatches[n_disp:]
    done = [o for o in outcomes.values() if o.completion_s is not None]
    print(f"[feedback] before window {before}; after "
          f"{feedback_state(session)}", file=log)
    print(f"[window] requests={len(window)} rate_rps={rate:.3f} "
          f"completed={len(done)} on_time={sum(o.ok for o in done)} "
          f"dispatches={len(dispatches)} wall_s={wall_s:.3f} "
          f"setup_s={setup_s:.3f} (start {t_deploy - t0:.3f}, deploy "
          f"{t_warm - t_deploy:.3f}, warm-up {t0 + setup_s - t_warm:.3f})",
          file=log)
    after = feedback_counters(session)
    stats = jax.devices()[0].memory_stats() or {}
    ctx = {
        "config": conf, "family": fam, "seconds": seconds,
        "chips": wl["chips"],
        "wall_s": wall_s, "setup_s": setup_s, "requests": window,
        "outcomes": outcomes, "dispatches": dispatches,
        "queue_delay_s": tel.queue_delay_s[n_qd:],
        "plan_batch": conf["plan"]["batch"], "stage_layers": layers,
        "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0)),
        "peak": costs.peaks(device["kind"]) if require_chip else None,
        "feedback": (window_feedback(counters, after)
                     if after is not None else None),
        "trace": None, "spans": None,
    }
    samples = capture.samples()
    del session, capture, tel
    gc.collect()
    if trace:
        try:
            events = trace_reduce.read_xplane(logdir)
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
        ctx["trace"] = trace_reduce.summarize(events, wall_s)
        ctx["spans"] = span_reduce.reduce(events, wall_s)
        if events_out:
            Path(events_out).parent.mkdir(parents=True, exist_ok=True)
            Path(events_out).write_text(json.dumps({"window_s": wall_s,
                                                    **events}))
        del events
        print(spans_line(ctx), file=log)

    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for spec in cell_metrics(bench, kind, cell):
        value = read_metric(spec["name"], ctx)
        if value is None:
            print(f"[metric] {spec['name']}: nothing to read", file=log)
            continue
        if not math.isfinite(value):
            raise RuntimeError(f"{spec['name']} = {value}: more window "
                               "requests were dropped than its percentile "
                               "allows")
        metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}

    got = compare(samples, fam, m, seed, control) if samples else {"rows": 0}
    print(f"[check] compared {got['rows']} served rows of "
          f"{len(samples)} batches: {json.dumps(got)}", file=log)
    limit = conf["check"][CHECKED]["limit"]
    checks = {CHECKED: {"value": got.get(CHECKED), "limit": limit}}
    correct = got["rows"] >= 1 and got[CHECKED] <= limit
    device = {**device, "memory_peak_bytes": ctx["memory_peak_bytes"]}
    result = {"correct": correct, "attempted": len(window),
              "failed": len(ids) - len(outcomes), "metrics": metrics,
              "device": device}
    if ctx["trace"] is not None:
        tr = ctx["trace"]
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    result["checks"] = checks
    for name, c in checks.items():
        print(f"[check] {name} = {c['value']} limit {c['limit']}", file=log)
    return result, ctx
