"""`correct` on a toy run on the CPU: a sound run passes; a run whose timed
path is broken fails, and so does a run with the control (the reference in
fp8) in the program's place.  These drive all of a run but the look for a
chip; the cells' limits and readings are set on the chip (PERF.md)."""

import os
import shutil
import subprocess
import sys
import time

import pytest

import control
import harness

SEEDS = (2**31 + 11, 2**31 + 12, 2**31 + 13)


def toy_run(conf, seed=2**31 + 1, control=None):
    return harness.run_cell("stablelm-3b.steady", seed, 2.0, False,
                            time.perf_counter(), conf=conf,
                            require_chip=False, control=control,
                            log=sys.stderr)


def test_sound_run_is_correct_and_names_its_checks(conf):
    r = toy_run(conf)
    assert r["correct"] is True
    check = r["checks"][harness.CHECKED]
    assert list(r)[-1] == "checks"
    assert check["limit"] == conf["check"][harness.CHECKED]["limit"]
    assert check["value"] <= check["limit"]
    assert r["attempted"] == round(conf["knee_rps"] * 0.8 * 2.0)
    assert r["failed"] == 0
    assert set(r["metrics"]) == {"goodput_rps", "latency_p50_ms",
                                 "latency_p95_ms", "chip_ms_per_req",
                                 "setup_s"}


def test_traced_run_reads_its_per_layer_metrics(conf):
    """The traced path end to end on the CPU: the profiler runs, the host
    and plane metrics are read, and the device ones (no TPU plane, no
    peaks) are left out rather than read as 0."""
    r = harness.run_cell("stablelm-3b.steady", 2**31 + 2, 2.0, True,
                         time.perf_counter(), conf=conf, require_chip=False,
                         log=sys.stderr)
    assert r["correct"] is True
    assert set(r["metrics"]) == {"batch_fill", "queue_delay_p95_ms",
                                 "serve_wall_ratio"}
    assert 0 < r["metrics"]["batch_fill"]["value"] <= 1


@pytest.mark.parametrize("fault", sorted(control.FAULTS))
def test_broken_timed_path_is_not_correct(conf, fault):
    undo = control.plant(fault)
    try:
        assert toy_run(conf)["correct"] is False
    finally:
        undo()


@pytest.mark.parametrize("seed", SEEDS)
def test_control_in_the_programs_place_is_not_correct(conf, seed):
    r = toy_run(conf, seed, control="fp8")
    assert r["correct"] is False
    # the step above the control, bf16 products, stays within the limit
    assert toy_run(conf, seed, control="bf16")["correct"] is True


def test_no_chip_no_result(tmp_path):
    """Off a TPU, and in a checkout holding only the benchmark, a run exits
    non-zero and prints no result line."""
    root = harness.ROOT
    shutil.copy(root / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(harness.BENCH_DIR, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".jax_cache", "__pycache__"))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    for cwd in (root, tmp_path):
        p = subprocess.run(
            [sys.executable, "benchmarks/chip/run.py", "--workload",
             "stablelm-3b.steady", "--seed", str(2**31 + 3), "--seconds", "1"],
            cwd=cwd, env=env, capture_output=True, text=True, timeout=300)
        assert p.returncode != 0 and p.stdout.strip() == "", p.stderr[-2000:]
