"""Benchmark harness entry point — one bench per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines.  ``--quick`` shrinks sweeps.

  bench_milp        Fig. 14  control-plane scalability (devices/classes/blocks)
  bench_e2e_load    Fig. 6/7/9  max load factor vs NP/DART-r, Poisson+bursty
  bench_utilization Fig. 8   high/low-class temporal utilization
  bench_ablation    Fig. 10  reservation vs reactive data plane
  bench_sensitivity Fig. 13  SLO scale / class ratio / margin sweeps
  bench_sched       §5.4     scheduler hot-path old-vs-new (BENCH_sched.json)

``--full`` additionally runs the paper-scale (HC1-L, 3-model) drift and
oscillation re-planning scenarios in bench_e2e_load.
"""

from __future__ import annotations

import argparse
import inspect
import sys
import time
import traceback

from repro.api.compilation import enable_compile_cache

from . import (
    bench_ablation,
    bench_e2e_load,
    bench_milp,
    bench_sched,
    bench_sensitivity,
    bench_utilization,
)

BENCHES = {
    "milp": bench_milp.main,
    "e2e_load": bench_e2e_load.main,
    "utilization": bench_utilization.main,
    "ablation": bench_ablation.main,
    "sensitivity": bench_sensitivity.main,
    "sched": bench_sched.main,
}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", choices=list(BENCHES), default=None)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--full", action="store_true",
                    help="include paper-scale scenarios (HC1-L 3-model "
                         "drift/oscillation) in benches that support them")
    args = ap.parse_args()

    print("name,us_per_call,derived")
    failures = 0
    for name, fn in BENCHES.items():
        if args.only and name != args.only:
            continue
        kwargs = {"quick": args.quick}
        if "full" in inspect.signature(fn).parameters:
            kwargs["full"] = args.full
        t0 = time.perf_counter()
        try:
            for line in fn(**kwargs):
                print(line, flush=True)
            print(f"bench_{name}_total,{(time.perf_counter()-t0)*1e6:.0f},ok",
                  flush=True)
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"bench_{name}_total,0,FAILED", flush=True)
            traceback.print_exc()
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    enable_compile_cache()
    main()
