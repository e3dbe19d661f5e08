#!/usr/bin/env python3
"""Run one cell of the chip benchmark once; print one JSON result line.

    python3 benchmarks/chip/run.py --workload stablelm-3b.steady \\
        --seed 2147483659 --seconds 20 --trace 0

Run from the root of a checkout, on a machine whose JAX sees the TPU chips
the cell asks for (BENCHMARK.json); anywhere else it exits 3 and prints no
result.  `--trace 1` runs the window under the JAX profiler and reports the
cell's per-layer metrics instead of its end-to-end ones; with
`--events-out <file>` it also writes the window's trace events there (the
recording `tests/data/spans_v5e.json` was cut from).  The checks that decide
`correct` are the last lines on stderr and the last key of the line.
"""

import time

T0 = time.perf_counter()  # set-up time counts from here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--events-out", default=None)
    args = ap.parse_args()

    import harness

    try:
        result, _ = harness.run_cell(args.workload, args.seed, args.seconds,
                                     bool(args.trace), T0,
                                     events_out=args.events_out)
    except harness.NoChip as exc:
        print(f"no result: {exc}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
