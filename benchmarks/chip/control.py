#!/usr/bin/env python3
"""Runs that must come out not correct: the control, and planted faults.

    python3 benchmarks/chip/control.py --workload stablelm-3b.steady \\
        --seconds 10 --seeds 11 --control fp8
    python3 benchmarks/chip/control.py --workload stablelm-3b.steady \\
        --seconds 10 --seeds 14 --fault row_shifted

Each seed is one whole run of the cell (`harness.run_cell`: deploy, warm-up,
window, comparison), in this process and one after another, with either

* `--control fp8` (or `bf16`): the reference computed in that precision put
  in the program's place for the comparison, at the window's own requests
  and positions; or
* `--fault <name>`: the program's head altered where it produces the
  logits (`FAULTS`), so the served tokens are wrong in the way named.

Prints each run's result line; the readings and the limit are the last
key.  A sound limit lies below every control's and every fault's reading.
Runs on whatever device JAX finds (the chip for the readings in PERF.md);
the benchmark's own runs never run this.
"""

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))

import harness  # noqa: E402

FAULTS = {
    # one request's logits shifted by one vocabulary column
    "row_shifted": lambda y: y.at[0].set(_roll(y[0])),
    # the second half of each batch's rows served the first row's logits
    "rows_dropped": lambda y: y.at[y.shape[0] // 2:].set(y[:1]),
}


def _roll(x):
    import jax.numpy as jnp

    return jnp.roll(x, 1, axis=-1)


def plant(fault: str):
    """Alter the program's head by `FAULTS[fault]`; returns the undo."""
    from repro.models import transformer

    unembed, alter = transformer.unembed, FAULTS[fault]

    def broken(*args, **kwargs):
        return alter(unembed(*args, **kwargs))

    transformer.unembed = broken
    return lambda: setattr(transformer, "unembed", unembed)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    which = ap.add_mutually_exclusive_group(required=True)
    which.add_argument("--control", choices=("bf16", "fp8"))
    which.add_argument("--fault", choices=sorted(FAULTS))
    args = ap.parse_args()
    undo = plant(args.fault) if args.fault else None
    try:
        for seed in args.seeds:
            r = harness.run_cell(args.workload, seed, args.seconds, False,
                                 time.perf_counter(), control=args.control)
            print(json.dumps({"seed": seed, "control": args.control,
                              "fault": args.fault, **r}), flush=True)
    finally:
        if undo:
            undo()
    return 0


if __name__ == "__main__":
    sys.exit(main())
