"""The one traffic generator: arrival times of an open loop, from a mix file.

A mix is a JSON file under `traffic/` that names its arrival `process` and
that process's parameters; the rate is `load` times the configuration's
`knee_rps`, so one mix serves every configuration.  A process offers exactly
round(rate x seconds) arrivals, so a seed moves when requests arrive and
never how many there are.  The one process so far:

* ``poisson``: independent cameras.  The n + 1 gaps between arrivals are
  the exponential distribution's quantiles at (i + 1/2) / (n + 1), scaled
  to fill the window; the seed shuffles their order.  Every seed thus
  offers the same set of gaps, Poisson-shaped, in another order.

Arrival arithmetic is kept here, apart from the program's own generators
(`repro.data.requests`), so that a change to the program cannot change the
offered load.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load_mix(name: str) -> dict:
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


def arrivals(mix: dict, rate_rps: float, seconds: float,
             rng: np.random.Generator) -> np.ndarray:
    """Sorted arrival times in [0, seconds) for `mix` at `rate_rps`."""
    if mix["process"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['process']!r}")
    n = int(round(rate_rps * seconds))
    q = (np.arange(n + 1) + 0.5) / (n + 1)
    gaps = rng.permutation(-np.log1p(-q))
    return np.sort(np.cumsum(gaps)[:n] * (seconds / gaps.sum()))
