"""Sandbox tests of the chip benchmark.  They run on the CPU at toy sizes
and are collected only by explicit path (the repo's `testpaths` is
`tests`):

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""

import copy
import json
import sys
from pathlib import Path

import pytest

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
for p in (str(ROOT / "src"), str(BENCH_DIR)):
    if p not in sys.path:
        sys.path.insert(0, p)

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="session")
def toy_conf():
    """A toy stablelm-3b-family configuration (widths cut for the CPU) with
    a two-stage plan like the cells' and a limit set from toy readings."""
    return json.loads((DATA / "toy.json").read_text())


@pytest.fixture
def conf(toy_conf):
    return copy.deepcopy(toy_conf)
