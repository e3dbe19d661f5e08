"""The model-family plug-in (`families/`): a configuration's family is
found by name, an unknown one is an error, a file must restate every width
its family names, the dense family's costs, readings and comparison are
those of `costs.py` and `reference.py` to the bit, and a family module kept
elsewhere drives a whole run."""

import json
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

import costs
import families
import harness
import reference
from conftest import DATA

CONFIGS = sorted((harness.BENCH_DIR / "configs").glob("*.json"))
DENSE = families.load("dense")


def _confs():
    return [json.loads(p.read_text()) for p in [*CONFIGS, DATA / "toy.json"]]


def test_a_file_without_a_family_is_dense(conf):
    assert "family" not in conf["model"]
    assert families.of(conf).KEYS == DENSE.KEYS
    assert "dense" in families.known()


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_every_configuration_names_a_known_family(path):
    conf = json.loads(path.read_text())
    assert families.of(conf).check(harness.program_model(conf))


def test_unknown_family_fails_with_its_name(conf):
    conf["model"]["family"] = "no_such_family"
    with pytest.raises(ValueError, match="no_such_family") as err:
        harness.program_model(conf)
    assert "'dense'" in str(err.value)  # the known families are listed


@pytest.mark.parametrize("key", sorted(DENSE.KEYS))
def test_a_file_that_restates_fewer_widths_fails(conf, key):
    del conf["model"][key]
    with pytest.raises(ValueError, match=key):
        harness.program_model(conf)


@pytest.mark.parametrize("conf", _confs(), ids=lambda c: c["name"])
def test_dense_costs_through_the_family_are_the_costs(conf):
    fam, m, seq = families.of(conf), conf["model"], conf["seq_len"]
    assert fam.request_flops(m, seq) == costs.request_flops(m, seq)
    stages = conf["plan"]["stages"]
    for i, s in enumerate(stages):
        for rows in (1, 3, 8):
            args = (m, seq, rows, tuple(s["layers"]), i == 0,
                    i == len(stages) - 1)
            assert fam.stage_cost(*args) == costs.stage_cost(*args)


class _Outcome:
    completion_s = 1.0


class _Dispatch:
    def __init__(self, batch_size):
        self.batch_size = batch_size


def test_mfu_and_stage_roofline_read_the_costs():
    """The two readers, given a run's context, read what `costs.py` gives
    for the same requests and dispatches, to the bit."""
    conf = json.loads((harness.BENCH_DIR / "configs" / "stablelm-3b.json")
                      .read_text())
    m, seq = conf["model"], conf["seq_len"]
    peak = costs.peaks("TPU v5 lite")
    layers = [((0, 5), True, False), ((5, 32), False, True)]
    batches = (1, 3, 8, 5)
    ctx = {"config": conf, "family": families.of(conf), "peak": peak,
           "outcomes": {i: _Outcome() for i in range(17)}, "wall_s": 2.5,
           "chips": 1, "dispatches": [_Dispatch(b) for b in batches],
           "stage_layers": layers,
           "trace": {"stage_s": 1.7, "stage_calls": 8}}
    flops = 17 * costs.request_flops(m, seq)
    assert harness.read_metric("mfu", ctx) == (
        100.0 * flops / (2.5 * 1 * peak["bf16_flops"]))
    least = {"flops": 0.0, "bytes": 0.0}
    for b in batches:
        for lay, emb, unemb in layers:
            t, bound = costs.least_time(
                *costs.stage_cost(m, seq, b, lay, emb, unemb), peak)
            least[bound] += t
    assert harness.read_metric("stage_roofline", ctx) == (
        100.0 * (least["flops"] + least["bytes"]) / 1.7)


@pytest.mark.parametrize("control", [None, "fp8"])
def test_compare_through_the_family_is_the_reference(conf, control):
    m, seq, seed = conf["model"], conf["seq_len"], 2**31 + 21
    rng = np.random.default_rng(5)
    toks = rng.integers(0, m["vocab"], (4, seq), dtype=np.int32)
    w = reference.weights(m, harness.derive(seed, "weights"))
    ref = reference.logits(w, m, jnp.asarray(toks[:3]))
    top1 = np.array(jnp.argmax(reference.logits(w, m, jnp.asarray(toks)),
                               axis=-1))
    top1[0, :5] = (top1[0, :5] + 1) % m["vocab"]  # some gaps above 0
    if control:
        chosen = jnp.argmax(reference.logits(w, m, jnp.asarray(toks[:3]),
                                             precision=control), axis=-1)
    else:
        chosen = jnp.asarray(top1[:3])
    want = harness.gap_stats(np.asarray(reference.top1_gaps(ref, chosen)))
    got = harness.compare([(3, toks, top1)], DENSE, m, seed, control)
    assert got == {**want, "rows": 3}
    assert got["top1_gap"] > 0


def test_a_family_module_elsewhere_drives_a_run(conf, monkeypatch):
    monkeypatch.setattr(families, "__path__", [*families.__path__, str(DATA)])
    conf["model"]["family"] = "dense_twin"
    assert "dense_twin" in families.known()
    try:
        r, ctx = harness.run_cell("stablelm-3b.steady", 2**31 + 22, 2.0,
                                  False, time.perf_counter(), conf=conf,
                                  require_chip=False, log=sys.stderr)
    finally:  # other tests see the package as it was
        sys.modules.pop("families.dense_twin", None)
        if hasattr(families, "dense_twin"):
            del families.dense_twin
    assert ctx["family"].__name__ == "families.dense_twin"
    assert r["correct"] is True and r["checks"][harness.CHECKED]["value"] < 0.1
