import json

import pytest

import costs
import harness


def _conf(name):
    return json.loads((harness.BENCH_DIR / "configs" / f"{name}.json")
                      .read_text())


def test_unknown_device_kind_is_an_error():
    assert costs.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        costs.peaks("cpu")


def _toy():
    return json.loads((harness.BENCH_DIR / "tests" / "data" / "toy.json")
                      .read_text())


@pytest.mark.parametrize("conf", [_conf("stablelm-3b"), _toy()],
                         ids=["stablelm-3b", "toy-gqa"])
def test_request_flops_are_two_flops_per_weight_plus_attention(conf):
    """2 x (layer + head weights) x tokens, plus causal attention; the toy
    has grouped key/value heads, so the count of k and v weights is checked
    too.  The norms' scales are weights but no matrix product."""
    m, seq = conf["model"], conf["seq_len"]
    norms = 2 * m["d_model"]
    weights = (m["n_layers"] * (costs.layer_params(m) - norms)
               + m["d_model"] * m["vocab"])
    attn = m["n_layers"] * 4 * m["n_heads"] * m["head_dim"] * seq * (seq + 1) / 2
    assert costs.request_flops(m, seq) == pytest.approx(
        2 * seq * weights + attn, rel=1e-12)


def test_stablelm_counts_match_the_published_size():
    conf = _conf("stablelm-3b")
    m, seq = conf["model"], conf["seq_len"]
    weights = m["n_layers"] * costs.layer_params(m) + m["d_model"] * m["vocab"]
    assert weights / 1e9 == pytest.approx(2.667, rel=0.01)
    assert costs.request_flops(m, seq) / 1e12 == pytest.approx(1.377, rel=0.01)


def test_stage_costs_add_up_to_the_request():
    conf = _conf("stablelm-3b")
    m, seq = conf["model"], conf["seq_len"]
    parts = [costs.stage_cost(m, seq, 8, tuple(s["layers"]), i == 0,
                              i == len(conf["plan"]["stages"]) - 1)
             for i, s in enumerate(conf["plan"]["stages"])]
    assert sum(f for f, _ in parts) == pytest.approx(
        8 * costs.request_flops(m, seq))
    t, bound = costs.least_time(*parts[1], costs.peaks("TPU v5 lite"))
    assert bound == "flops" and t > 0
