"""Each configuration file runs the published widths of its source, except
the keys its `reduced` lists: the program's ModelConfig is built the way a
run builds it (`ModelSpec.reduced` overrides, which shrink every width not
restated and force head_dim 32) and compared with the source."""

import json

import pytest

import harness

# published key -> the program's ModelConfig field (None: not a field)
KEYS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
        "num_attention_heads": "n_heads", "num_key_value_heads": "kv_heads",
        "intermediate_size": "d_ff", "vocab_size": "vocab",
        "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
        "layer_norm_eps": "norm_eps"}

BENCH = harness.load_benchmark()
FILES = sorted((harness.BENCH_DIR / "configs").glob("*.json"))


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_benchmark_entry_matches_its_file(entry):
    conf = json.loads((harness.ROOT / entry["file"]).read_text())
    assert conf["name"] == entry["name"] and conf["source"] == entry["source"]
    assert sorted(conf["reduced"]) == sorted(entry["reduced"])


@pytest.mark.parametrize("path", FILES, ids=lambda p: p.stem)
def test_program_runs_the_published_widths(path):
    conf = json.loads(path.read_text())
    assert conf["name"] == path.stem
    cfg = harness.program_model(conf)
    pub = conf["published"]
    for key, field in KEYS.items():
        if key not in pub:
            continue
        want = conf["reduced"][key]["run"] if key in conf["reduced"] else pub[key]
        assert getattr(cfg, field) == pytest.approx(want), key
    assert cfg.hd == pub["hidden_size"] // pub["num_attention_heads"]
    assert cfg.dtype.__name__ == conf["dtype"]


@pytest.mark.parametrize("path", FILES, ids=lambda p: p.stem)
def test_plan_is_pinned_and_within_the_slo(path):
    conf = json.loads(path.read_text())
    stages = conf["plan"]["stages"]
    assert stages[0]["blocks"][0] == 0
    assert stages[-1]["blocks"][1] == conf["plan"]["n_blocks"]
    assert stages[0]["layers"][0] == 0
    assert stages[-1]["layers"][1] == conf["model"]["n_layers"]
    for a, b in zip(stages, stages[1:]):
        assert a["blocks"][1] == b["blocks"][0]
        assert a["layers"][1] == b["layers"][0]
    assert 0 < sum(s["latency_ms"] for s in stages) <= conf["slo_ms"]
    assert conf["knee_rps"] > 0 and conf["check"][harness.CHECKED]["limit"] > 0


def test_every_cell_names_a_config_and_a_mix():
    names = {c["name"] for c in BENCH["configs"]}
    for wl in BENCH["workloads"]:
        assert wl["config"] in names
        assert (harness.BENCH_DIR / "traffic" / f"{wl['traffic']}.json").exists()
    for kind in ("end_to_end", "per_layer"):
        for m in BENCH[kind]:
            assert (harness.BENCH_DIR / "metrics" / f"{m['name']}.py").exists()
