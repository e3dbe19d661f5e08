"""Each configuration file runs the published widths of its source, except
the keys its `reduced` lists: the program's ModelConfig is built the way a
run builds it (`ModelSpec.reduced` overrides, which shrink every width not
restated and force a head width of 32) and compared with the source, key by key
as the file's family maps them (`PUBLISHED`, and `CONVENTIONS` for a width
the source leaves out)."""

import json

import pytest

import families
import harness

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
    fam = families.of(conf)
    cfg = harness.program_model(conf)
    pub = conf["published"]
    for key, mkey in fam.PUBLISHED.items():
        if key in conf["reduced"]:
            want = conf["reduced"][key]["run"]
        elif key in pub:
            want = pub[key]
        elif key in fam.CONVENTIONS:
            want = fam.CONVENTIONS[key](pub)
        else:
            continue
        assert conf["model"][mkey] == pytest.approx(want), key
        assert getattr(cfg, fam.KEYS[mkey]) == pytest.approx(want), key
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
