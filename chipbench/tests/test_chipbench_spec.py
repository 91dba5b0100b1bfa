"""``BENCHMARK.json`` and the files it names agree: every cell, mix,
configuration and metric has its file, every name keeps to the allowed
characters, and the configuration files hold what the program runs."""
import json
import re

import pytest

from chipbench import bench, harness

B = bench.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_names_units_and_lines():
    names = [c["name"] for c in B["configs"]] + [
        w["name"] for w in B["workloads"]] + [
        m["name"] for m in B["end_to_end"] + B["per_layer"]]
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in B["workloads"]]:
        assert NAME.match(n), n
    for m in B["end_to_end"] + B["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    texts = [x["why"] for x in B["configs"] + B["workloads"]] + [
        m["layer"] for m in B["per_layer"]] + [c["source"]
                                             for c in B["configs"]]
    for t in texts:
        assert 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t
    assert len(json.dumps(B)) < 64 * 1024


@pytest.mark.parametrize("w", B["workloads"], ids=lambda w: w["name"])
def test_every_cell_resolves(w):
    spec = bench.workload_spec(w["name"], B)
    assert spec["cell"]["rate_rps"] > 0
    assert bench.generator(spec["traffic"]).schedule
    assert bench.reference(spec["config"]).layer
    assert bench.counts(spec["config"]).decode_iteration
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert bench.metric_reader(m["name"]).read
    assert w["chips"] == 1


@pytest.mark.parametrize("c", B["configs"], ids=lambda c: c["name"])
def test_configuration_file(c):
    config = bench.load_json(bench.ROOT / c["file"])
    assert config["name"] == c["name"] and config["source"] == c["source"]
    assert config["reduced"] == c["reduced"]
    mcfg = harness.model_config(config)
    assert mcfg.n_layers == config["model"]["n_layers"]
    assert config["check"]["logit_gap_max"] > 0


def test_per_layer_moves_an_end_to_end_metric():
    e2e = {m["name"] for m in B["end_to_end"]}
    cells = {w["name"] for w in B["workloads"]}
    for m in B["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
    assert "setup_s" in e2e and all(m["bound"] <= 0.25
                                    for m in B["end_to_end"])
