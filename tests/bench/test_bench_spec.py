"""BENCHMARK.json and the files it names hold together."""
import json
import re
from pathlib import Path

import pytest

from bench import spec
from bench.spec import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
FIXTURES = Path(__file__).resolve().parent / "fixtures"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_name_and_file_resolves():
    names = ([c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    for w in BENCH["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.chips == 1
        assert len(w["why"]) <= 200
        assert cell.config["name"] == w["config"]
        assert cell.config["policy"] in {"recmg", "lru"}
        assert spec.reference_module(cell).logits
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "qps"}
    for m in BENCH["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}


def test_configs_keep_published_widths():
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert cfg["emb_dim"] == 128
        assert cfg["bottom_mlp"] == [512, 256, 128]
        assert cfg["top_mlp"] == [1024, 1024, 512, 256, 1]
        assert len(spec.table_rows(cfg)) == cfg["n_tables"]


@pytest.mark.parametrize("path", [ROOT / "BENCHMARK.json",
                                  FIXTURES / "BENCHMARK.json"])
def test_each_config_and_traffic_pair_once(path):
    """A cell is one configuration under one traffic mix: no pair is given
    twice, and every configuration is served by some cell."""
    bench = json.loads(path.read_text())
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs)), pairs
    assert {c["name"] for c in bench["configs"]} == {c for c, _ in pairs}
