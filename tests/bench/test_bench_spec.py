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


def _parent_model_config(config: dict):
    """``spec.model_config`` as it was before it passed the file's
    ``ModelConfig`` keys through: the oracle for the three files."""
    from repro.configs.base import ModelConfig

    rows = config["rows_per_table"]
    return ModelConfig(
        name=config["name"], family="dlrm",
        n_tables=int(config["n_tables"]),
        rows_per_table=int(max(rows) if isinstance(rows, list) else rows),
        emb_dim=int(config["emb_dim"]), multi_hot=int(config["multi_hot"]),
        dense_features=int(config["dense_features"]),
        bottom_mlp=tuple(config["bottom_mlp"]),
        top_mlp=tuple(config["top_mlp"]),
        param_dtype=config["param_dtype"],
        compute_dtype=config["compute_dtype"],
        source=config["source"])


def _config(path):
    return json.loads(Path(path).read_text())


@pytest.mark.parametrize("name", ["dlrm-recmg", "dlrm-recmg.lru",
                                  "dlrm-mlperf-criteo1tb"])
def test_model_config_is_the_one_built_before(name):
    cfg = _config(spec.BENCH_DIR / "configs" / f"{name}.json")
    assert spec.model_config(cfg) == _parent_model_config(cfg)


def test_per_table_pooling_reaches_the_program():
    cfg = _config(FIXTURES / "configs" / "dlrm-dcnv2-pooling.json")
    mc = spec.model_config(cfg)
    assert mc.multi_hot == tuple(cfg["multi_hot"])
    assert mc.rows_per_table == max(cfg["rows_per_table"]) == 4096
    assert mc.family == "dlrm" and mc.n_tables == 26
    assert mc.bottom_mlp == (512, 256, 128)
    assert spec.pooling(cfg) == cfg["multi_hot"]
    assert spec.ids_per_query(cfg) == 214
    assert not hasattr(mc, "published")


def test_pooling_one_int_or_one_per_table():
    cfg = {"name": "c", "n_tables": 3, "multi_hot": 4}
    assert spec.pooling(cfg) == [4, 4, 4] and spec.ids_per_query(cfg) == 12
    cfg["multi_hot"] = [1, 2, 3]
    assert spec.pooling(cfg) == [1, 2, 3] and spec.ids_per_query(cfg) == 6
    for wrong in ([1, 2], [1, 2, 3, 4]):
        with pytest.raises(ValueError, match="multi_hot"):
            spec.pooling(dict(cfg, multi_hot=wrong))
    with pytest.raises(ValueError, match="rows_per_table"):
        spec.table_rows(dict(cfg, rows_per_table=[5, 6]))


def test_cost_module_is_the_one_the_configuration_names(fixture_root):
    from bench import costs

    b = fixture_root / "bench"
    default = spec.load_cell("tiny-dlrm.fixture_mix.lru", fixture_root, b)
    assert spec.cost_module(default) is costs
    own = spec.load_cell("tiny-dlrm.own_costs.fixture_mix", fixture_root, b)
    mod = spec.cost_module(own)
    assert mod.__file__ == str(b / "configs" / "tiny_costs.py")
    assert mod.flops_per_query(own.config) == mod.FLOPS_PER_QUERY
