"""What ``BENCHMARK.json`` names, found by name in files of their own.

A cell (``workloads`` entry) names a configuration and a traffic mix; the
harness finds the rest by name, so adding any of them needs new files and
entries only, never an edit:

* ``<bench>/configs/<config>.json`` -- the deployment as served: sizes,
  the fast tier's share and the policy that manages it (``"policy"``:
  ``recmg`` or ``lru``), with the plain reference module it names
  (``"reference"``) beside it;
* ``<bench>/traffic/<traffic>.json`` -- the mix's parameters;
* ``<bench>/cells/<cell>.json`` -- how the cell serves: queries per batch, warm-up, trace room, profiling and checking sizes;
* ``<bench>/metrics/<metric>.py`` -- one reader per per-layer metric, a
  ``read(ctx)`` that returns the number or None when it finds nothing;
* ``<bench>/peaks.json`` -- the chip's peaks, keyed by ``device_kind``.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: str
    params: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: Path


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def load_cell(name: str, root: Path = ROOT,
              bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell ``name`` with its configuration, cell parameters and the
    metrics it reports; ``KeyError`` for a cell the benchmark lacks."""
    bench = load_benchmark(root)
    wl = {w["name"]: w for w in bench["workloads"]}
    if name not in wl:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(wl)})")
    w = wl[name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    entry = cfgs[w["config"]]
    config = json.loads((Path(root) / entry["file"]).read_text())
    params = json.loads((Path(bench_dir) / "cells" / f"{name}.json")
                        .read_text())
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        traffic=w["traffic"], params=params,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        bench_dir=Path(bench_dir))


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_module(cell: Cell):
    """The plain reference module the cell's configuration names."""
    ref = cell.config["reference"]
    return load_module(cell.bench_dir / "configs" / f"{ref}.py",
                       f"bench_reference_{ref}")


def metric_reader(name: str, bench_dir: Path = BENCH_DIR
                  ) -> Callable[[object], Optional[float]]:
    """``read(ctx)`` of the per-layer metric ``name``."""
    path = Path(bench_dir) / "metrics" / f"{name}.py"
    return load_module(path, "bench_metric_" + name.replace(".", "_")).read


def peaks(device_kind: str, bench_dir: Path = BENCH_DIR) -> Dict[str, float]:
    """The peaks of ``device_kind``; a kind missing from the table is an
    error, never a default."""
    table = json.loads((Path(bench_dir) / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"(have {sorted(table)})")
    return table[device_kind]


def model_config(config: dict):
    """The program's ``ModelConfig`` for a ``dlrm`` configuration file."""
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


def table_rows(config: dict):
    """Rows of each table as served, a list of ``n_tables`` ints."""
    rows = config["rows_per_table"]
    if isinstance(rows, list):
        if len(rows) != int(config["n_tables"]):
            raise ValueError(f"{config['name']}: {len(rows)} row counts for "
                             f"{config['n_tables']} tables")
        return [int(r) for r in rows]
    return [int(rows)] * int(config["n_tables"])
