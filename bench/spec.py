"""What ``BENCHMARK.json`` names, found by name in files of their own.

A cell (``workloads`` entry) names a configuration and a traffic mix; the
harness finds the rest by name, so adding any of them needs new files and
entries only, never an edit:

* ``<bench>/configs/<config>.json`` -- the deployment as served: sizes,
  the fast tier's share and the policy that manages it (``"policy"``:
  ``recmg`` or ``lru``), with the plain reference module it names
  (``"reference"``) beside it, and the module of its operation and byte
  counts if it names one (``"costs"``; else ``bench/costs.py``).  Every key
  that names a field of the program's ``ModelConfig`` reaches the program;
  ``rows_per_table`` and ``multi_hot`` are one int for every table or a
  list of one per table;
* ``<bench>/traffic/<traffic>.json`` -- the mix's parameters;
* ``<bench>/cells/<cell>.json`` -- how the cell serves: queries per batch, warm-up, trace room, profiling and checking sizes;
* ``<bench>/metrics/<metric>.py`` -- one reader per per-layer metric, a
  ``read(ctx)`` that returns the number or None when it finds nothing;
* ``<bench>/peaks.json`` -- the chip's peaks, keyed by ``device_kind``.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, fields
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


def cost_module(cell: Cell):
    """The operation and byte counts the cell's configuration names
    (``"costs"``, a module in ``<bench>/configs/``), by default
    ``bench/costs.py``; the per-layer readers get it as ``ctx.costs``."""
    name = cell.config.get("costs")
    if name is None:
        from bench import costs

        return costs
    return load_module(cell.bench_dir / "configs" / f"{name}.py",
                       f"bench_costs_{name}")


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
    """The program's ``ModelConfig`` for a ``dlrm`` configuration file:
    every key of the file that names a ``ModelConfig`` field, lists as
    tuples, with ``family="dlrm"`` and ``rows_per_table`` the largest
    table's rows.  Keys that name no field are the harness's own."""
    from repro.configs.base import ModelConfig

    names = {f.name for f in fields(ModelConfig)}
    kw = {k: tuple(v) if isinstance(v, list) else v
          for k, v in config.items() if k in names}
    kw.update(family="dlrm", rows_per_table=max(table_rows(config)))
    return ModelConfig(**kw)


def _per_table(config: dict, key: str):
    """``config[key]`` as a list of ``n_tables`` ints: one int for every
    table, or a list of exactly one per table."""
    v, n = config[key], int(config["n_tables"])
    if isinstance(v, list):
        if len(v) != n:
            raise ValueError(f"{config['name']}: {len(v)} values of {key} "
                             f"for {n} tables")
        return [int(x) for x in v]
    return [int(v)] * n


def table_rows(config: dict):
    """Rows of each table as served, a list of ``n_tables`` ints."""
    return _per_table(config, "rows_per_table")


def pooling(config: dict):
    """Ids each table pools a query (``multi_hot``), a list of ``n_tables``
    ints."""
    return _per_table(config, "multi_hot")


def ids_per_query(config: dict) -> int:
    """Ids one query looks up: the sum of every table's pooling."""
    return sum(pooling(config))
