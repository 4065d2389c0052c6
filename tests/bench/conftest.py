import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

FIXTURES = Path(__file__).resolve().parent / "fixtures"


@pytest.fixture(scope="module")
def fixture_root(tmp_path_factory):
    """A checkout-like root: the benchmark's ``bench/`` plus the fixture's
    configuration, mix, cells and metric, under the fixture's
    ``BENCHMARK.json``.  No code names any of the fixture's files."""
    root = tmp_path_factory.mktemp("bench_root")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for sub in ("configs", "traffic", "cells", "metrics"):
        for f in (FIXTURES / sub).iterdir():
            shutil.copy(f, root / "bench" / sub / f.name)
    shutil.copy(FIXTURES / "BENCHMARK.json", root / "BENCHMARK.json")
    return root
