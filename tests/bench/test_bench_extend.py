"""A configuration, a mix, a cell and a per-layer metric that no code names
are picked up from their files alone, and a whole run drives them."""
import numpy as np
import pytest

from bench import run

CELL = "tiny-dlrm.fixture_mix"


@pytest.mark.parametrize("traced", [False, True])
def test_fixture_cell_runs_from_its_files(fixture_root, traced):
    res = run.run_cell(CELL, 2**31 + 9, 0.5, traced, root=fixture_root,
                       bench_dir=fixture_root / "bench", require_tpu=False)
    assert res["correct"], res["checks"]
    assert res["checks"]["rows_mismatched"] == {"value": 0, "limit": 0}
    m = res["metrics"]
    if traced:
        assert m["fixture.batches_seen"]["value"] >= 1
        assert m["store.miss_rows_per_query"]["value"] > 0
        assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
        assert len(res["breakdown"]["device_ops"]) <= 10
        assert len(res["breakdown"]["idle_gaps"]) <= 10
    else:
        assert set(m) == {"qps", "setup_s"}
        assert m["qps"]["value"] > 0 and m["setup_s"]["value"] > 0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert np.isfinite(res["checks"]["logit_err"]["value"])
