"""A configuration, a mix, a cell, a cost module and a per-layer metric
that no code names are picked up from their files alone, and a whole run
drives them."""
import json

import numpy as np
import pytest

from bench import costs, run

CELL = "tiny-dlrm.fixture_mix"
OWN_COSTS = "tiny-dlrm.own_costs.fixture_mix"


@pytest.mark.parametrize("traced", [False, True])
def test_fixture_cell_runs_from_its_files(fixture_root, traced):
    res = run.run_cell(CELL, 2**31 + 9, 0.5, traced, root=fixture_root,
                       bench_dir=fixture_root / "bench", require_tpu=False)
    assert res["correct"], res["checks"]
    assert res["checks"]["rows_mismatched"] == {"value": 0, "limit": 0}
    m = res["metrics"]
    if traced:
        assert m["fixture.batches_seen"]["value"] >= 1
        cfg = json.loads((fixture_root / "bench" / "configs" / "tiny-dlrm.json")
                         .read_text())
        assert m["fixture.flops_per_query"]["value"] == \
            costs.flops_per_query(cfg)
        assert m["store.miss_rows_per_query"]["value"] > 0
        assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
        assert len(res["breakdown"]["device_ops"]) <= 10
        assert len(res["breakdown"]["idle_gaps"]) <= 10
    else:
        assert set(m) == {"qps", "setup_s"}
        assert m["qps"]["value"] > 0 and m["setup_s"]["value"] > 0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert np.isfinite(res["checks"]["logit_err"]["value"])


def test_named_costs_and_window_steps_reach_the_readers(fixture_root):
    """The configuration's own cost module is ``ctx.costs``, and the served
    batches' steps, summed over the untraced window, are
    ``ctx.window.steps``: they cover the batches the store's counters do,
    and the step readers agree with them."""
    res = run.run_cell(OWN_COSTS, 2**31 + 27, 0.5, True, root=fixture_root,
                       bench_dir=fixture_root / "bench", require_tpu=False)
    assert res["correct"], res["checks"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["fixture.flops_per_query"] == 12345
    batches, h2d = m["fixture.batches_seen"], m["fixture.h2d_bytes"]
    assert batches >= 1 and h2d > 0
    assert abs(m["fixture.fetch_s_gap"]) < 1e-9
    assert m["serve.h2d_mb_per_batch"] == pytest.approx(h2d / 1e6 / batches)
    assert m["store.overflow_rows_per_batch"] >= 0
