"""Operation and byte counts against the hand counts."""
import json

import pytest

from bench import costs
from bench.spec import ROOT

CFG = {name: json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                        .read_text())
       for name in ("dlrm-recmg", "dlrm-mlperf-criteo1tb")}


@pytest.mark.parametrize("name,macs,top_in", [
    ("dlrm-recmg", 424_554_752, 366_924),
    ("dlrm-mlperf-criteo1tb", 2_410_112, 479),
])
def test_macs_and_top_inputs(name, macs, top_in):
    cfg = CFG[name]
    assert costs.top_inputs(cfg) == top_in
    assert costs.macs_per_query(cfg) == macs
    assert costs.flops_per_query(cfg) == 2 * macs


def test_forward_and_gather_bytes():
    cfg = CFG["dlrm-recmg"]
    w = ((13 * 512 + 512) + (512 * 256 + 256) + (256 * 128 + 128)
         + (366_924 * 1024 + 1024) + (1024 * 1024 + 1024)
         + (1024 * 512 + 512) + (512 * 256 + 256) + (256 * 1 + 1)) * 2
    assert costs.weight_bytes(cfg) == w
    assert costs.forward_bytes(cfg, 8) == w + 8 * (856 * 128 + 13 + 1) * 4
    assert costs.gather_bytes([100, 50], cfg) == 150 * 128 * 4 * 2
    peak = {"hbm_bytes_per_s": 819e9, "flops_bf16": 197e12}
    # dlrm-recmg's forward is bound by its 751 MB of weights, not by its
    # 6.8 GFLOP at 8 queries.
    assert costs.forward_seconds_bound(cfg, 8, peak) == pytest.approx(
        costs.forward_bytes(cfg, 8) / 819e9)
