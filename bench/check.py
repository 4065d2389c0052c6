"""Whether what the timed path served is correct.

Run after the window has closed, the device peak has been read and the
program's state is freed.  For each kept window batch (a reservoir drawn
from the seed) it compares:

* ``rows_mismatched``: served rows that differ, bit for bit, from the slow
  tier's rows at the batch's ids.  This covers residency, the miss
  transfer, the scatter and the gather.  Limit 0.
* ``logit_err``: the largest gap between a served logit and the plain
  float32 reference, over the largest reference logit of the batches.
  This covers pooling and the dense forward.  Its limit is the
  configuration's ``check.logit_err_limit``, set from the program's
  readings over many seeds and from the lower-precision control's.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def compare(kept, cfg: dict, params, ref, precision: str = "f32") -> Dict:
    """Readings over the kept batches.  ``ref`` is the configuration's
    reference module; ``precision="fp8"`` puts the control's logits in the
    program's place (the rows are then the reference's own)."""
    from bench.spec import table_rows

    n_rows = int(np.sum(table_rows(cfg)))
    sizes = [int(np.asarray(k.ids).size) for k in kept]
    table = ref.slow_tier_rows(
        n_rows, int(cfg["emb_dim"]),
        np.concatenate([np.asarray(k.ids) for k in kept]) if kept else [])
    mismatched, gaps, scales, queries = 0, [], [], 0
    for k, want in zip(kept, np.split(table, np.cumsum(sizes)[:-1])):
        dense = np.asarray(k.dense)
        want_logits = ref.logits(params, dense, ref.pool(want, cfg))
        if precision == "f32":
            got_rows = np.asarray(k.rows)
            if got_rows.shape != want.shape:
                mismatched += int(want.shape[0])
            else:
                bad = got_rows.view(np.uint32) != want.view(np.uint32)
                mismatched += int(np.count_nonzero(bad.any(axis=1)))
            got = np.asarray(k.logits, np.float32)
        else:
            got = ref.logits(params, dense, ref.pool(want, cfg),
                             precision=precision)
        queries += int(want_logits.size)
        if got.shape != want_logits.shape or not np.isfinite(got).all():
            gaps.append(np.inf)
        else:
            gaps.append(float(np.max(np.abs(got - want_logits))))
        scales.append(float(np.max(np.abs(want_logits))))
    err = max(gaps) / max(max(scales), 1e-30) if gaps else np.inf
    return {"rows_mismatched": mismatched, "logit_err": float(err),
            "batches": len(kept), "queries": queries}


def judge(readings: Dict, cfg: dict) -> Dict:
    """``{name: {"value", "limit"}}`` of the compared numbers."""
    return {
        "rows_mismatched": {"value": readings["rows_mismatched"],
                            "limit": 0},
        "logit_err": {"value": readings["logit_err"],
                      "limit": float(cfg["check"]["logit_err_limit"])},
    }


def passed(checks: Dict, readings: Dict) -> bool:
    return readings["batches"] > 0 and all(
        np.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
