#!/usr/bin/env python
"""Perf regression gate: compare ``runs/bench_results.json`` against the
checked-in baseline (``scripts/bench_baseline.json``).

Two metrics guard the serving hot path:

* ``batched_lookup_rows_per_s`` (bench ``tentpole``) — absolute batched
  lookup throughput; a floor metric (machine-dependent, so the baseline
  is deliberately conservative and the tolerance generous).
* ``recmg_lru_p50_ratio`` (bench ``fig16``) — measured p50 of a batch's
  lookup, pooling and forward steps (its latency without the flush of the
  previous batch's model outputs) under the recmg policy relative to
  LRU; a ceiling metric (machine-
  independent: both sides run on the same box, so this is the true guard
  against the ML policy's bookkeeping creeping back onto the hot path).

Two more guard the workload-scenario matrix (bench ``scenario``; both
counter-derived, hence machine-independent):

* ``recmg_lru_on_demand_ratio_worst`` — worst-case recmg/LRU on-demand
  fetch ratio over the paper-target scenarios; a ceiling metric (the ML
  policy must keep fetching less than LRU on the regimes the paper's
  claim covers).
* ``adapt_recovery`` — drift-adaptive recmg's post-switch steady-state
  hit rate relative to pre-switch on the diurnal regime; a floor metric
  (adaptation must keep recovering after a hot-set rotation).

One guards the learned serving path (bench ``learned``; counter-derived):

* ``recmg_vs_voyager_on_demand_ratio`` — worst on-demand fetch ratio of
  the learned dual-model RecMG vs the Voyager-class prefetch-only
  baseline; a ceiling metric with an *absolute cap of 1.0* (the paper's
  §VII-C claim is directional — RecMG must fetch less than Voyager — so
  no tolerance may push the ceiling past parity).

One guards the observability layer (bench ``obs``):

* ``tracing_on_lookup_slowdown`` — batched-lookup throughput with a
  ``SpanTracer`` installed relative to the default ``NullTracer``; a
  ceiling metric (span emission must stay a few percent of the hot
  path; the tracing-*off* cost is already guarded by the two hot-path
  gates above, which run with tracing off).

One guards overload behavior (bench ``overload``; counter-derived,
deterministic on the VirtualClock):

* ``overload_goodput_4x_vs_1x`` — goodput (full-quality served requests
  per modeled second) at 4x offered load relative to 1x, through the
  SLO-aware admission path; a floor metric with an *absolute floor of
  0.7* (graceful degradation means shedding and degraded answers absorb
  the excess — goodput must not collapse as load quadruples).

Two guard the quantized fast tier (bench ``beyond``; counter-derived
fixed-byte-budget cells plus a deterministic fidelity probe):

* ``quantized_hit_rate_gain_at_fixed_bytes`` — worst-case quantized/fp32
  hit-rate ratio over the paper-target scenarios at the same byte
  budget; a floor metric with an *absolute floor of 1.0* (the acceptance
  bar is directional — at fixed bytes the quantized tier must improve
  the hit rate on every paper-target cell, so no tolerance may push the
  floor below parity).
* ``quantized_dequant_max_abs_err`` — max per-row dequantization error
  in units of the acceptance bound ``max|row|/127``; a ceiling metric
  with an *absolute cap of 1.0* (round-half-even sits at ~0.5; 1.0 is
  the hard fidelity bar).

One guards fault tolerance (bench ``failover``; counter-derived,
deterministic on the VirtualClock):

* ``failover_goodput_kill_vs_clean`` — goodput (exact-answer rows per
  modeled second) under a deterministic mid-run shard kill relative to
  the same workload with no faults; a floor metric with an *absolute
  floor of 0.8* (hot-row replication + the degraded contract must keep
  the service exact-or-zero and near full speed through a shard loss).

A metric regresses when it moves more than ``tolerance`` (default 30%)
past its baseline in the bad direction.  Exit 1 on any regression —
wired into the CI bench-smoke lane after the bench_e2e smoke.

    PYTHONPATH=src python scripts/check_bench_regression.py \
        [--results runs/bench_results.json] \
        [--baseline scripts/bench_baseline.json]
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path


def load_rows(path: Path) -> dict:
    rows = json.loads(path.read_text())
    return {(r["bench"], r["name"]): r["value"] for r in rows}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--results", default="runs/bench_results.json")
    ap.add_argument("--baseline",
                    default=str(Path(__file__).parent / "bench_baseline.json"))
    args = ap.parse_args(argv)

    results = load_rows(Path(args.results))
    base = json.loads(Path(args.baseline).read_text())
    tol = float(base.get("tolerance", 0.30))

    failures = []

    def check_floor(key, name, floor=None):
        """Floor metric; ``floor`` is an optional *absolute* bound that
        tightens the tolerance-derived floor (for ratios with a hard
        semantic threshold — e.g. "no congestion collapse" means goodput
        at 4x must stay >= 0.7x of 1x no matter how generous the
        tolerance)."""
        want = base.get(name)
        got = results.get(key)
        if want is None or got is None:
            print(f"SKIP {name}: baseline={want} measured={got}")
            return
        lo = want * (1.0 - tol)
        if floor is not None:
            lo = max(lo, floor)
        status = "OK" if got >= lo else "REGRESSION"
        print(f"{status} {name}: measured {got:g} vs floor {lo:g} "
              f"(baseline {want}, tolerance {tol:.0%})")
        if got < lo:
            failures.append(name)

    def check_ceiling(key, name, cap=None):
        """Ceiling metric; ``cap`` is an optional *absolute* bound that
        tightens the tolerance-derived ceiling (for ratios with a hard
        semantic threshold — e.g. "learned must beat voyager" means the
        ratio must stay < 1.0 no matter how generous the tolerance)."""
        want = base.get(name)
        got = results.get(key)
        if want is None or got is None:
            print(f"SKIP {name}: baseline={want} measured={got}")
            return
        ceil = want * (1.0 + tol)
        if cap is not None:
            ceil = min(ceil, cap)
        status = "OK" if got <= ceil else "REGRESSION"
        print(f"{status} {name}: measured {got:.3f} vs ceiling {ceil:.3f} "
              f"(baseline {want}, tolerance {tol:.0%})")
        if got > ceil:
            failures.append(name)

    check_floor(("tentpole", "batched_lookup_rows_per_s"),
                "batched_lookup_rows_per_s")
    check_ceiling(("fig16", "recmg_lru_p50_ratio"), "recmg_lru_p50_ratio")
    check_ceiling(("scenario", "recmg_lru_on_demand_ratio_worst"),
                  "recmg_lru_on_demand_ratio_worst")
    check_floor(("scenario", "adapt_recovery"), "adapt_recovery")
    check_ceiling(("learned", "recmg_vs_voyager_on_demand_ratio"),
                  "recmg_vs_voyager_on_demand_ratio", cap=1.0)
    check_ceiling(("obs", "tracing_on_lookup_slowdown"),
                  "tracing_on_lookup_slowdown")
    check_floor(("overload", "overload_goodput_4x_vs_1x"),
                "overload_goodput_4x_vs_1x", floor=0.7)
    check_floor(("failover", "failover_goodput_kill_vs_clean"),
                "failover_goodput_kill_vs_clean", floor=0.8)
    check_floor(("beyond", "quantized_hit_rate_gain_at_fixed_bytes"),
                "quantized_hit_rate_gain_at_fixed_bytes", floor=1.0)
    check_ceiling(("beyond", "quantized_dequant_max_abs_err"),
                  "quantized_dequant_max_abs_err", cap=1.0)

    if failures:
        print(f"perf gate FAILED: {', '.join(failures)}", file=sys.stderr)
        return 1
    print("perf gate passed")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
