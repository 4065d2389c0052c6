"""Paper §VII-F: end-to-end DLRM inference on tiered memory (Figs. 16/17),
the linear performance model (Fig. 18) and strategy estimates (Fig. 19)."""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.common import BenchContext
from repro.configs import get_config
from repro.core.cache_sim import make_cache, simulate
from repro.core.perf_model import fit_perf_model
from repro.launch.serve import serve_trace
from repro.models.dlrm import init_dlrm


# The serve loop's steps of a batch's own lookup, pooling and forward: its
# latency without the flush of the previous batch's model outputs.
_HOT_STEPS = ("lookup_s", "pool_s", "forward_s")


def _serving_cfg(ctx):
    import dataclasses

    # CPU-sized DLRM but with enough unique vectors (65K) that the access
    # distribution keeps its production-like skew/reuse structure.
    cfg = dataclasses.replace(
        get_config("dlrm-recmg").reduced(),
        n_tables=16, rows_per_table=4096, multi_hot=4, emb_dim=16,
    )
    from repro.core.trace import TraceGenConfig, generate_trace

    n_acc = 80_000 if ctx.cfg.quick else 160_000
    tr = generate_trace(TraceGenConfig(
        n_tables=cfg.n_tables, rows_per_table=cfg.rows_per_table,
        n_accesses=n_acc, seed=0, drift_every=10**9))
    return cfg, tr


def fig16_17_e2e(ctx: BenchContext):
    cfg, tr = _serving_cfg(ctx)
    params = init_dlrm(jax.random.PRNGKey(0), cfg)
    cap = int(0.18 * tr.unique_count())

    from repro.core.belady import belady_labels
    from repro.core.caching_model import CachingModelConfig, train_caching_model
    from repro.core.features import make_windows
    from repro.core.prefetch_model import (PrefetchModelConfig,
                                           make_prefetch_data,
                                           train_prefetch_model)
    from repro.core.recmg import precompute_outputs

    labels, _, _ = belady_labels(tr.global_id, cap)
    mcfg = CachingModelConfig(n_tables=cfg.n_tables)
    cparams, _ = train_caching_model(make_windows(tr, labels=labels), mcfg,
                                     epochs=ctx.cfg.epochs,
                                     batch_size=ctx.cfg.batch_size,
                                     lr=ctx.cfg.lr)
    pcfg = PrefetchModelConfig(n_tables=cfg.n_tables)
    pparams, _ = train_prefetch_model(make_prefetch_data(tr, stride=10), pcfg,
                                      epochs=ctx.cfg.epochs,
                                      batch_size=ctx.cfg.batch_size,
                                      lr=ctx.cfg.lr)
    out_cm = precompute_outputs(tr, caching=(cparams, mcfg))
    out_full = precompute_outputs(tr, caching=(cparams, mcfg),
                                  prefetch=(pparams, pcfg))
    # Oracle keep-bits: the mechanism's ceiling in serving (what a fully
    # trained caching model converges to — the paper trains 12+ hours).
    import numpy as np

    from repro.core.recmg import RecMGOutputs

    starts = out_cm.chunk_starts
    oracle_bits = np.stack([labels[max(0, int(s) - 15): int(s)]
                            for s in starts]).astype(bool)
    out_oracle = RecMGOutputs(starts, oracle_bits, None)

    results, hot_ms = {}, {}
    for policy, outputs in (("lru", None), ("cm", out_cm),
                            ("recmg", out_full),
                            ("recmg-oracle", out_oracle)):
        pol = "recmg" if policy.startswith(("cm", "recmg")) else "lru"
        hot = hot_ms[policy] = []
        res = serve_trace(
            cfg, params, tr, cap, pol, outputs, batch_queries=32,
            probe=lambda sb, hot=hot: hot.append(
                1e3 * sum(sb.steps[k] for k in _HOT_STEPS)))
        results[policy] = res
        ctx.emit("fig16", f"{policy}_hit_rate", res["hit_rate"])
        ctx.emit("fig16", f"{policy}_fetch_ms",
                 round(res["modeled_fetch_ms_per_batch"], 3),
                 "modeled slow-tier on-demand per batch")
        ctx.emit("fig16", f"{policy}_e2e_ms", round(res["modeled_e2e_ms"], 3),
                 "compute + slow-tier model (paper §VII-F decomposition)")
        # Tail latency trajectory (measured per-batch wall time).
        ctx.emit_percentiles("fig16", policy, res)
        # Full per-policy counter space into the artifact (reconciled).
        ctx.emit_snapshot("fig16", policy, res["metrics"])
    lru_t = results["lru"]["modeled_e2e_ms"]
    for name in ("cm", "recmg", "recmg-oracle"):
        red = 1 - results[name]["modeled_e2e_ms"] / max(lru_t, 1e-9)
        ctx.emit("fig16", f"{name}_time_reduction", round(red, 4),
                 "paper: 31% avg / 43% max (production traces, 12h training)")
    # The ML policy's bookkeeping must not slow the serving hot path: the
    # p50 of recmg's lookup + pooling + forward steps vs lru's is the
    # perf-gate metric (scripts/check_bench_regression.py); the
    # array-backed priority engine brought it from ~4.5x to ~1.1x.  The
    # batch latency also holds the flush of the previous batch's model
    # outputs, which is the policy's work by design: its ratio is reported
    # beside the gate, ungated.
    ratio = (float(np.median(hot_ms["recmg"]))
             / max(float(np.median(hot_ms["lru"])), 1e-9))
    ctx.emit("fig16", "recmg_lru_p50_ratio", round(ratio, 3),
             "p50 of lookup+pool+forward; acceptance: <= 1.5x "
             "(was ~4.5x with the heap)")
    ratio = (results["recmg"]["p50_batch_ms"]
             / max(results["lru"]["p50_batch_ms"], 1e-9))
    ctx.emit("fig16", "recmg_lru_p50_batch_ratio", round(ratio, 3),
             "p50 batch latency, the model-output flush included; ungated")
    return cfg, tr, cap, results, out_full


def fig18_19_perf_model(ctx: BenchContext):
    """Fit latency = f(hit rate) from controlled runs; estimate strategies."""
    cfg, tr = _serving_cfg(ctx)
    params = init_dlrm(jax.random.PRNGKey(0), cfg)
    keys = tr.global_id

    # Controlled hit rates via buffer sizes (the paper re-orders traces; a
    # capacity sweep spans the same hit-rate axis).
    hrs, lats = [], []
    for frac in (0.01, 0.03, 0.08, 0.15, 0.3, 0.6):
        cap = max(16, int(frac * tr.unique_count()))
        res = serve_trace(cfg, params, tr.slice(0, 40_000), cap, "lru", None,
                          batch_queries=16)
        hrs.append(res["hit_rate"])
        lats.append(res["modeled_e2e_ms"])
    model = fit_perf_model(hrs, lats)
    ctx.emit("fig18", "slope_ms_per_hitrate", round(model.slope, 3))
    ctx.emit("fig18", "intercept_ms", round(model.intercept, 3))
    ctx.emit("fig18", "rmse_ms", round(model.rmse, 4),
             f"rel={model.rmse / max(np.mean(lats), 1e-9):.3f} "
             "(paper: <=1.7%)")

    # Fig. 19: estimated latency per strategy from simulated hit rates.
    cap = max(16, int(0.15 * tr.unique_count()))
    sims = {}
    for name in ("lru_32w", "srrip", "drrip", "hawkeye", "mockingjay"):
        sims[name] = simulate(keys, make_cache(name, cap)).hit_rate
    from repro.core.prefetchers import make_prefetcher

    sims["bop+lru"] = simulate(keys, make_cache("lru_32w", cap),
                               make_prefetcher("bop")).hit_rate
    lru_est = float(model.predict(sims["lru_32w"]))
    for name, hr in sims.items():
        est = float(model.predict(hr))
        ctx.emit("fig19", f"{name}_est_ms", round(est, 3),
                 f"vs lru: {1 - est / max(lru_est, 1e-9):+.3f}")
    return model


def quantized_buffer_beyond_paper(ctx: BenchContext):
    """Beyond-paper: quantized fast tier (SDM's capacity/precision trade,
    [90] in the paper) at a FIXED byte budget — a cell per paper-target
    scenario served end-to-end through the harness twice, fp32 rows vs
    int8 rows + per-row scales in the *same* bytes (the quantized arm
    holds ~2.7x the rows at D=8).  Two gated rows:

    * ``quantized_hit_rate_gain_at_fixed_bytes`` — worst-case quantized/
      fp32 hit-rate ratio over the paper-target cells; a floor metric
      with an absolute floor of 1.0 (the acceptance bar: quantization
      must improve the hit rate on EVERY paper-target cell).
    * ``quantized_dequant_max_abs_err`` — per-row dequantization error in
      units of the acceptance bound ``max|row|/127``; a ceiling metric
      with an absolute cap of 1.0 (round-half-even lands at ~0.5).
    """
    import numpy as np

    from repro.core.tiered import TieredEmbeddingStore, fast_row_bytes
    from repro.workloads import (PAPER_TARGET_SCENARIOS, replay_scenario,
                                 scenario)
    from repro.workloads.spec import make_trace

    n_acc = 16_384 if ctx.cfg.quick else 49_152
    scale = dict(n_tables=8, rows_per_table=2048, n_accesses=n_acc, seed=0)
    emb_dim = 8  # harness default; quantized row = 12 B vs 32 B fp32
    gains, cap_ratios = [], []
    for name in sorted(PAPER_TARGET_SCENARIOS):
        spec = scenario(name, **scale)
        # The budget a 12% fp32 buffer would spend — both arms get it.
        budget = (int(0.12 * make_trace(spec).unique_count())
                  * fast_row_bytes(emb_dim, np.float32, False))
        res_f = replay_scenario(spec, policy="lru", batch=512,
                                byte_budget=budget)
        res_q = replay_scenario(spec, policy="lru", batch=512,
                                byte_budget=budget, quantize=True)
        gains.append(res_q["hit_rate"] / max(res_f["hit_rate"], 1e-9))
        cap_ratios.append(res_q["capacity"] / max(res_f["capacity"], 1))
        ctx.emit("beyond", f"{name}_fp32_hit_rate_at_fixed_bytes",
                 round(res_f["hit_rate"], 4),
                 f"{res_f['capacity']} rows in {budget} B, "
                 f"p50 {res_f['p50_batch_ms']:.2f}ms")
        ctx.emit("beyond", f"{name}_int8_hit_rate_at_fixed_bytes",
                 round(res_q["hit_rate"], 4),
                 f"{res_q['capacity']} rows (same bytes), "
                 f"p50 {res_q['p50_batch_ms']:.2f}ms")
    ctx.emit("beyond", "quantized_capacity_ratio_at_fixed_bytes",
             round(min(cap_ratios), 3),
             "acceptance: >= 2x resident rows at the same byte budget")
    ctx.emit("beyond", "quantized_hit_rate_gain_at_fixed_bytes",
             round(min(gains), 4),
             f"worst over {sorted(PAPER_TARGET_SCENARIOS)}; perf-gate "
             "floor (abs floor 1.0: must improve on every cell)")
    # Numerical fidelity of the quantized tier, normalized per row by the
    # acceptance bound max|row|/127 (so the gate is scale-free).
    host = np.random.default_rng(0).normal(
        size=(1000, emb_dim)).astype(np.float32)
    st = TieredEmbeddingStore(host, 64, quantize=True)
    ids = np.arange(64)
    out = np.asarray(st.lookup(ids))
    amax = np.abs(host[ids]).max(axis=1)
    err = np.abs(out - host[ids]).max(axis=1)
    norm = float((err / (amax / 127.0 + 1e-9)).max())
    ctx.emit("beyond", "quantized_dequant_max_abs_err", round(norm, 4),
             "max per-row |dequant - host| / (max|row|/127); perf-gate "
             "ceiling (abs cap 1.0)")


def lookup_throughput(ctx: BenchContext):
    """Tentpole microbench: batched array-backed store vs. the per-key seed
    reference (kept in ``repro.core.tiered_reference``) on identical
    Zipf-skewed batches, LRU policy.  Acceptance bar: >= 3x at batch >=
    1024."""
    import time

    import numpy as np

    from repro.core.tiered import TieredEmbeddingStore
    from repro.core.tiered_reference import ReferenceTieredStore

    rng = np.random.default_rng(0)
    n_rows, d, batch = 65_536, 64, 2048
    host = rng.normal(size=(n_rows, d)).astype(np.float32)
    cap = n_rows // 8
    ranks = np.minimum(rng.zipf(1.1, size=64 * batch), n_rows) - 1
    ids = rng.permutation(n_rows)[ranks].astype(np.int64)
    n_batches = 16 if ctx.cfg.quick else 32

    def run_store(store, n_b):
        for b in range(30):  # warm the buffer + compile caches
            store.lookup(ids[b * batch: (b + 1) * batch])
        t0 = time.perf_counter()
        for b in range(n_b):
            lo = (b % 30) * batch
            store.lookup(ids[lo: lo + batch])
        return n_b * batch / (time.perf_counter() - t0)

    fast = run_store(TieredEmbeddingStore(host, cap, policy="lru",
                                          warmup_batch=batch),
                     n_batches)
    slow = run_store(ReferenceTieredStore(host, cap, policy="lru"),
                     max(4, n_batches // 8))
    ctx.emit("tentpole", "batched_lookup_rows_per_s", round(fast),
             f"batch={batch} cap={cap} lru")
    ctx.emit("tentpole", "reference_lookup_rows_per_s", round(slow),
             "per-key seed implementation")
    ctx.emit("tentpole", "lookup_speedup_vs_reference",
             round(fast / max(slow, 1e-9), 2), "acceptance bar: >= 3x")
    return fast / max(slow, 1e-9)


def tracing_overhead(ctx: BenchContext):
    """Observability cost rows: the batched-lookup microbench with the
    default ``NullTracer`` (tracing off — the mode every perf gate runs
    in, so the throughput/latency gates themselves enforce near-zero
    disabled cost) and again with a ``SpanTracer`` installed.  The
    tracing-on slowdown is itself a gated ceiling row
    (``tracing_on_lookup_slowdown``): span emission must stay a few
    percent of the lookup hot path, not a profiling mode you can't
    afford in production."""
    import time

    import numpy as np

    from repro.core.tiered import TieredEmbeddingStore
    from repro.obs.tracing import SpanTracer, install_tracer

    rng = np.random.default_rng(1)
    n_rows, d, batch = 65_536, 64, 2048
    host = rng.normal(size=(n_rows, d)).astype(np.float32)
    cap = n_rows // 8
    ranks = np.minimum(rng.zipf(1.1, size=64 * batch), n_rows) - 1
    ids = rng.permutation(n_rows)[ranks].astype(np.int64)
    n_batches = 16 if ctx.cfg.quick else 32

    def run(n_b):
        store = TieredEmbeddingStore(host, cap, policy="lru",
                                     warmup_batch=batch)
        for b in range(30):
            store.lookup(ids[b * batch: (b + 1) * batch])
        t0 = time.perf_counter()
        for b in range(n_b):
            lo = (b % 30) * batch
            store.lookup(ids[lo: lo + batch])
        return n_b * batch / (time.perf_counter() - t0)

    off = run(n_batches)
    tracer = SpanTracer(ring_batches=8)
    install_tracer(tracer)
    try:
        on = run(n_batches)
    finally:
        install_tracer(None)
    ctx.emit("obs", "tracing_off_rows_per_s", round(off),
             "NullTracer (default): the gated perf numbers run like this")
    ctx.emit("obs", "tracing_on_rows_per_s", round(on),
             f"SpanTracer installed ({len(tracer.events)} events)")
    ctx.emit("obs", "tracing_on_lookup_slowdown",
             round(off / max(on, 1e-9), 3),
             "perf-gate ceiling: span emission stays off the hot path")


def multi_table_facade(ctx: BenchContext):
    """Per-table facade vs. monolithic store at the same total row budget
    (per-table isolation: a hot table cannot starve the rest)."""
    cfg, tr = _serving_cfg(ctx)
    params = init_dlrm(jax.random.PRNGKey(0), cfg)
    cap = int(0.18 * tr.unique_count())
    short = tr.slice(0, 40_000)
    mono = serve_trace(cfg, params, short, cap, "lru", None,
                       batch_queries=32)
    multi = serve_trace(cfg, params, short, cap, "lru", None,
                        batch_queries=32, multi_table=True)
    ctx.emit("facade", "mono_hit_rate", mono["hit_rate"])
    ctx.emit("facade", "multi_table_hit_rate", multi["hit_rate"],
             f"{cfg.n_tables} per-table stores, shared {cap}-row budget")
    ctx.emit("facade", "multi_table_fetch_ms",
             round(multi["modeled_fetch_ms_per_batch"], 3),
             f"mono: {mono['modeled_fetch_ms_per_batch']:.3f}")


def runtime_pipeline(ctx: BenchContext, cfg, tr, cap, outputs, sync_res):
    """Pipelined serving runtime vs. the synchronous path (same trace,
    capacity and predictions): the pipelined run must reproduce the
    synchronous hit/miss/eviction counters exactly while moving on-demand
    fetch time off the modeled critical path (acceptance: >= 30% lower
    stall on the recmg policy)."""
    import jax

    from repro.models.dlrm import init_dlrm

    params = init_dlrm(jax.random.PRNGKey(0), cfg)
    # One cost model for both pipeline stages.  The modeled device time
    # per batch is the synchronous run's own mean per-batch compute,
    # floored at the modeled per-batch slow-tier fetch: this container's
    # CPU MLP runs in ~1ms (now that serve_trace warms the forward's XLA
    # compile out of the measured batches) while the modeled fetch is
    # ~12ms — mixing measured microsecond CPU compute with the modeled
    # 10us/row slow tier would understate what an accelerator-rate
    # forward can hide (the paper's Fig. 6 regime: fetch overlapped under
    # a forward of comparable length).
    compute_ms = max(sync_res["compute_ms"],
                     sync_res["modeled_fetch_ms_per_batch"])
    pipe = serve_trace(cfg, params, tr, cap, "recmg", outputs,
                       batch_queries=32, async_prefetch=True,
                       pipeline_depth=2,
                       compute_us=compute_ms * 1e3)
    equal = all(pipe[k] == sync_res[k] for k in
                ("hit_rate", "prefetch_hits", "on_demand_rows", "lookups",
                 "evictions", "batches"))
    rt = pipe["runtime"]
    sync_stall = sync_res["on_demand_stall_ms"]
    red = 1 - pipe["on_demand_stall_ms"] / max(sync_stall, 1e-9)
    ctx.emit("runtime", "counters_equal_sync_vs_pipelined", equal,
             "determinism contract: identical hit/miss/eviction counters")
    ctx.emit("runtime", "sync_fetch_stall_ms", round(sync_stall, 3),
             "synchronous path: every on-demand fetch on the critical path")
    ctx.emit("runtime", "pipelined_fetch_stall_ms",
             round(pipe["on_demand_stall_ms"], 3),
             "after overlapping batch k's fetch with batch k-1's forward")
    ctx.emit("runtime", "stall_reduction", round(red, 4),
             "acceptance bar: >= 0.30 (recmg policy, depth 2)")
    ctx.emit("runtime", "hidden_ms", rt["hidden_ms"],
             "fetch time overlapped with compute")
    ctx.emit("runtime", "pf_timeliness", rt["pf_timeliness"],
             f"timely {rt['pf_timely']} / late {rt['pf_late']} "
             f"(modeled background channel)")
    ctx.emit("runtime", "pf_issued_rows", rt["pf_issued"],
             f"deduped {rt['pf_deduped']}, "
             f"cancelled resident {rt['pf_cancelled_resident']}")
    for q in ("req_p50_ms", "req_p95_ms", "req_p99_ms"):
        ctx.emit("runtime", q, rt[q],
                 "modeled per-request latency (admission -> completion)")
    ctx.emit_percentiles("runtime", "pipelined", pipe)
    ctx.emit_snapshot("runtime", "pipelined", pipe["metrics"],
                      "store + rt counter space of the pipelined run")
    return red


def sharded_placements(ctx: BenchContext, n_shards: int = 4):
    """Sharded multi-worker serving, one row set per placement policy:
    hit rate, tail latency, max-shard load imbalance, and the parallel
    critical-path fetch (workers fetch concurrently, the batch pays the
    slowest shard).  The RecShard-style ``freq`` planner should match or
    beat the monolithic hit rate; ``row``/``hash`` should pin imbalance
    near 1.0."""
    from repro.sharding.embedding_shard import PLACEMENTS

    cfg, tr = _serving_cfg(ctx)
    params = init_dlrm(jax.random.PRNGKey(0), cfg)
    cap = int(0.18 * tr.unique_count())
    short = tr.slice(0, 40_000)
    mono = serve_trace(cfg, params, short, cap, "lru", None,
                       batch_queries=32)
    ctx.emit("sharded", "mono_hit_rate", mono["hit_rate"],
             f"single worker, {cap}-row budget")
    for placement in PLACEMENTS:
        res = serve_trace(cfg, params, short, cap, "lru", None,
                          batch_queries=32, shards=n_shards,
                          placement=placement)
        sh = res["shard"]
        ctx.emit("sharded", f"{placement}_hit_rate", res["hit_rate"],
                 f"{n_shards} workers")
        ctx.emit("sharded", f"{placement}_load_imbalance",
                 sh["load_imbalance"],
                 f"max/mean shard load (worst batch "
                 f"{sh['max_batch_imbalance']})")
        ctx.emit("sharded", f"{placement}_fetch_ms_critical",
                 round(sh["modeled_fetch_ms_critical"]
                       / max(res["batches"], 1), 3),
                 f"slowest-shard path; sum view "
                 f"{res['modeled_fetch_ms_per_batch']:.3f}, parallel "
                 f"speedup {sh['parallel_fetch_speedup']}")
        ctx.emit_percentiles("sharded", placement, res)


def scenario_matrix(ctx: BenchContext):
    """Beyond-paper workload-scenario matrix: every catalog scenario x
    {lru, recmg} through the model-free scenario harness (identical
    serving semantics, no dense forward) — per-scenario on-demand fetch
    count, hit rate and p50/p95 batch latency, plus two gate rows:

    * ``recmg_lru_on_demand_ratio_worst`` — worst-case ratio of recmg's
      on-demand fetches to LRU's over the paper-target regimes (ceiling
      metric: the ML policy must keep fetching less than LRU);
    * ``adapt_recovery`` — post-switch steady-state hit rate of
      drift-adaptive recmg on the diurnal regime relative to its
      pre-switch steady state (floor metric: the ISSUE's acceptance bar
      is 0.9 at the pinned test scale).
    """
    from repro.runtime.drift import DriftConfig
    from repro.workloads import (PAPER_TARGET_SCENARIOS, SCENARIOS,
                                 phase_steady_hit_rates, replay_scenario,
                                 scenario)

    n_acc = 16_384 if ctx.cfg.quick else 49_152
    scale = dict(n_tables=8, rows_per_table=2048, n_accesses=n_acc, seed=0)
    ratios = {}
    for name in sorted(SCENARIOS):
        per_policy = {}
        for policy in ("lru", "recmg"):
            res = replay_scenario(scenario(name, **scale), policy=policy,
                                  capacity_frac=0.12, batch=512)
            per_policy[policy] = res
            ctx.emit("scenario", f"{name}_{policy}_on_demand",
                     res["on_demand_rows"],
                     f"hit rate {res['hit_rate']}")
            ctx.emit("scenario", f"{name}_{policy}_p50_batch_ms",
                     round(res["p50_batch_ms"], 3))
            ctx.emit("scenario", f"{name}_{policy}_p95_batch_ms",
                     round(res["p95_batch_ms"], 3))
        r = (per_policy["recmg"]["on_demand_rows"]
             / max(per_policy["lru"]["on_demand_rows"], 1))
        ratios[name] = r
        ctx.emit("scenario", f"{name}_recmg_lru_on_demand_ratio",
                 round(r, 4), "paper direction: < 1 on target regimes")
    worst = max(ratios[n] for n in PAPER_TARGET_SCENARIOS)
    ctx.emit("scenario", "recmg_lru_on_demand_ratio_worst", round(worst, 4),
             f"over {sorted(PAPER_TARGET_SCENARIOS)}; perf-gate ceiling")

    # Drift-adaptation recovery row (diurnal, model frozen on phase 1).
    spec = scenario("diurnal", n_tables=4, rows_per_table=512,
                    n_accesses=16_384, seed=0)
    kw = dict(policy="recmg", batch=256, profile_frac=0.25,
              capacity_frac=0.12)
    frozen = replay_scenario(spec, **kw)
    adapt = replay_scenario(spec, adapt=True,
                            adapt_cfg=DriftConfig(window=1024, hot_k=128),
                            **kw)

    n_phases = int(spec.param("n_phases"))
    ph = phase_steady_hit_rates(adapt, n_phases)
    pre, post = ph[0], ph[1:].mean()
    ctx.emit("scenario", "adapt_recovery", round(post / max(pre, 1e-9), 4),
             f"post-switch steady hit {post:.3f} vs pre {pre:.3f}; "
             "perf-gate floor")
    ctx.emit("scenario", "frozen_decay",
             round(phase_steady_hit_rates(frozen, n_phases)[1:].mean()
                   / max(pre, 1e-9), 4),
             "same model without adaptation (the gap --adapt closes)")
    ctx.emit("scenario", "adapt_triggers", adapt["drift"]["triggers"],
             f"min jaccard {adapt['drift']['min_jaccard']}")
    ctx.emit_snapshot("scenario", "adapt_diurnal", adapt["metrics"],
                      "store + drift counter space of the adaptive run")


def learned_vs_voyager(ctx: BenchContext):
    """Learned dual-model RecMG vs the Voyager-class prefetch-only
    baseline (paper §VII-C: RecMG needs ~1/1.5 the on-demand fetches of
    Voyager because the caching model protects rows the prefetcher would
    have to re-fetch).  Both arms train on the same trace through the
    scenario harness; the gate row is the *worst* learned/voyager
    on-demand ratio over the covered scenarios — a ceiling metric with an
    absolute cap of 1.0 (learned must beat Voyager outright, not just
    stay near a baseline).

    Training cost dominates this bench, so the quick lane covers one
    paper-target scenario and the full lane all four.  The learned arm
    uses the :class:`LearnedModelConfig` defaults (tuned for exactly this
    scale) rather than ``ctx.cfg.epochs`` — a 1-epoch smoke model would
    undertrain and gate on noise.
    """
    from repro.workloads import PAPER_TARGET_SCENARIOS, replay_scenario, scenario

    names = (("zipf_mid",) if ctx.cfg.quick
             else tuple(sorted(PAPER_TARGET_SCENARIOS)))
    scale = dict(n_tables=4, rows_per_table=512, n_accesses=8192, seed=0)
    ratios = {}
    for name in names:
        spec = scenario(name, **scale)
        per_model = {}
        for model in ("learned", "voyager"):
            res = replay_scenario(spec, policy="recmg", model=model,
                                  capacity_frac=0.12, batch=256)
            per_model[model] = res
            ctx.emit("learned", f"{name}_{model}_on_demand",
                     res["on_demand_rows"], f"hit rate {res['hit_rate']}")
        r = (per_model["learned"]["on_demand_rows"]
             / max(per_model["voyager"]["on_demand_rows"], 1))
        ratios[name] = r
        ctx.emit("learned", f"{name}_learned_voyager_ratio", round(r, 4),
                 "paper target: ~1/1.5")
    worst = max(ratios.values())
    ctx.emit("learned", "recmg_vs_voyager_on_demand_ratio", round(worst, 4),
             f"worst over {list(names)}; perf-gate ceiling, hard cap 1.0")


def overload_degradation(ctx: BenchContext):
    """ROADMAP item 4: goodput under sustained overload.  Sweeps offered
    load 0.5x -> 4x of modeled compute capacity through the SLO-aware
    admission path on the VirtualClock (deterministic) and emits the
    smooth-degradation figure of merit the perf gate floors: goodput at
    4x must stay >= 0.7x of goodput at 1x — shedding and degraded
    answers absorb the excess instead of collapsing the service."""
    from repro.workloads import make_spec
    from repro.workloads.overload import degradation_ratio, overload_sweep

    n_acc = 24_000 if ctx.cfg.quick else 48_000
    spec = make_spec("sustained_overload", n_accesses=n_acc, seed=0)
    sweep = overload_sweep(loads=(0.5, 1.0, 2.0, 4.0), spec=spec,
                           policy="lru", batch=32, per_query=8)
    for x, r in sweep.items():
        tag = f"{x:g}x"
        ctx.emit("overload", f"goodput_rps_{tag}", r["goodput_rps"],
                 f"served {r['served']} shed {r['shed']} "
                 f"degraded {r['degraded']} of {r['admitted']}")
        ctx.emit("overload", f"p999_ms_{tag}", r["p999_ms"],
                 f"p99 {r['p99_ms']} ms; queue bound {r['queue_bound']}")
    r4 = sweep[4.0]
    ctx.emit("overload", "shed_4x", r4["shed"],
             f"lowest-priority-first: gold {r4['gold_shed']} "
             f"silver {r4['silver_shed']} bronze {r4['bronze_shed']}")
    ctx.emit("overload", "degraded_4x", r4["degraded"],
             f"stale rows {r4['degraded_rows_stale']} default rows "
             f"{r4['degraded_rows_default']}; pf suppressed "
             f"{r4['pf_suppressed']}")
    ratio = degradation_ratio(sweep)
    ctx.emit("overload", "overload_goodput_4x_vs_1x", round(ratio, 4),
             "smooth-degradation gate: absolute floor 0.7 (no collapse)")


def failover_resilience(ctx: BenchContext):
    """Goodput under a deterministic mid-run shard kill vs the same
    workload with no faults.  Hot-row replication + the degraded
    ``lookup_resident`` contract keep every answer exact-or-zero (the
    lockstep audit proves zero wrong rows) while recovery streams the
    lost resident set back as int8 chunks; the perf gate floors the
    kill/clean goodput ratio at 0.8 — losing a shard costs availability
    headroom, never correctness or a collapse."""
    from repro.workloads import make_spec
    from repro.workloads.chaos import (DEFAULT_FAULT_PLAN, chaos_sweep,
                                       failover_goodput)

    n_acc = 24_000 if ctx.cfg.quick else 48_000
    spec = make_spec("shard_failure", n_accesses=n_acc, seed=0)
    sweep = chaos_sweep(plans=(None, DEFAULT_FAULT_PLAN), spec=spec,
                        batch=128, shards=4, policy="lru")
    clean, kill = sweep[""], sweep[DEFAULT_FAULT_PLAN]
    ctx.emit("failover", "goodput_rps_clean", clean["goodput_rps"],
             f"{clean['batches']} batches, {clean['shards']} shards")
    ctx.emit("failover", "goodput_rps_kill", kill["goodput_rps"],
             f"plan {kill['fault_plan']}; replica rows "
             f"{kill['failover_replica']} degraded "
             f"{kill['failover_degraded']} of {kill['served']}")
    ctx.emit("failover", "wrong_rows_kill", kill["wrong_rows"],
             "lockstep byte-audit vs the no-fault run; contract: 0")
    ctx.emit("failover", "recovery_bytes_int8", kill["recovery_bytes"],
             f"{kill['recovery_rows']} rows in {kill['recovery_chunks']} "
             f"chunks; fp32-equivalent {kill['recovery_bytes_raw']} B")
    ratio = failover_goodput(sweep)
    ctx.emit("failover", "failover_goodput_kill_vs_clean", round(ratio, 4),
             "shard-loss resilience gate: absolute floor 0.8")


def run(ctx: BenchContext):
    lookup_throughput(ctx)
    tracing_overhead(ctx)
    cfg, tr, cap, results, out_full = fig16_17_e2e(ctx)
    runtime_pipeline(ctx, cfg, tr, cap, out_full, results["recmg"])
    fig18_19_perf_model(ctx)
    quantized_buffer_beyond_paper(ctx)
    multi_table_facade(ctx)
    sharded_placements(ctx)
    scenario_matrix(ctx)
    learned_vs_voyager(ctx)
    overload_degradation(ctx)
    failover_resilience(ctx)
