"""DLRM serving launcher on tiered memory — the paper's deployment.

    PYTHONPATH=src python -m repro.launch.serve --policy recmg --batches 50
    PYTHONPATH=src python -m repro.launch.serve --published --policy lru \
        --batches 10 --batch-queries 16      # dlrm-recmg at published widths

Pipeline per inference batch (paper Fig. 6):
  1. embedding lookups go through the TieredEmbeddingStore (device buffer
     backed by host-tier tables);
  2. the DLRM dense compute runs jitted on the device;
  3. between batches, the CPU-side caching/prefetch model outputs for the
     *previous* chunk are applied (Algorithm 1), pipelined one batch ahead.

Prints the Fig.16-style latency breakdown and hit rates per policy.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.recmg import RecMGOutputs, precompute_outputs
from repro.core.serving import MultiTableTieredStore
from repro.core.tiered import TieredEmbeddingStore
from repro.core.trace import Trace, TraceGenConfig, generate_trace
from repro.launch.compile_cache import enable_compile_cache
from repro.models.dlrm import forward_pooled, init_dlrm_dense
from repro.obs import MetricsRegistry
from repro.obs.tracing import Steps, get_tracer


# Rows per table when the published 72,704 do not fit the host: 856 x 8,192
# x 128 fp32 rows are 3.6 GB of slow tier.
CUT_ROWS_PER_TABLE = 8192
# Host RAM the full published table needs, as a multiple of its own bytes:
# the trace, its window features and the runtime need room beside it.
FULL_TABLE_RAM_FACTOR = 1.5


def make_host_table(n_rows: int, d: int, seed: int = 0) -> np.ndarray:
    """The slow tier: ``(n_rows, d)`` float32 rows drawn N(0, 1) from
    ``seed``.  Filled in place in float32, so a 31.9 GB table never has a
    float64 twin."""
    host = np.empty((n_rows, d), np.float32)
    np.random.default_rng(seed).standard_normal(out=host, dtype=np.float32)
    return host


def host_mem_available() -> Optional[int]:
    """``MemAvailable`` from ``/proc/meminfo`` in bytes (None off Linux)."""
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        return None
    return None


def published_rows_per_table(cfg, mem_available: Optional[int]) -> int:
    """Rows per table for ``cfg`` at its published widths: all of them
    when host RAM holds ``FULL_TABLE_RAM_FACTOR`` times the fp32 table,
    else ``CUT_ROWS_PER_TABLE``."""
    full_bytes = cfg.n_tables * cfg.rows_per_table * cfg.emb_dim * 4
    if mem_available is not None \
            and mem_available >= FULL_TABLE_RAM_FACTOR * full_bytes:
        return cfg.rows_per_table
    return min(cfg.rows_per_table, CUT_ROWS_PER_TABLE)


@dataclasses.dataclass
class ServeStats:
    """Host seconds of the serve loop's own steps, always on (one
    ``perf_counter`` reading per boundary), published under ``serve.*``.
    On the synchronous path a batch is ``outputs_s + flush_s + lookup_s +
    pool_s + forward_s`` of its steps, exactly: the previous batch's
    outputs and flush, which this batch waits for, then its own lookup,
    pooling and forward."""
    outputs_s: float = 0.0  # staging the previous batch's model outputs
    flush_s: float = 0.0  # flush_staged: applying them (store.populate)
    lookup_s: float = 0.0  # store.lookup (its steps: TierStats)
    pool_s: float = 0.0  # dispatch of pool_bags
    forward_s: float = 0.0  # dense input, forward and its device sync
    h2d_bytes: int = 0  # dense inputs sent to the forward
    pool_rows: int = 0  # rows pool_bags summed into bags

    def as_dict(self) -> Dict[str, float]:
        return dataclasses.asdict(self)

    def publish(self, reg, lat, prefix: str = "serve"):
        """The steps, and the count and summed seconds of the batches'
        latencies ``lat``."""
        reg.counter(f"{prefix}.batches").inc(len(lat))
        reg.counter(f"{prefix}.batch_s").inc(float(sum(lat)))
        for key, val in self.as_dict().items():
            reg.counter(f"{prefix}.{key}").inc(val)
        return reg


@functools.partial(jax.jit, static_argnums=(1, 2))
def pool_bags(rows, queries: int, pooling: Tuple[int, ...]):
    """Sum-pool served rows, ``(n, D)`` in request order (each query's
    ``pooling[0]`` rows of table 0, then ``pooling[1]`` of table 1, ...),
    into ``(queries, tables, D)`` bags, as one program (``jit_pool_bags``).
    One bag size for every table pools by one reshape; uneven sizes sum
    each table's segment of the query.  A partial batch (EDF pops under
    admission control can close one below ``max_batch``) is zero-padded to
    the full shape first."""
    pad = queries * sum(pooling) - rows.shape[0]
    if pad:
        rows = jnp.concatenate([rows, jnp.zeros((pad, rows.shape[1]),
                                                rows.dtype)])
    tables, d = len(pooling), rows.shape[1]
    if len(set(pooling)) == 1:
        return rows.reshape(queries, tables, pooling[0], d).sum(axis=2)
    q = rows.reshape(queries, sum(pooling), d)
    ends = np.cumsum(pooling)
    return jnp.stack([q[:, e - p:e].sum(axis=1)
                      for p, e in zip(pooling, ends)], axis=1)


@dataclasses.dataclass
class ServedBatch:
    """One served batch, handed to ``serve_trace``'s ``probe`` outside the
    timed window: what went in, what the store served, what came out."""
    index: int
    # (B * sum(P_t),) global row ids in request order: each query's P_0
    # ids of table 0, then P_1 of table 1, ...
    ids: np.ndarray
    rows: jax.Array  # (B * sum(P_t), D) the rows the store served
    dense: jax.Array  # (B, dense_features) dense input
    logits: jax.Array  # (B,) served logits
    host: np.ndarray  # the slow tier the rows came from
    cfg: object  # the model config served (tables, width, pooling)
    params: dict  # dense-MLP parameters of the forward
    store: object  # the store that served the batch
    # This batch's step seconds and counts (TierStats.steps() and
    # ServeStats deltas since the previous batch's probe), the flush that
    # ran before its lookup included; h2d_bytes sums store and forward.
    steps: Dict[str, float] = dataclasses.field(default_factory=dict)


def serve_trace(cfg, params, trace: Trace, capacity: int, policy: str,
                outputs: Optional[RecMGOutputs], batch_queries: int = 64,
                fetch_us_per_row: float = 10.0, multi_table: bool = False,
                shards: int = 0, placement: str = "table",
                async_prefetch: bool = False, pipeline_depth: int = 2,
                scheduler: str = "inline", interarrival_us: float = 0.0,
                compute_us: Optional[float] = None, adapt: bool = False,
                adapt_cfg=None, model=None, overload: float = 0.0,
                priority_mix=None, queue_bound: int = 0,
                fault_plan: str = "", fault_seed: int = 0,
                replicate_hot: int = 0, quantize: bool = False,
                row_format: Optional[str] = None, log=None,
                probe: Optional[Callable[[ServedBatch], None]] = None
                ) -> Dict:
    """Replay a trace as DLRM inference batches through the tiered store.

    ``quantize=True`` stores the fast tier quantized (``row_format``:
    ``"int8"`` default or ``"fp8"``) with per-row fp32 scales — ``D + 4``
    bytes per resident row instead of ``D * 4``, so the same byte budget
    holds more hot rows (``capacity`` here is still in rows; the CLI's
    ``--quantize`` converts the byte budget implied by
    ``--capacity-frac`` into the larger quantized row count).

    ``multi_table=True`` serves through the per-table facade (one batched
    store per sparse feature under the shared row budget) instead of one
    monolithic store.

    ``shards > 0`` serves through the sharded multi-worker store
    (:class:`~repro.core.sharded_serving.ShardedTieredStore`): the tables
    are partitioned across ``shards`` simulated workers under the chosen
    ``placement`` policy (``table`` / ``row`` / ``hash`` / ``freq``; the
    frequency-aware planner profiles the first quarter of the trace) and
    each batch is routed shard-locally and gathered back.  The result
    dict gains a ``"shard"`` key with per-shard load/skew/stall
    telemetry.

    ``async_prefetch=True`` serves through the pipelined runtime
    (:mod:`repro.runtime`): requests go through the admission queue +
    micro-batcher, staged model outputs are applied by the background
    prefetch engine, and batch *k*'s slow-tier fetch overlaps batch
    *k-1*'s dense forward on the modeled timeline.  With the default
    ``"inline"`` scheduler the store sees the exact same operation
    sequence as the synchronous path (identical hit/miss/eviction
    counters); only the on-demand fetch *stall* accounting changes.

    ``adapt=True`` attaches a drift-adaptive controller
    (:class:`~repro.runtime.drift.AdaptiveController`): windowed
    hit-rate + hot-set-Jaccard telemetry over the live stream, and on a
    drift trigger the caching/prefetch model *features* are refreshed
    online (hot-pool rebuild + per-chunk re-rank + prefetch of the
    newly-hot rows), staged through the normal model-output path.  The
    result dict gains a ``"drift"`` telemetry key.

    ``model`` optionally passes the live
    :class:`~repro.core.model_runtime.LearnedRecMGModel` behind
    ``outputs``; with ``adapt=True`` the drift controller then also
    fine-tunes the model online on every refresh and swaps in recomputed
    outputs (:class:`~repro.core.model_runtime.LearnedController`) — on
    both the synchronous and the pipelined (``VirtualClock``) path.

    ``fault_plan`` (requires ``shards``) arms deterministic fault
    injection on the sharded store — the CLI grammar from
    :class:`~repro.runtime.faults.FaultPlan` (``"kill:1@mid,
    recover:1@75%"``; fractional times resolve against the batch count).
    ``replicate_hot`` keeps the top-k profiled rows resident on every
    shard so a dead shard's hot traffic stays exactly answerable.  The
    result gains an ``"ft"`` key and the reconciled ``ft.*`` namespace.

    ``overload > 0`` (requires ``async_prefetch``) serves through the
    SLO-aware admission path (:mod:`repro.runtime.admission`): requests
    arrive open-loop at ``overload`` times the modeled compute capacity
    with priorities drawn from ``priority_mix`` (a weight per class,
    most-important first), the queue is bounded at ``queue_bound``
    (default 4 batches) with lowest-priority-first shedding, EDF batch
    scheduling, deadline-driven degraded answers and prefetch
    backpressure.  The result gains ``admission`` /  ``goodput_rps``
    keys and the ``adm.*`` metrics namespace.

    The slow tier is :func:`make_host_table` over the trace's rows, seed
    0.  ``probe``, if given, is called with a :class:`ServedBatch` after
    every batch, outside the timed window (it needs FIFO batches, so not
    with ``overload``)."""
    pooling = cfg.pooling
    qp = cfg.ids_per_query  # ids per query = one request
    segments, longest_bag = batch_queries * len(pooling), max(pooling)
    per_batch = batch_queries * qp
    n_batches = len(trace.global_id) // per_batch
    if n_batches == 0:
        raise ValueError(
            f"the trace holds {len(trace.global_id)} accesses, fewer than "
            f"one batch of {batch_queries} queries x {qp} ids = {per_batch}")
    if probe is not None and overload:
        raise ValueError("probe needs FIFO batches; admission control "
                         "(overload) reorders them")
    host = make_host_table(int(trace.rows_per_table.sum()), cfg.emb_dim)
    pol = "recmg" if policy == "recmg" else "lru"
    if shards and multi_table:
        raise ValueError("pass at most one of shards / multi_table")
    # Warm the jitted scatter/gather shape buckets at construction (off the
    # measured path): without this, the first batch that hits each
    # power-of-two bucket pays an XLA compile inside the latency window —
    # visible as ~600ms p99 spikes against a ~10ms p50.
    if fault_plan and not shards:
        raise ValueError("--fault-plan requires --shards (the fault layer "
                         "lives in the sharded store)")
    if shards:
        from repro.core.sharded_serving import ShardedTieredStore

        profile = (trace.global_id
                   if placement == "freq" or replicate_hot else None)
        store = ShardedTieredStore.build(
            host, trace.rows_per_table, shards, placement,
            capacity=capacity, policy=pol, profile_ids=profile,
            replicate_hot=int(replicate_hot),
            quantize=quantize, row_format=row_format,
            fetch_us_per_row=fetch_us_per_row, warmup_batch=per_batch)
        if fault_plan:
            store.arm_faults(
                fault_plan, seed=fault_seed,
                horizon_batches=len(trace.global_id) // per_batch)
    elif multi_table:
        store = MultiTableTieredStore.from_global_table(
            host, trace.rows_per_table, capacity=capacity, policy=pol,
            quantize=quantize, row_format=row_format,
            fetch_us_per_row=fetch_us_per_row, warmup_batch=per_batch)
    else:
        store = TieredEmbeddingStore(
            host, capacity, policy=pol, quantize=quantize,
            row_format=row_format, fetch_us_per_row=fetch_us_per_row,
            warmup_batch=per_batch)

    def dense_forward(pr, d, e):
        return _dense_forward(pr, cfg, d, e)

    fwd = jax.jit(dense_forward)  # runs as jit_dense_forward

    gid = trace.global_id
    rng = np.random.default_rng(1)
    chunk_state = {"ptr": 0}
    serve = ServeStats()

    from repro.core.model_runtime import OutputsRef

    oref = OutputsRef(outputs)

    controller = None
    if adapt:
        from repro.runtime.drift import AdaptiveController, DriftConfig

        if adapt_cfg is None:
            adapt_cfg = DriftConfig(window=max(1024, 4 * per_batch),
                                    hot_k=min(capacity, 256))
        if model is not None:
            from repro.core.model_runtime import LearnedController

            controller = LearnedController(store, capacity, model, oref,
                                           trace, adapt_cfg)
        else:
            controller = AdaptiveController(store, capacity, adapt_cfg)

    def staged_for_batch(b):
        """Model outputs to stage after batch ``b``: caching priorities for
        every chunk the batch covered, but prefetches only from the most
        recent one — the paper issues ONE prefetch set per inference batch
        (Fig. 6); flooding every chunk's PO would churn the buffer.  Reads
        through ``oref`` so an online output refresh (LearnedController)
        takes effect at the next batch; the chunk grid is identical, so
        the chunk pointer stays valid."""
        out = oref.outputs
        if out is None:
            return []
        items, last_pf = [], None
        hi = (b + 1) * per_batch
        empty = np.empty(0, np.int64)
        ptr = chunk_state["ptr"]
        while (ptr < len(out.chunk_starts)
               and out.chunk_starts[ptr] < hi):
            s = int(out.chunk_starts[ptr])
            trunk = gid[max(0, s - 15): s]
            bits = (out.caching_bits[ptr]
                    if out.caching_bits is not None
                    else np.zeros(len(trunk)))
            items.append((trunk, bits, empty))
            if out.prefetch_ids is not None:
                last_pf = out.prefetch_ids[ptr]
            ptr += 1
        chunk_state["ptr"] = ptr
        if last_pf is not None:
            items.append((empty, empty, np.asarray(last_pf, np.int64)))
        return items

    def forward_batch(emb, clk: Steps):
        """Pool + dense forward, as the steps ``pool`` and ``forward`` of
        ``clk``; returns ``(forward seconds, logits, dense input)``."""
        n = int(emb.shape[0])
        with clk.step(serve, "pool_s", "serve", "pool", track="serve",
                      segments=segments, longest_bag=longest_bag, rows=n):
            pooled = pool_bags(emb, batch_queries, pooling)
        serve.pool_rows += n
        f0 = serve.forward_s
        with clk.step(serve, "forward_s", "serve", "forward",
                      track="serve"):
            dense_np = rng.normal(
                size=(batch_queries, cfg.dense_features)).astype(np.float32)
            serve.h2d_bytes += dense_np.nbytes
            dense = jnp.asarray(dense_np)
            out = fwd(params, dense, pooled)
            jax.block_until_ready(out)
        return serve.forward_s - f0, out, dense

    steps_seen = {}  # step totals at the previous probe

    def step_totals():
        d = store.stats.steps()
        d.update(serve.as_dict(), h2d_bytes=d["h2d_bytes"]
                 + serve.h2d_bytes)
        return d

    def report(b, ids, emb, fwd_out):
        if probe is not None:
            _, logits, dense = fwd_out
            now = step_totals()
            steps = {k: v - steps_seen.get(k, 0) for k, v in now.items()}
            steps_seen.update(now)
            probe(ServedBatch(b, ids, emb, dense, logits, host, cfg, params,
                              store, steps))

    # Warm the pooling and the dense forward off the measured path: their
    # first-call XLA compiles otherwise land inside batch 0's latency
    # window and dominate the p99 (~150ms against a ~5ms p50).  Shapes and
    # dtypes match the real batches, so this is a pure compile-cache fill.
    warm_rows = jnp.zeros((per_batch, cfg.emb_dim), host.dtype)
    warm_pooled = pool_bags(warm_rows, batch_queries, pooling)
    warm_dense = jnp.zeros((batch_queries, cfg.dense_features), jnp.float32)
    jax.block_until_ready(fwd(params, warm_dense, warm_pooled))

    rt = None
    adm_cfg = None
    if overload and not async_prefetch:
        raise ValueError("--overload requires --async-prefetch (the "
                         "admission path lives in the pipelined runtime)")
    if async_prefetch:
        from repro.runtime import (AdmissionConfig, PipelinedRuntime,
                                   RuntimeConfig)

        if overload:
            # Offered load as a multiple of modeled compute capacity:
            # one batch per compute_us -> interarrival pins the rate.
            if compute_us is None:
                compute_us = 500.0
            interarrival_us = compute_us / (batch_queries * float(overload))
            adm_cfg = AdmissionConfig(
                queue_bound=int(queue_bound) if queue_bound
                else 4 * batch_queries,
                class_deadline_us=(4 * compute_us, 16 * compute_us,
                                   64 * compute_us))

        # ``compute_us`` pins the modeled device time per batch (so the
        # overlap window uses one cost model for both fetch and compute);
        # None overlaps against the measured wall-clock forward instead.
        # When a tracer with a virtual clock is installed, the runtime
        # shares it so the trace timeline and the modeled pipeline
        # timeline are one and the same.
        _tr = get_tracer()
        rt_clock = _tr.clock if (_tr.enabled
                                 and hasattr(_tr.clock, "advance_to")) \
            else None
        rt = PipelinedRuntime(store, RuntimeConfig(
            max_batch=batch_queries, pipeline_depth=pipeline_depth,
            interarrival_us=interarrival_us, scheduler=scheduler,
            fetch_us_per_row=fetch_us_per_row, compute_us=compute_us,
            admission=adm_cfg),
            clock=rt_clock,
            batch_hook=controller.on_batch if controller else None)

        served = {"n": 0}  # FIFO batches: batch b's ids follow b-1's

        def step(b, emb):
            fwd_out = forward_batch(emb, Steps(get_tracer()))
            c = fwd_out[0]
            lo = served["n"]
            served["n"] += emb.shape[0]
            report(b, gid[lo: served["n"]], emb, fwd_out)
            if log and b % 10 == 0:
                log(f"batch {b}: hit {store.stats.hit_rate:.3f} "
                    f"stall {rt.telemetry.stall_ms:.1f} ms")
            return c, staged_for_batch(b)

        n_queries = n_batches * batch_queries
        if adm_cfg is not None:
            mix = np.asarray(priority_mix if priority_mix is not None
                             else (0.2, 0.3, 0.5), np.float64)
            if mix.size != adm_cfg.n_classes or mix.min() < 0 \
                    or mix.sum() <= 0:
                raise ValueError(f"priority_mix needs {adm_cfg.n_classes} "
                                 f"non-negative weights, got "
                                 f"{priority_mix!r}")
            pri = np.random.default_rng(2).choice(
                adm_cfg.n_classes, size=n_queries, p=mix / mix.sum())
            stream = ((gid[i * qp: (i + 1) * qp], int(pri[i]))
                      for i in range(n_queries))
        else:
            stream = (gid[i * qp: (i + 1) * qp]
                      for i in range(n_queries))
        rt.run(stream, step)
        lat = rt.wall_batch_s
    else:
        lat = []
        _tr = get_tracer()

        def stage_outputs(clk, b, ids, pre_hits):
            """Stage batch ``b``'s model outputs and flush them: the steps
            ``outputs`` and ``flush`` of the next batch, which waits for
            them.  ``stage_model_outputs`` double-buffers, so the outputs
            land at a batch boundary and never inside a lookup."""
            with clk.step(serve, "outputs_s", "serve", "outputs",
                          track="serve"):
                for item in staged_for_batch(b):
                    store.stage_model_outputs(*item)
                if controller is not None:
                    # Adaptation items stage after the model's: the fresh
                    # re-ranks must win over stale ones at the next drain.
                    for item in controller.on_batch(
                            ids, store.stats.hits - pre_hits, b):
                        store.stage_model_outputs(*item)
            with clk.step(serve, "flush_s"):  # spans: store.populate
                store.flush_staged()

        # A batch is timed as its client waits for it: from the end of the
        # previous batch's probe (its outputs and flush come first) to the
        # end of this batch's forward, on the step timer's readings.
        pending = None  # (b, ids, hits before its lookup) to stage
        for b in range(n_batches):
            clk = Steps(_tr)
            t0 = clk.t
            if pending is not None:
                stage_outputs(clk, *pending)
            if _tr.enabled:
                _tr.set_batch(b)
            ids = gid[b * per_batch: (b + 1) * per_batch]
            pre_hits = store.stats.hits
            with clk.step(serve, "lookup_s"):  # spans: store.lookup
                emb = store.lookup(ids)  # (per_batch, D)
            fwd_out = forward_batch(emb, clk)
            lat.append(clk.t - t0)
            report(b, ids, emb, fwd_out)
            pending = (b, ids, pre_hits)
            if log and b % 10 == 0:
                log(f"batch {b}: {lat[-1]*1e3:.1f} ms "
                    f"hit {store.stats.hit_rate:.3f}")
        if pending is not None:
            stage_outputs(Steps(_tr), *pending)

    st = store.stats.as_dict()
    compute_ms = serve.forward_s / max(n_batches, 1) * 1e3
    st.update(
        policy=policy,
        mean_batch_ms=float(np.mean(lat) * 1e3),
        p50_batch_ms=float(np.percentile(lat, 50) * 1e3),
        p95_batch_ms=float(np.percentile(lat, 95) * 1e3),
        p99_batch_ms=float(np.percentile(lat, 99) * 1e3),
        compute_ms=compute_ms,
        modeled_fetch_ms_per_batch=store.modeled_batch_ms(),
        # The paper's §VII-F decomposition: device compute (policy-
        # independent) + the slow-tier on-demand model.  Our python slot
        # bookkeeping (TorchRec does it in C++/CUDA, the paper reports a
        # 10x engineering speedup there) is excluded from this figure.
        modeled_e2e_ms=compute_ms + store.modeled_batch_ms(),
    )
    if rt is not None:
        tel = rt.telemetry
        st["on_demand_stall_ms"] = round(tel.stall_ms, 3)
        st["pf_accuracy"] = round(
            store.stats.prefetch_hits / max(tel.pf_issued, 1), 4)
        st["pf_coverage"] = round(
            store.stats.prefetch_hits
            / max(store.stats.prefetch_hits + store.stats.on_demand_rows, 1),
            4)
        st["runtime"] = rt.results()
        if rt.admission_stats is not None:
            adm = rt.admission_stats
            modeled_s = max(rt.clock.now() * 1e-6, 1e-12)
            st["admission"] = adm.as_dict(adm_cfg)
            st["goodput_rps"] = round(adm.total_served / modeled_s, 3)
            st["offered_rps"] = round(1e6 / interarrival_us, 3)
    else:
        # Synchronous serving: every on-demand fetch sits on the critical
        # path, so the stall is the whole modeled slow-tier cost.
        st["on_demand_stall_ms"] = round(store.stats.modeled_fetch_s * 1e3, 3)
    if controller is not None:
        st["drift"] = controller.as_dict()
    if multi_table:
        st["per_table_hit_rates"] = [
            round(h, 4) for h in store.per_table_hit_rates()]
    if shards:
        st["shard"] = store.shard_telemetry()
        st["shard_load_imbalance"] = st["shard"]["load_imbalance"]
        if store.ft_stats is not None:
            store.ft_stats.check()
            st["ft"] = store.ft_stats.as_dict()

    # Unified metrics registry: every telemetry producer of the run
    # publishes into one namespace, so the reconciliation checker (and
    # ``--metrics-out``) sees a single flat counter space.
    reg = MetricsRegistry()
    store.publish_metrics(reg)
    serve.publish(reg, lat)
    if rt is not None:
        rt.publish(reg)
    if controller is not None and hasattr(controller, "publish"):
        controller.publish(reg)
    st["metrics"] = reg.snapshot()
    return st


# The forward every served batch runs, as ``jit_dense_forward``: logits from
# the pooled embeddings (B, T, D), with the interaction ``cfg`` names.
_dense_forward = forward_pooled


def main(argv=None, probe: Optional[Callable[[ServedBatch], None]] = None):
    """The serve CLI; ``probe`` is passed through to :func:`serve_trace`."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--policy", default="recmg",
                    choices=["lru", "recmg", "recmg-oracle"])
    ap.add_argument("--model", default="learned",
                    choices=["learned", "frequency", "voyager"],
                    help="where the recmg model outputs come from: the "
                         "trained dual models (learned — jitted bucketed "
                         "inference, online fine-tune under --adapt), the "
                         "deterministic frequency heuristic, or the "
                         "Voyager-class ML prefetcher baseline (prefetch "
                         "stream on an LRU store)")
    ap.add_argument("--published", action="store_true",
                    help="serve dlrm-recmg at its published widths (856 "
                         "tables, D=128, P=20, published MLPs) instead of "
                         "the reduced smoke config; only the batch count "
                         "and, when host RAM is short, the rows per table "
                         f"(to {CUT_ROWS_PER_TABLE}) are cut")
    ap.add_argument("--batches", type=int, default=40,
                    help="batches to serve; the trace holds exactly "
                         "batches x batch-queries x the ids of a query")
    ap.add_argument("--batch-queries", type=int, default=32)
    ap.add_argument("--capacity-frac", type=float, default=0.2)
    ap.add_argument("--train-epochs", type=int, default=3)
    ap.add_argument("--quantize", action="store_true",
                    help="store the fast tier quantized (per-row scales); "
                         "the byte budget implied by --capacity-frac is "
                         "re-spent as quantized rows, so the buffer holds "
                         "~2-4x the rows at the same bytes")
    ap.add_argument("--row-format", default="int8",
                    choices=("int8", "fp8"),
                    help="quantized row storage format (with --quantize)")
    ap.add_argument("--multi-table", action="store_true",
                    help="serve through the per-table facade "
                         "(one batched store per sparse feature)")
    ap.add_argument("--shards", type=int, default=0,
                    help="partition the tables across this many simulated "
                         "workers (0 = single-worker store)")
    ap.add_argument("--placement", default="table",
                    choices=["table", "row", "hash", "freq"],
                    help="shard placement policy: table-wise bin-pack, "
                         "row-wise round-robin, keyed hash, or the "
                         "frequency-aware (RecShard-style) planner")
    ap.add_argument("--async-prefetch", action="store_true",
                    help="serve through the pipelined runtime: admission "
                         "queue + micro-batcher, background prefetch "
                         "engine, fetch/compute overlap")
    ap.add_argument("--pipeline-depth", type=int, default=2,
                    help="how many batches the host may run ahead of the "
                         "device (2 = double buffering; 1 = synchronous)")
    ap.add_argument("--scheduler", default="inline",
                    choices=["inline", "thread"],
                    help="prefetch-engine scheduler: inline is "
                         "deterministic, thread overlaps wall-clock")
    ap.add_argument("--overload", type=float, default=0.0,
                    help="serve open-loop at this multiple of modeled "
                         "compute capacity through the SLO-aware admission "
                         "path (EDF scheduling, bounded queue with "
                         "lowest-priority-first shedding, degraded answers "
                         "past deadline, prefetch backpressure); implies "
                         "--async-prefetch")
    ap.add_argument("--priority-mix", default="",
                    help="comma-separated traffic weights per priority "
                         "class, most-important first (default 0.2,0.3,0.5 "
                         "over gold,silver,bronze)")
    ap.add_argument("--queue-bound", type=int, default=0,
                    help="admission-queue bound in requests (default: 4 "
                         "batches); the excess is shed "
                         "lowest-priority-first")
    ap.add_argument("--fault-plan", default="",
                    help="deterministic fault schedule for the sharded "
                         "store (requires --shards): comma-separated "
                         "kind[:shard[xfactor]]@start[..end] events with "
                         "kinds kill/recover/slow/flaky and times as batch "
                         "indices, percentages or 'mid' — e.g. "
                         "'kill:1@mid,recover:1@75%' or "
                         "'flaky:2x0.3@25%..75%'")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the fault plan's transient-failure "
                         "draws (byte-reproducible per seed)")
    ap.add_argument("--replicate-hot", type=int, default=0,
                    help="replicate the top-k profiled hot rows on every "
                         "shard (RecShard-style) so a dead shard's hot "
                         "traffic is answered exactly from survivors")
    ap.add_argument("--workload", default="",
                    help="serve a named workload scenario instead of the "
                         "default calibrated trace: a catalog name "
                         "(zipf_hot, diurnal, flash_crowd, multi_tenant, "
                         "churn, ...) or 'regime:key=val,...' — e.g. "
                         "'diurnal:n_phases=6' or 'replay:path=tr.npz'")
    ap.add_argument("--adapt", action="store_true",
                    help="drift-adaptive serving: windowed hit-rate + "
                         "hot-set-Jaccard drift detector, online refresh "
                         "of the caching/prefetch features on trigger")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome/Perfetto trace-event JSON of the "
                         "run to this path (enables span tracing; open at "
                         "https://ui.perfetto.dev)")
    ap.add_argument("--metrics-out", default="",
                    help="write the run's metrics-registry snapshot JSON "
                         "to this path (check it with "
                         "scripts/check_accounting.py)")
    ap.add_argument("--flight-recorder", default="",
                    help="also write the flight-recorder ring — spans of "
                         "the last --trace-ring batches — to this path")
    ap.add_argument("--trace-ring", type=int, default=64,
                    help="flight-recorder ring size in batches")
    args = ap.parse_args(argv)
    if args.overload:
        args.async_prefetch = True
    enable_compile_cache()

    cfg = get_config("dlrm-recmg")
    if args.published:
        mem = host_mem_available()
        rows = published_rows_per_table(cfg, mem)
        print(f"{cfg.name} at published widths: {cfg.n_tables} tables, "
              f"D={cfg.emb_dim}, P={cfg.multi_hot}, "
              f"dense={cfg.dense_features}, bottom={cfg.bottom_mlp}, "
              f"top={cfg.top_mlp}")
        if rows != cfg.rows_per_table:
            full_gb = cfg.n_tables * cfg.rows_per_table * cfg.emb_dim * 4e-9
            avail = "unknown" if mem is None else f"{mem * 1e-9:.1f} GB"
            print(f"cut: rows_per_table {cfg.rows_per_table} -> {rows} "
                  f"(host RAM available {avail} < {FULL_TABLE_RAM_FACTOR} "
                  f"x the {full_gb:.1f} GB fp32 table)")
            cfg = dataclasses.replace(cfg, rows_per_table=rows)
    else:
        cfg = cfg.reduced()
    accesses = args.batches * args.batch_queries * cfg.ids_per_query
    print(f"cut: {args.batches} batches x {args.batch_queries} queries "
          f"({accesses} accesses)")
    # Serving reads embedding rows from the host tier: only the dense
    # MLPs live on the device.
    params = init_dlrm_dense(jax.random.PRNGKey(0), cfg)

    if args.workload:
        from repro.workloads import make_trace, parse_workload

        spec = parse_workload(args.workload)
        if spec.regime != "replay":  # replay: the file's geometry wins
            spec = spec.with_(n_tables=cfg.n_tables,
                              rows_per_table=cfg.rows_per_table,
                              n_accesses=accesses)
        trace = make_trace(spec)
    else:
        tr_cfg = TraceGenConfig(
            n_tables=cfg.n_tables, rows_per_table=cfg.rows_per_table,
            n_accesses=accesses, drift_every=10**9,
        )
        trace = generate_trace(tr_cfg)
    capacity = int(args.capacity_frac * trace.unique_count())
    if args.quantize:
        # Hold the byte budget fixed: re-spend the fp32 budget implied by
        # --capacity-frac as quantized rows (D + 4 bytes each).
        from repro.core.tiered import fast_row_bytes

        fp32_bytes = capacity * fast_row_bytes(cfg.emb_dim, np.float32,
                                               False)
        capacity = fp32_bytes // fast_row_bytes(cfg.emb_dim, np.float32,
                                                True, args.row_format)
        print(f"quantize({args.row_format}): {fp32_bytes} fast-tier bytes "
              f"-> {capacity} resident rows")

    outputs = None
    model_rt = None
    pol = args.policy
    if args.policy.startswith("recmg"):
        if args.policy == "recmg-oracle":
            outputs = precompute_outputs(trace)
            outputs = RecMGOutputs(outputs.chunk_starts, None, None)
        elif args.model == "frequency":
            from repro.core.recmg import frequency_outputs

            outputs = frequency_outputs(trace, capacity)
        elif args.model == "voyager":
            from repro.core.model_runtime import voyager_outputs

            # Prefetch-only baseline: LRU residency + Voyager's stream.
            outputs = voyager_outputs(trace, capacity,
                                      epochs=args.train_epochs)
            pol = "lru"
        else:
            from repro.core.model_runtime import (LearnedModelConfig,
                                                  LearnedRecMGModel)

            # CLI-scale knobs (the LearnedModelConfig defaults are tuned
            # for the small scenario-matrix scale): the seed launcher's
            # model size, epochs from --train-epochs, sparser windows and
            # the wide deployment candidate pool.
            lcfg = LearnedModelConfig(
                hidden=40, caching_epochs=args.train_epochs,
                prefetch_epochs=args.train_epochs, batch_size=256,
                lr=3e-3, train_stride=5, n_candidates=5000)
            model_rt = LearnedRecMGModel.train_from_trace(
                trace, capacity, lcfg, log=print)
            outputs = model_rt.outputs_for(trace)

    tracer = None
    if args.trace_out or args.flight_recorder:
        from repro.obs.tracing import SpanTracer, install_tracer
        from repro.runtime.clock import VirtualClock

        # Pipelined serving runs on the modeled (virtual) timeline, so
        # the trace does too; synchronous serving traces wall time.
        clock = VirtualClock() if args.async_prefetch else None
        tracer = SpanTracer(clock=clock, ring_batches=args.trace_ring)
        install_tracer(tracer)

    try:
        res = serve_trace(cfg, params, trace, capacity, pol, outputs,
                          batch_queries=args.batch_queries,
                          multi_table=args.multi_table,
                          shards=args.shards, placement=args.placement,
                          async_prefetch=args.async_prefetch,
                          pipeline_depth=args.pipeline_depth,
                          scheduler=args.scheduler, adapt=args.adapt,
                          model=model_rt, overload=args.overload,
                          priority_mix=tuple(
                              float(w) for w in
                              args.priority_mix.split(","))
                          if args.priority_mix else None,
                          queue_bound=args.queue_bound,
                          fault_plan=args.fault_plan,
                          fault_seed=args.fault_seed,
                          replicate_hot=args.replicate_hot,
                          quantize=args.quantize,
                          row_format=args.row_format if args.quantize
                          else None, log=print, probe=probe)
    finally:
        if tracer is not None:
            install_tracer(None)

    if args.metrics_out:
        import json

        with open(args.metrics_out, "w") as f:
            json.dump(res["metrics"], f, indent=1, sort_keys=True)
        print(f"metrics snapshot -> {args.metrics_out}")
    if tracer is not None:
        from repro.obs import reconcile, validate_chrome_trace

        trace_obj = tracer.chrome_trace()
        if args.trace_out:
            tracer.write(args.trace_out)
            print(f"trace ({len(trace_obj['traceEvents'])} events) -> "
                  f"{args.trace_out}")
        if args.flight_recorder:
            tracer.write(args.flight_recorder, flight_only=True)
            print(f"flight recorder -> {args.flight_recorder}")
        problems = validate_chrome_trace(trace_obj)
        problems += reconcile(metrics=res["metrics"], trace=trace_obj,
                              strict=False)
        if problems:
            print("RECONCILIATION PROBLEMS:")
            for p in problems:
                print(f"  {p}")
            raise SystemExit(1)
        print("trace/metrics reconciliation: OK")
    print({k: v for k, v in res.items() if k != "metrics"})
    return res


if __name__ == "__main__":
    main()
