"""Step timing of a served batch: the step counters tile the store's and
the serve loop's totals, the step counts are exact, a traced batch emits
its named spans (one ``store.populate`` per flush) on the profiler's host
plane too, and the batch runs as named device programs."""
import dataclasses
import time

import jax
import numpy as np
import pytest

from repro.core.tiered import TierStats, TieredEmbeddingStore
from repro.obs import (MetricsRegistry, NullTracer, SpanTracer, Steps,
                       install_tracer, reconcile, validate_chrome_trace)


SERVE_STEPS = ("outputs_s", "flush_s", "lookup_s", "pool_s", "forward_s")


def _tiny_model():
    from repro.configs import get_config
    from repro.core.trace import TraceGenConfig, generate_trace
    from repro.models.dlrm import init_dlrm_dense

    cfg = dataclasses.replace(get_config("dlrm-recmg").reduced(),
                              n_tables=4, rows_per_table=512, multi_hot=2,
                              emb_dim=16)
    params = init_dlrm_dense(jax.random.PRNGKey(0), cfg)
    trace = generate_trace(TraceGenConfig(
        n_tables=4, rows_per_table=512, n_accesses=2400, seed=0,
        drift_every=10**9))
    return cfg, params, trace


@pytest.fixture(scope="module")
def served():
    """A tiny model and trace served under RecMG (frequency outputs) and
    LRU, each traced with a ``SpanTracer``; probe records kept."""
    from repro.core.recmg import frequency_outputs
    from repro.launch.serve import serve_trace

    cfg, params, trace = _tiny_model()
    cap = int(0.15 * trace.unique_count())
    out = {}
    for policy in ("recmg", "lru"):
        outputs = frequency_outputs(trace, cap) if policy == "recmg" else None
        recs = []
        tr = install_tracer(SpanTracer())
        try:
            res = serve_trace(cfg, params, trace, cap, policy, outputs,
                              batch_queries=8, probe=recs.append)
        finally:
            install_tracer(None)
        out[policy] = (res, recs, tr)
    return out


@pytest.mark.parametrize("policy", ["recmg", "lru"])
def test_steps_tile_the_totals(served, policy):
    res, recs, _ = served[policy]
    st = recs[-1].store.stats
    assert st.fetch_s == pytest.approx(
        st.slow_read_s + st.residency_s + st.write_s, rel=1e-9)
    assert st.gather_s == pytest.approx(st.gather_dispatch_s + st.sync_s,
                                        rel=1e-9)
    assert st.model_s == pytest.approx(st.rank_s + st.prefetch_s, rel=1e-9,
                                       abs=1e-12)
    assert st.fetch_s > 0 and st.gather_s > 0
    assert (st.model_s > 0) == (policy == "recmg")
    # A batch is its serve steps exactly: the previous batch's outputs and
    # flush, then its own lookup, pooling and forward.
    lat = np.array([sum(r.steps[k] for k in SERVE_STEPS) for r in recs])
    assert res["mean_batch_ms"] == pytest.approx(float(lat.mean() * 1e3),
                                                 rel=1e-9)
    assert res["p50_batch_ms"] == pytest.approx(
        float(np.percentile(lat, 50) * 1e3))
    # The store's steps lie inside the loop's lookup step, the flush's
    # inside its flush step.
    tot = {k: sum(r.steps[k] for r in recs) for k in recs[0].steps}
    assert tot["partition_s"] + tot["fetch_s"] + tot["gather_s"] \
        <= tot["lookup_s"]
    assert tot["model_s"] <= tot["flush_s"] + 1e-12
    m = res["metrics"]["counters"]
    assert m["serve.batches"] == len(recs)
    assert m["serve.batch_s"] == pytest.approx(float(lat.sum()), rel=1e-9)
    assert m["serve.forward_s"] == pytest.approx(tot["forward_s"])
    assert res["compute_ms"] == pytest.approx(
        tot["forward_s"] / len(recs) * 1e3)


def _counts(store):
    return {f: getattr(store.stats, f) for f in TierStats.COUNTS}


@pytest.mark.parametrize("capacity,counts", [
    # 3 unique misses fit: 3 rows written through a 16-row bucket (slots
    # 64 B + rows 512 B), one (2, 16) int32 gather operand (128 B).
    (16, {"populate_calls": 0, "rank_passes": 0, "write_rows": 3,
          "overflow_rows": 0, "overflow_full": 0,
          "h2d_bytes": 64 + 512 + 128}),
    # 6 unique misses into 4 slots (LRU): the first 2 overflow and are
    # folded in from their host rows in a 16-row operand (512 B).
    (4, {"populate_calls": 0, "rank_passes": 0, "write_rows": 4,
         "overflow_rows": 2, "overflow_full": 0,
         "h2d_bytes": 64 + 512 + 128 + 512}),
])
def test_step_counts_are_exact(capacity, counts):
    host = np.arange(64 * 8, dtype=np.float32).reshape(64, 8)
    store = TieredEmbeddingStore(host, capacity, policy="lru")
    ids = np.array([1, 2, 3, 1]) if capacity == 16 \
        else np.array([1, 2, 3, 4, 5, 6])
    out = np.asarray(store.lookup(ids))
    np.testing.assert_array_equal(out, host[ids])
    assert _counts(store) == counts
    # Two staged outputs, one flush: two calls; a direct call is a third.
    empty = np.empty(0, np.int64)
    store.stage_model_outputs(empty, empty, np.array([10]))
    store.stage_model_outputs(empty, empty, np.array([11]))
    store.flush_staged()
    store.apply_model_outputs(empty, empty, np.array([12]))
    after = _counts(store)
    assert after["populate_calls"] == 3
    assert after["write_rows"] == counts["write_rows"] + 3
    assert after["h2d_bytes"] == counts["h2d_bytes"] + 3 * (64 + 512)


@pytest.mark.parametrize("policy,passes", [("recmg", 1), ("lru", 0)])
def test_flush_ranks_a_run_in_one_pass(policy, passes):
    """N trunk-only items and one prefetch item: N + 1 populate calls and,
    under RecMG, one rank pass; the flush's span carries both deltas."""
    rng = np.random.default_rng(0)
    host = rng.normal(size=(512, 8)).astype(np.float32)
    store = TieredEmbeddingStore(host, 64, policy=policy)
    store.lookup(np.arange(100))
    before = _counts(store)
    n = 40
    tr = install_tracer(SpanTracer())
    try:
        for _ in range(n):
            store.stage_model_outputs(rng.integers(0, 100, 15),
                                      rng.integers(0, 2, 15),
                                      np.empty(0, np.int64))
        store.stage_model_outputs(rng.integers(0, 100, 15),
                                  rng.integers(0, 2, 15),
                                  np.arange(200, 204))
        store.flush_staged()
    finally:
        install_tracer(None)
    after = _counts(store)
    calls = after["populate_calls"] - before["populate_calls"]
    ranked = after["rank_passes"] - before["rank_passes"]
    assert (calls, ranked) == (n + 1, passes)
    (span,) = tr.spans("store", "populate")
    assert span["args"]["calls"] == calls
    assert span["args"]["rank_passes"] == ranked
    assert span["args"]["pf_rows"] == 4
    assert (store.stats.model_s > 0) == (policy == "recmg")


@pytest.mark.parametrize("policy", ["recmg", "lru"])
def test_traced_batch_spans(served, policy):
    res, recs, tr = served[policy]
    trace = tr.chrome_trace()
    assert validate_chrome_trace(trace) == []
    assert reconcile(metrics=res["metrics"], trace=trace,
                     strict=False) == []
    parents = {"store.partition": "store.lookup",
               "store.admit": "store.lookup",
               "store.slow_read": "store.admit",
               "store.residency": "store.admit",
               "store.write": "store.admit",
               "store.gather": "store.lookup",
               "store.sync": "store.lookup",
               "store.lookup": "", "store.populate": "",
               "serve.outputs": "", "serve.pool": "", "serve.forward": ""}
    if policy == "lru":  # no model outputs: no flush applies anything
        del parents["store.populate"]
    spans = tr.spans()
    seen = {f"{e['cat']}.{e['name']}" for e in spans}
    assert seen == set(parents)
    for e in spans:
        assert e["args"]["parent"] == parents[f"{e['cat']}.{e['name']}"]
    # Every batch's lookup, pooling and forward carry its id; under RecMG
    # one flush (one populate span) follows each batch, the last included.
    n = len(recs)
    for name in ("store.lookup", "serve.pool", "serve.forward"):
        cat, nm = name.split(".")
        assert [e["args"]["batch"] for e in tr.spans(cat, nm)] \
            == list(range(n))
    pops = tr.spans("store", "populate")
    calls = res["metrics"]["counters"]["store.steps.populate_calls"]
    assert sum(e["args"]["calls"] for e in pops) == calls
    assert sum(e["args"]["rank_passes"] for e in pops) \
        == res["metrics"]["counters"]["store.steps.rank_passes"]
    if policy == "recmg":
        assert len(pops) == n and calls > n


@pytest.mark.parametrize("tracer", [NullTracer(), SpanTracer()],
                         ids=["null", "disabled"])
def test_disabled_tracers_record_nothing(tracer):
    host = np.ones((32, 4), np.float32)
    tracer.enabled = False
    install_tracer(tracer)
    try:
        store = TieredEmbeddingStore(host, 8, policy="lru")
        store.lookup(np.arange(12))
        store.apply_model_outputs(np.empty(0), np.empty(0), np.arange(3))
        with tracer.span("serve", "forward") as sp:
            sp.set(x=1)
        steps = Steps(tracer)
        with steps.step(store.stats, "sync_s", "store", "sync"):
            pass
    finally:
        install_tracer(None)
    assert getattr(tracer, "events", []) == []
    assert store.stats.partition_s > 0  # the step counters stay on


def test_spans_land_on_the_profiler_host_plane(tmp_path):
    """On the wall clock every span is also a same-named host event of a
    profiler session, within 1 ms of the span moved onto the profiler's
    clock by one paired reading."""
    from jax.profiler import ProfileData, TraceAnnotation

    from repro.obs.tracing import _WallUs

    host = np.random.default_rng(0).normal(size=(256, 8)).astype(np.float32)
    store = TieredEmbeddingStore(host, 32, policy="lru")
    store.lookup(np.arange(8))  # compile the buckets outside the session
    tr = SpanTracer()
    assert tr.wall and isinstance(tr.clock, _WallUs)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("test.clock"):
            t_mark = time.perf_counter()
        install_tracer(tr)
        try:
            for b in range(3):
                tr.set_batch(b)
                store.lookup(np.arange(b * 40, b * 40 + 60))
                store.apply_model_outputs(np.empty(0), np.empty(0),
                                          np.arange(200, 204))
        finally:
            install_tracer(None)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    events = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                events.setdefault(ev.name, []).append(ev.start_ns)
    offset = events["test.clock"][0] - t_mark * 1e9
    spans = tr.spans()
    assert len(spans) >= 3 * 9
    for e in spans:
        label = f"{e['cat']}.{e['name']}"
        want = e["ts"] * 1e3 + offset
        assert min(abs(s - want) for s in events[label]) < 1e6, label


def test_served_batch_runs_named_programs(served, tmp_path):
    """One served batch runs the write, the pooling and the forward as
    ``jit_store_write``, ``jit_pool_bags`` and ``jit_dense_forward``."""
    from jax.profiler import ProfileData

    from repro.launch.serve import serve_trace

    _, recs, _ = served["lru"]
    rec = recs[0]
    trace = _one_batch_trace(rec)
    jax.profiler.start_trace(str(tmp_path))
    try:
        serve_trace(rec.cfg, rec.params, trace, 64, "lru", None,
                    batch_queries=rec.dense.shape[0])
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    modules = set()
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                try:
                    mod = dict(ev.stats).get("hlo_module")
                except Exception:  # a stat the binding cannot convert
                    continue
                if mod:
                    modules.add(str(mod).split("(", 1)[0])
    assert {"jit_store_write", "jit_pool_bags",
            "jit_dense_forward"} <= modules, sorted(modules)


def _one_batch_trace(rec):
    from repro.core.trace import Trace

    cfg = rec.cfg
    per_table = cfg.rows_per_table
    gid = np.asarray(rec.ids, np.int64)
    return Trace((gid // per_table).astype(np.int32), gid % per_table,
                 np.full(cfg.n_tables, per_table, np.int64))


def test_registry_carries_the_steps(served):
    res, _, _ = served["recmg"]
    flat = MetricsRegistry.from_snapshot(res["metrics"]).as_dict()
    for f in TierStats.SECONDS:
        assert f"store.time.{f}" in flat
    for f in TierStats.COUNTS:
        assert f"store.steps.{f}" in flat
    for f in SERVE_STEPS + ("batches", "batch_s", "h2d_bytes"):
        assert f"serve.{f}" in flat


def test_span_parents_stay_on_their_thread():
    """A span opened on another thread (the prefetch worker's populate
    under ``scheduler="thread"``) neither takes a parent from nor gives
    one to the spans open on the main thread."""
    import threading

    tr = SpanTracer()
    opened, release = threading.Event(), threading.Event()

    def worker():
        with tr.span("store", "populate", track="store"):
            opened.set()
            assert release.wait(10)

    with tr.span("serve", "forward", track="serve"):
        t = threading.Thread(target=worker)
        t.start()
        assert opened.wait(10)
        with tr.span("serve", "pool", track="serve"):
            pass
        release.set()
        t.join()
        with tr.span("serve", "outputs", track="serve"):
            pass
    parents = {f"{e['cat']}.{e['name']}": e["args"]["parent"]
               for e in tr.spans()}
    assert parents == {"store.populate": "", "serve.pool": "serve.forward",
                       "serve.outputs": "serve.forward", "serve.forward": ""}


@pytest.mark.parametrize("scheduler", ["inline", "thread"])
def test_pipelined_spans_keep_their_parents(scheduler):
    """Through the pipelined runtime, the thread scheduler's populate
    spans run on the prefetch worker; every span still names the parent
    it has on its own thread, and the trace validates."""
    from repro.core.recmg import frequency_outputs
    from repro.launch.serve import serve_trace

    cfg, params, trace = _tiny_model()
    cap = int(0.15 * trace.unique_count())
    tr = install_tracer(SpanTracer())
    try:
        serve_trace(cfg, params, trace, cap, "recmg",
                    frequency_outputs(trace, cap), batch_queries=8,
                    async_prefetch=True, scheduler=scheduler)
    finally:
        install_tracer(None)
    assert validate_chrome_trace(tr.chrome_trace()) == []
    parents = {"store.lookup": {""}, "store.partition": {"store.lookup"},
               "store.admit": {"store.lookup"}, "store.gather":
               {"store.lookup"}, "store.sync": {"store.lookup"},
               "store.slow_read": {"store.admit"}, "store.residency":
               {"store.admit"}, "store.write": {"store.admit"},
               "store.populate": {""},
               "serve.pool": {""}, "serve.forward": {""}}
    spans = [e for e in tr.spans() if e["cat"] in ("store", "serve")]
    assert {f"{e['cat']}.{e['name']}" for e in tr.spans("store", "populate")}
    for e in spans:
        label = f"{e['cat']}.{e['name']}"
        assert e["args"]["parent"] in parents[label], (label, e["args"])
