"""Tiered embedding store: correctness of returned rows, hit accounting,
prefetch insertion, eviction, and the serving path end to end."""
import jax
import numpy as np
import pytest

from repro.core.tiered import TieredEmbeddingStore


@pytest.fixture
def host():
    rng = np.random.default_rng(0)
    return rng.normal(size=(100, 8)).astype(np.float32)


def test_lookup_returns_correct_rows(host):
    store = TieredEmbeddingStore(host, capacity=16, policy="lru")
    ids = np.array([3, 7, 3, 50])
    out = np.asarray(store.lookup(ids))
    np.testing.assert_allclose(out, host[ids], rtol=1e-6)


def test_hit_accounting(host):
    store = TieredEmbeddingStore(host, capacity=16, policy="lru")
    store.lookup(np.array([1, 2, 3]))
    assert store.stats.hits == 0
    store.lookup(np.array([1, 2, 4]))
    assert store.stats.hits == 2
    assert store.stats.on_demand_rows == 4


def test_eviction_under_capacity(host):
    store = TieredEmbeddingStore(host, capacity=4, policy="lru")
    store.lookup(np.arange(8))  # 8 uniques through a 4-slot buffer
    assert len(store.slot_of) == 4
    out = np.asarray(store.lookup(np.array([7])))
    np.testing.assert_allclose(out[0], host[7], rtol=1e-6)


def test_prefetch_insertion_counts_hits(host):
    store = TieredEmbeddingStore(host, capacity=16, policy="recmg")
    store.apply_model_outputs(np.array([]), np.array([]), np.array([5, 6]))
    store.lookup(np.array([5, 6]))
    assert store.stats.prefetch_hits == 2
    assert store.stats.hits == 2


def test_recmg_priorities_protect_kept_rows(host):
    store = TieredEmbeddingStore(host, capacity=3, policy="recmg")
    store.lookup(np.array([1, 2, 3]))
    # Caching model says: keep 1 (bit=1), not 2, 3.
    store.apply_model_outputs(np.array([1, 2, 3]), np.array([1, 0, 0]),
                              np.array([]))
    store.lookup(np.array([9]))  # forces one eviction
    assert 1 in store.slot_of  # the kept row survived


def test_modeled_fetch_accounting(host):
    store = TieredEmbeddingStore(host, capacity=8, policy="lru",
                                 fetch_us_per_row=10, fetch_us_fixed=0)
    store.lookup(np.arange(8))
    assert store.stats.modeled_fetch_s == pytest.approx(80e-6, rel=1e-6)


def test_serve_trace_smoke():
    from repro.configs import get_config
    from repro.core.trace import TraceGenConfig, generate_trace
    from repro.launch.serve import serve_trace
    from repro.models.dlrm import init_dlrm

    cfg = get_config("dlrm-recmg").reduced()
    params = init_dlrm(jax.random.PRNGKey(0), cfg)
    tr = generate_trace(TraceGenConfig(
        n_tables=cfg.n_tables, rows_per_table=cfg.rows_per_table,
        n_accesses=cfg.n_tables * cfg.multi_hot * 8 * 6))
    res = serve_trace(cfg, params, tr, capacity=64, policy="lru",
                      outputs=None, batch_queries=8)
    assert res["batches"] >= 4
    assert 0.0 <= res["hit_rate"] <= 1.0
    assert res["mean_batch_ms"] > 0


def test_recmg_store_survives_eviction_pressure(host):
    """Regression: priority entries for evicted/non-resident keys must not
    desync the slot map (pipelined model outputs reference old vectors)."""
    store = TieredEmbeddingStore(host, capacity=6, policy="recmg")
    rng = np.random.default_rng(0)
    for step in range(30):
        ids = rng.integers(0, 100, size=8)
        store.lookup(ids)
        # Apply outputs referencing BOTH resident and long-gone keys.
        trunk = rng.integers(0, 100, size=5)
        store.apply_model_outputs(trunk, np.ones(5), rng.integers(0, 100, 3))
        assert len(store.slot_of) <= 6
    out = np.asarray(store.lookup(np.array([1, 2])))
    np.testing.assert_allclose(out, host[[1, 2]], rtol=1e-6)


def test_quantized_store_roundtrip(host):
    st = TieredEmbeddingStore(host, capacity=16, policy="lru", quantize=True)
    ids = np.array([0, 5, 9, 5])
    out = np.asarray(st.lookup(ids))
    err = np.abs(out - host[ids]).max() / np.abs(host).max()
    assert err < 0.02
    # eviction + refill path
    st.lookup(np.arange(40))
    out2 = np.asarray(st.lookup(np.array([0])))
    assert np.abs(out2 - host[[0]]).max() / np.abs(host).max() < 0.02


def test_use_kernel_with_quantize_honored(host):
    """Regression: the constructor used to silently drop an explicit
    ``use_kernel=True`` whenever ``quantize=True`` (``bool(use_kernel)
    and not quantize``).  The combination now routes through the fused
    dequantizing kernel path."""
    st = TieredEmbeddingStore(host, capacity=16, quantize=True,
                              use_kernel=True, kernel_interpret=True)
    assert st.use_kernel  # honored, not downgraded
    ids = np.array([0, 5, 9, 5])
    out = np.asarray(st.lookup(ids))
    assert np.abs(out - host[ids]).max() / np.abs(host).max() < 0.02


def test_fp32_kernel_store_matches_xla_store():
    """The fp32 kernel path (interpret mode) copies only the unique rows of
    each padded bucket and returns exactly what the XLA gather returns,
    overflow fold included."""
    host = np.random.default_rng(1).normal(size=(400, 128)).astype(np.float32)
    xla = TieredEmbeddingStore(host, capacity=48, use_kernel=False)
    ker = TieredEmbeddingStore(host, capacity=48, use_kernel=True,
                               kernel_interpret=True)
    rng = np.random.default_rng(2)
    for n in (20, 37, 90):  # 90 unique-ish ids overflow the 48-row buffer
        ids = rng.integers(0, 400, size=n)
        want = np.asarray(xla.lookup(ids))
        np.testing.assert_array_equal(np.asarray(ker.lookup(ids)), want)
        np.testing.assert_array_equal(want, host[ids])
    ker.check_invariants()


def test_use_kernel_unsupported_combos_raise(host):
    """An explicit ``use_kernel=True`` is a contract: unsupported setups
    raise instead of silently downgrading (auto mode may still fall
    back)."""
    import jax
    if jax.default_backend() != "tpu":
        # Explicit kernel request off-TPU needs the interpret escape hatch.
        with pytest.raises(ValueError, match="TPU backend"):
            TieredEmbeddingStore(host, capacity=16, use_kernel=True)
        with pytest.raises(ValueError, match="TPU backend"):
            TieredEmbeddingStore(host, capacity=16, quantize=True,
                                 use_kernel=True)
    # row_format is a quantized-tier knob.
    with pytest.raises(ValueError, match="requires quantize=True"):
        TieredEmbeddingStore(host, capacity=16, row_format="fp8")
    with pytest.raises(ValueError, match="unknown row_format"):
        TieredEmbeddingStore(host, capacity=16, quantize=True,
                             row_format="int4")
    # Auto mode still silently picks the portable path.
    st = TieredEmbeddingStore(host, capacity=16, quantize=True)
    assert isinstance(st.use_kernel, bool)


def test_tierstats_merge_additive():
    """TierStats.merge: counter additivity and the merged hit rate."""
    from repro.core.tiered import TierStats

    a = TierStats(batches=2, lookups=10, hits=4, prefetch_hits=1,
                  on_demand_rows=6, evictions=3, fetch_s=0.5, gather_s=0.25,
                  model_s=0.125, modeled_fetch_s=1.0)
    b = TierStats(batches=3, lookups=30, hits=24, prefetch_hits=2,
                  on_demand_rows=6, evictions=5, fetch_s=0.5, gather_s=0.75,
                  model_s=0.375, modeled_fetch_s=0.5)
    out = a.merge(b)
    assert out is a  # merges in place and returns self
    assert (a.batches, a.lookups, a.hits) == (5, 40, 28)
    assert (a.prefetch_hits, a.on_demand_rows, a.evictions) == (3, 12, 8)
    assert a.fetch_s == pytest.approx(1.0)
    assert a.gather_s == pytest.approx(1.0)
    assert a.model_s == pytest.approx(0.5)
    assert a.modeled_fetch_s == pytest.approx(1.5)
    # Merged hit rate is recomputed from merged counters, not averaged:
    # (4 + 24) / (10 + 30), not mean(0.4, 0.8).
    assert a.hit_rate == pytest.approx(28 / 40)
    assert a.as_dict()["evictions"] == 8


def test_tierstats_merge_identity():
    from repro.core.tiered import TierStats

    a = TierStats(batches=1, lookups=5, hits=2)
    a.merge(TierStats())
    assert (a.batches, a.lookups, a.hits) == (1, 5, 2)
    assert TierStats().merge(TierStats()).hit_rate == 0.0


def test_eviction_counter(host):
    store = TieredEmbeddingStore(host, capacity=8, policy="lru")
    store.lookup(np.arange(8))
    assert store.stats.evictions == 0
    store.lookup(np.arange(8, 12))  # 4 admissions force 4 evictions
    assert store.stats.evictions == 4


def test_resident_mask(host):
    store = TieredEmbeddingStore(host, capacity=8, policy="lru")
    store.lookup(np.array([1, 2, 3]))
    mask = store.resident_mask(np.array([1, 2, 3, 4]))
    assert mask.tolist() == [True, True, True, False]


# ---------------- shape-bucket edges ----------------


def test_bucket_exact_powers_of_two():
    from repro.core.tiered import _bucket

    assert _bucket(1) == 16 and _bucket(16) == 16  # floor bucket
    for p in (16, 32, 64, 1024):
        assert _bucket(p) == p           # exact power of two: no padding
        assert _bucket(p + 1) == 2 * p   # one past: next bucket
        assert _bucket(p - 1) == p


@pytest.mark.parametrize("policy", ["lru", "recmg"])
def test_capacity_one_store(host, policy):
    """A single-slot buffer: every distinct id evicts the previous one and
    most of each batch is served from the host overflow path."""
    store = TieredEmbeddingStore(host, capacity=1, policy=policy)
    ids = np.array([3, 7, 3, 50, 7, 3])
    out = np.asarray(store.lookup(ids))
    np.testing.assert_allclose(out, host[ids], rtol=1e-6)
    assert store.n_resident == 1
    store.check_invariants()
    out2 = np.asarray(store.lookup(np.arange(40)))
    np.testing.assert_allclose(out2, host[:40], rtol=1e-6)
    store.check_invariants()


@pytest.mark.parametrize("m", [16, 17, 31, 32, 33])
def test_batch_at_bucket_boundary(host, m):
    """Batches exactly at / one past a power-of-two bucket boundary must
    return correct rows (the padded gather slices back to the true size)."""
    store = TieredEmbeddingStore(host, capacity=64, policy="lru")
    ids = np.arange(m) % host.shape[0]
    out = np.asarray(store.lookup(ids))
    np.testing.assert_allclose(out, host[ids], rtol=1e-6)
    # repeat once resident (pure-hit path) and once more after eviction mix
    out = np.asarray(store.lookup(ids[::-1].copy()))
    np.testing.assert_allclose(out, host[ids[::-1]], rtol=1e-6)


def test_warmup_preserves_buffer_contents(host):
    store = TieredEmbeddingStore(host, capacity=16, policy="lru")
    ids = np.array([5, 9, 13])
    store.lookup(ids)
    store.warmup(64)  # compiles buckets 16..64; must not clobber rows
    out = np.asarray(store.lookup(ids))
    np.testing.assert_allclose(out, host[ids], rtol=1e-6)
    assert store.stats.hits == 3  # still resident: warmup didn't evict


def test_warmup_quantized(host):
    store = TieredEmbeddingStore(host, capacity=16, policy="lru",
                                 quantize=True, warmup_batch=32)
    ids = np.array([0, 5, 9])
    out = np.asarray(store.lookup(ids))
    assert np.abs(out - host[ids]).max() / np.abs(host).max() < 0.02
