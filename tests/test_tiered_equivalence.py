"""Batched engine vs. per-key seed reference: identical counters and rows.

The batched ``TieredEmbeddingStore`` must reproduce the seed semantics
exactly — same hit / miss / on-demand / prefetch counters and the same
returned embeddings on a recorded synthetic trace — under both the LRU and
the recmg policy, including eviction pressure and batch overflow.
"""
import numpy as np
import pytest

from repro.core.tiered import TieredEmbeddingStore
from repro.core.tiered_reference import ReferenceTieredStore

COUNTERS = ("batches", "lookups", "hits", "prefetch_hits", "on_demand_rows",
            "evictions")


def _trace(rng, n_rows, n_acc, zipf_a=1.2):
    """Zipf-skewed key stream like the DLRM generator's per-table law."""
    ranks = np.minimum(rng.zipf(zipf_a, size=n_acc), n_rows) - 1
    perm = rng.permutation(n_rows)
    return perm[ranks].astype(np.int64)


def _replay(store, host, ids, batch, rng, prefetch_every=0, bits_every=0):
    """Drive a store through the trace; returns per-batch counter snapshots."""
    snaps = []
    for b in range(len(ids) // batch):
        chunk = ids[b * batch: (b + 1) * batch]
        out = np.asarray(store.lookup(chunk))
        np.testing.assert_allclose(out, host[chunk], rtol=1e-6)
        if bits_every and b % bits_every == 0:
            trunk = chunk[:16]
            bits = (rng.random(len(trunk)) < 0.5).astype(np.int64)
            store.apply_model_outputs(trunk, bits, np.empty(0, np.int64))
        if prefetch_every and b % prefetch_every == 0:
            pf = np.unique(rng.integers(0, host.shape[0], size=8))
            store.apply_model_outputs(
                np.empty(0, np.int64), np.empty(0, np.int64), pf)
        snaps.append(tuple(getattr(store.stats, c) for c in COUNTERS))
    return snaps


@pytest.mark.parametrize("policy,cap", [
    ("lru", 64), ("lru", 17), ("recmg", 64), ("recmg", 23),
])
def test_counters_match_reference(policy, cap):
    rng = np.random.default_rng(0)
    host = rng.normal(size=(500, 8)).astype(np.float32)
    ids = _trace(rng, 500, 6000)
    new = TieredEmbeddingStore(host, cap, policy=policy)
    ref = ReferenceTieredStore(host, cap, policy=policy)
    s_new = _replay(new, host, ids, 48, np.random.default_rng(1),
                    prefetch_every=3, bits_every=2)
    s_ref = _replay(ref, host, ids, 48, np.random.default_rng(1),
                    prefetch_every=3, bits_every=2)
    assert s_new == s_ref
    new.check_invariants()
    assert new.slot_of == ref.slot_of or set(new.slot_of) == set(ref.slot_of)


@pytest.mark.parametrize("policy", ["lru", "recmg"])
def test_batch_overflow_matches_reference(policy):
    """Working set larger than the buffer: overflow rows are served from the
    host tier and the engines agree on every counter."""
    rng = np.random.default_rng(2)
    host = rng.normal(size=(300, 8)).astype(np.float32)
    cap = 16
    new = TieredEmbeddingStore(host, cap, policy=policy)
    ref = ReferenceTieredStore(host, cap, policy=policy)
    for batch in (np.arange(60), np.arange(30, 90), rng.integers(0, 300, 128)):
        o_new = np.asarray(new.lookup(batch))
        o_ref = np.asarray(ref.lookup(batch))
        np.testing.assert_allclose(o_new, host[batch], rtol=1e-6)
        np.testing.assert_allclose(o_ref, host[batch], rtol=1e-6)
    for c in COUNTERS:
        assert getattr(new.stats, c) == getattr(ref.stats, c), c
    assert new.n_resident == len(ref.slot_of) == cap
    new.check_invariants()


@pytest.mark.parametrize("policy,cap", [
    ("lru", 1), ("recmg", 1), ("lru", 2), ("recmg", 2),
])
def test_capacity_one_prefetch_matches_reference(policy, cap):
    """Regression for the PR-4 reference deviation: a multi-key prefetch
    batch at capacity ~1 evicts its own earlier keys mid-admission, and
    the reference used to leave those keys a phantom ``prefetched`` mark
    that inflated ``prefetch_hits`` on their next residency.  With the
    mark scoped to still-resident keys the engines agree at every
    capacity — the property suite's cap range now starts at 1 instead of
    having to avoid it."""
    rng = np.random.default_rng(5)
    host = rng.normal(size=(40, 8)).astype(np.float32)
    ids = _trace(rng, 40, 1200, zipf_a=1.3)
    new = TieredEmbeddingStore(host, cap, policy=policy)
    ref = ReferenceTieredStore(host, cap, policy=policy)
    s_new = _replay(new, host, ids, 8, np.random.default_rng(6),
                    prefetch_every=2, bits_every=3)
    s_ref = _replay(ref, host, ids, 8, np.random.default_rng(6),
                    prefetch_every=2, bits_every=3)
    assert s_new == s_ref
    new.check_invariants()
    assert set(new.slot_of) == set(ref.slot_of)


def test_quantized_counters_match_reference():
    rng = np.random.default_rng(3)
    host = rng.normal(size=(200, 8)).astype(np.float32)
    ids = _trace(rng, 200, 2000)
    new = TieredEmbeddingStore(host, 32, policy="lru", quantize=True)
    ref = ReferenceTieredStore(host, 32, policy="lru", quantize=True)
    for b in range(len(ids) // 64):
        chunk = ids[b * 64: (b + 1) * 64]
        o_new = np.asarray(new.lookup(chunk))
        o_ref = np.asarray(ref.lookup(chunk))
        np.testing.assert_allclose(o_new, o_ref, rtol=1e-6, atol=1e-7)
    for c in COUNTERS:
        assert getattr(new.stats, c) == getattr(ref.stats, c), c


def test_staged_outputs_apply_at_next_boundary():
    """stage_model_outputs must not mutate the store until the next lookup."""
    rng = np.random.default_rng(4)
    host = rng.normal(size=(100, 8)).astype(np.float32)
    st = TieredEmbeddingStore(host, 16, policy="lru")
    st.stage_model_outputs(np.empty(0, np.int64), np.empty(0, np.int64),
                           np.array([5, 6]))
    assert st.n_resident == 0  # nothing applied yet
    st.lookup(np.array([5, 6]))
    assert st.stats.prefetch_hits == 2  # staged prefetch landed first
    assert st.stats.hits == 2


EMPTY = np.empty(0, np.int64)


def _chunks(rng, keys, n, size=15):
    """``n`` trunk-only items of ``size`` ids drawn from ``keys`` (so keys
    repeat across chunks), with random caching bits."""
    return [(rng.choice(keys, size),
             (rng.random(size) < 0.5).astype(np.int64), EMPTY)
            for _ in range(n)]


def _flush_items(case, rng, resident, n_rows):
    """The model outputs one flush applies; returns them and the key the
    flush must leave non-resident (or None)."""
    non_res = np.setdiff1d(np.arange(n_rows), resident)
    if case == "repeated_chunks":
        return _chunks(rng, resident[:24], 80), None
    if case == "non_resident":
        return _chunks(rng, np.arange(n_rows), 80), None
    if case == "prefetch_evicts":
        # Every resident key ranked cache-friendly but ``v``, ranked
        # evict-next: the mid-flush prefetch of one key evicts ``v``,
        # and the trunks after it name ``v`` again.
        v = int(resident[5])
        items = [(c, (c != v).astype(np.int64), EMPTY)
                 for c in np.array_split(resident, len(resident) // 15)]
        items.append((resident[6:21], np.ones(15, np.int64),
                      non_res[:1]))
        return items + [(np.r_[v, c[0]], np.r_[1, c[1]], EMPTY)
                        for c in _chunks(rng, resident, 40)], v
    if case == "ragged":
        return [(t, b[:len(t) - 4] if i % 2 else np.r_[b, b], EMPTY)
                for i, (t, b, _) in enumerate(
                    _chunks(rng, np.arange(n_rows), 80))], None
    assert case == "lru"
    items = _chunks(rng, np.arange(n_rows), 80)
    items[40] = (items[40][0], items[40][1], non_res[:6])
    return items, None


@pytest.mark.parametrize("case,policy", [
    ("repeated_chunks", "recmg"), ("non_resident", "recmg"),
    ("prefetch_evicts", "recmg"), ("ragged", "recmg"), ("lru", "lru"),
])
def test_flush_matches_per_item_reference(case, policy):
    """A flush of many staged items (each run of prefetch-free items
    ranked in one engine pass) leaves the store where the per-item
    reference leaves it: equal counters and residency after the flush and
    at every batch of the eviction-heavy lookups that follow."""
    rng = np.random.default_rng(7)
    host = rng.normal(size=(500, 8)).astype(np.float32)
    cap = 64
    ids = _trace(rng, 500, 4800)
    new = TieredEmbeddingStore(host, cap, policy=policy)
    ref = ReferenceTieredStore(host, cap, policy=policy)
    for b in range(10):  # fill the fast tier
        new.lookup(ids[b * 48: (b + 1) * 48])
        ref.lookup(ids[b * 48: (b + 1) * 48])
    resident = np.array(sorted(ref.slot_of), np.int64)
    assert len(resident) == cap and set(new.slot_of) == set(ref.slot_of)
    items, gone = _flush_items(case, np.random.default_rng(8), resident,
                               host.shape[0])
    for item in items:
        new.stage_model_outputs(*item)
        ref.apply_model_outputs(*item)
    new.flush_staged()
    assert new.stats.populate_calls == len(items)
    if gone is not None:
        assert gone not in new.slot_of and gone not in ref.slot_of
    for b in range(10, len(ids) // 48):
        assert set(new.slot_of) == set(ref.slot_of), b
        for c in COUNTERS:
            assert getattr(new.stats, c) == getattr(ref.stats, c), (b, c)
        chunk = ids[b * 48: (b + 1) * 48]
        np.testing.assert_allclose(np.asarray(new.lookup(chunk)),
                                   host[chunk], rtol=1e-6)
        ref.lookup(chunk)
    new.check_invariants()
