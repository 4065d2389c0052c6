"""The overflow fold ships only the overflow rows to the device gather.

A batch whose unique working set is larger than the fast tier evicts some
of its own rows before the gather reads them; those rows come from the host
in an ``(ob, D)`` operand, where ``ob`` is the bucket's small overflow size
(1/64 of it, at least 16) or, past that, the whole bucket, and the requests
for them point past the bucket, into that operand.  The rows served are the
same bits as the where-select over a full-bucket staging array that the
fold replaces."""
import jax
import numpy as np
import pytest

from repro.core import tiered
from repro.core.tiered import TieredEmbeddingStore
from repro.obs.tracing import SpanTracer, install_tracer

N_IDS = 4096  # one gather bucket: the small overflow operand is 64 rows
SMALL = tiered._small_overflow(N_IDS)
UNIQ = 3000  # distinct ids of the batch; the rest repeat them


def _batch(seed):
    """``N_IDS`` ids over ``UNIQ`` distinct keys of a 5,000-row table,
    shuffled, so the unique->request expansion repeats rows."""
    rng = np.random.default_rng(seed)
    keys = rng.choice(5000, UNIQ, replace=False)
    ids = np.concatenate([keys, rng.choice(keys, N_IDS - UNIQ)])
    return rng.permutation(ids)


def _old_fold(store, ids):
    """What the where-select over a full-bucket staging array served: the
    fast tier's row where the key is resident after the batch, the host's
    row where it overflowed."""
    slots = store._slot_map[ids]
    res = slots >= 0
    fast = np.asarray(store.buffer)[np.maximum(slots, 0)]
    if store.quantize:
        fast = fast.astype(np.float32) \
            * np.asarray(store.scales)[np.maximum(slots, 0)][:, None]
    return np.where(res[:, None], fast,
                    store.host[ids].astype(fast.dtype, copy=False))


STORES = {
    "xla": dict(use_kernel=False),
    "kernel": dict(use_kernel=True, kernel_interpret=True),
    "quantized": dict(use_kernel=False, quantize=True),
}


@pytest.mark.parametrize("policy", ["lru", "recmg"])
@pytest.mark.parametrize("store_kind", list(STORES))
@pytest.mark.parametrize("n_over", [0, 1, SMALL - 1, SMALL, SMALL + 1,
                                    UNIQ - 1])
def test_overflow_rows_bit_equal(store_kind, policy, n_over):
    """An empty fast tier of ``UNIQ - n_over`` rows overflows exactly
    ``n_over`` unique rows of the batch (the last, ``UNIQ - 1``, at
    capacity 1); the rows served equal the old fold's bit for bit, and
    the host's too wherever the rows are fp32."""
    host = np.random.default_rng(7).normal(size=(5000, 16)).astype(
        np.float32)
    store = TieredEmbeddingStore(host, UNIQ - n_over, policy=policy,
                                 **STORES[store_kind])
    ids = _batch(n_over)
    tr = install_tracer(SpanTracer())
    try:
        out = store.lookup_host(ids)
    finally:
        install_tracer(None)
    assert store.stats.overflow_rows == n_over
    assert store.stats.overflow_full == int(n_over > SMALL)
    np.testing.assert_array_equal(out, _old_fold(store, ids))
    if not store.quantize:
        np.testing.assert_array_equal(out, host[ids])
    (gather,) = [e for e in tr.events if e.get("name") == "gather"]
    if n_over:
        assert gather["args"]["overflow_rows"] == n_over
        assert gather["args"]["overflow_bucket"] == (
            SMALL if n_over <= SMALL else N_IDS)
    else:
        assert "overflow_rows" not in gather["args"]
    store.check_invariants()


def test_overflow_h2d_bytes_are_exact():
    """4,096 ids, 20 of whose rows overflow: the gather sends its index
    operand and 64 rows, not a 4,096-row staging array and its mask."""
    d = 128
    host = np.random.default_rng(3).normal(size=(5000, d)).astype(
        np.float32)
    store = TieredEmbeddingStore(host, UNIQ - 20, policy="lru")
    ids = _batch(20)
    np.testing.assert_array_equal(store.lookup_host(ids), host[ids])
    st = store.stats
    assert (st.write_rows, st.overflow_rows, st.overflow_full) == \
        (UNIQ - 20, 20, 0)
    write = 4096 * (4 + d * 4)  # UNIQ - 20 rows through a 4,096-row bucket
    gather = 2 * N_IDS * 4 + SMALL * d * 4
    assert st.h2d_bytes == write + gather
    assert SMALL == 64


def test_warmup_compiles_every_overflow_size():
    """After ``warmup(batch_hint)``, lookups that ship no overflow, the
    small operand and the whole bucket compile no gather or write."""
    host = np.random.default_rng(5).normal(size=(5000, 16)).astype(
        np.float32)
    stores = [TieredEmbeddingStore(host, UNIQ - n, policy="lru",
                                   warmup_batch=N_IDS)
              for n in (0, SMALL, SMALL + 1)]
    progs = (tiered._JIT_GATHER, tiered._JIT_GATHER_OV,
             tiered._JIT_STORE_WRITE)
    before = [p._cache_size() for p in progs]
    for store, full in zip(stores, (0, 0, 1)):
        ids = _batch(3)
        np.testing.assert_array_equal(store.lookup_host(ids), host[ids])
        assert store.stats.overflow_full == full
    assert [p._cache_size() for p in progs] == before
    assert [s.stats.overflow_rows for s in stores] == [0, SMALL, SMALL + 1]


@pytest.mark.parametrize("bsz,small", [
    (16, 16), (512, 16), (4096, 64), (131_072, 2048),
])
def test_small_overflow(bsz, small):
    assert tiered._small_overflow(bsz) == small


def test_kernel_copies_only_the_rows_the_expansion_reads():
    """Requests for overflow rows point past the bucket: the Pallas gather
    copies the unique rows up to the largest entry below it, and no
    further."""
    iv = np.zeros((2, 16), np.int32)
    iv[1, :6] = [0, 3, 16, 2, 17, 3]  # unique rows 0-3; two overflowed
    assert int(tiered._n_unique(jax.numpy.asarray(iv))) == 4
    iv[1, :6] = [0, 1, 16, 1, 17, 18]  # the last unique rows overflowed
    assert int(tiered._n_unique(jax.numpy.asarray(iv))) == 2
