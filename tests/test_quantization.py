"""Quantized fast-tier fidelity suite: per-row round-trip error bounds
(int8 + fp8), host-vs-device quantizer parity, ``lookup_resident`` dequant
parity, and kernel-vs-jit gather equivalence under interpret-mode Pallas
(the CPU lane for the fused dequant kernels)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.tiered import TieredEmbeddingStore
from repro.kernels.embedding_gather import (dequantize_rows_ref,
                                            quantize_rows,
                                            quantize_rows_ref)


@pytest.fixture
def host():
    return np.random.default_rng(7).normal(size=(300, 8)).astype(np.float32)


# ---------------- round-trip error bounds ----------------


def test_int8_roundtrip_error_bound_per_row(host):
    """Acceptance bar: max abs dequant error <= max|row|/127 + eps per
    row — and round-half-even actually achieves half that."""
    q, s = quantize_rows_ref(jnp.asarray(host), "int8")
    back = np.asarray(dequantize_rows_ref(q, s))
    err = np.abs(back - host).max(axis=1)
    amax = np.abs(host).max(axis=1)
    assert (err <= amax / 127.0 + 1e-6).all()
    assert (err <= 0.5 * (amax / 127.0 + 1e-12) + 1e-6).all()


def test_fp8_roundtrip_error_bound_per_row(host):
    """fp8 (e4m3, 3 mantissa bits): relative step 2^-3, so round-to-
    nearest keeps the per-element error within amax/16 per row."""
    q, s = quantize_rows_ref(jnp.asarray(host), "fp8")
    back = np.asarray(dequantize_rows_ref(q, s))
    err = np.abs(back - host).max(axis=1)
    amax = np.abs(host).max(axis=1)
    assert (err <= amax / 16.0 + 1e-6).all()


def test_round_half_even_parity():
    """np.round and jnp.round are both round-half-even — the property the
    host/device quantizer bit-parity rests on."""
    grid = np.arange(-8, 8, 0.5, dtype=np.float32)  # every .5 midpoint
    np.testing.assert_array_equal(np.round(grid),
                                  np.asarray(jnp.round(grid)))


# ---------------- host vs device quantizer parity ----------------


def test_device_quantizer_matches_host_reference(host):
    """The store's fused device-side quantize+scatter produces the exact
    int8 codes the old host NumPy quantizer did (scales may differ by one
    float32 ulp: XLA is free to fuse the scale division differently)."""
    st = TieredEmbeddingStore(host, 64, quantize=True)
    ids = np.arange(64)
    st.lookup(ids)
    rows = host[ids]
    scale = np.abs(rows).max(axis=1) / 127.0 + 1e-12
    q = np.clip(np.round(rows / scale[:, None]), -127, 127).astype(np.int8)
    slots = st._slot_map[ids]
    np.testing.assert_array_equal(np.asarray(st.buffer)[slots], q)
    np.testing.assert_allclose(np.asarray(st.scales)[slots], scale,
                               rtol=2e-7)


@pytest.mark.parametrize("row_format", ["int8", "fp8"])
def test_pallas_quantizer_matches_jnp_reference(host, row_format):
    """The populate-side Pallas kernel and the jnp reference agree on the
    stored codes bit-for-bit (interpret mode; scales to one ulp)."""
    rows = jnp.asarray(host[:32])
    qk, sk = quantize_rows(rows, row_format=row_format, interpret=True)
    qr, sr = quantize_rows_ref(rows, row_format)
    np.testing.assert_array_equal(np.asarray(qk).view(np.uint8),
                                  np.asarray(qr).view(np.uint8))
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), rtol=2e-7)


# ---------------- store-level parity ----------------


@pytest.mark.parametrize("row_format", [None, "fp8"])
def test_lookup_resident_dequant_parity(host, row_format):
    """The degraded read dequantizes host-side; it must return exactly
    what the device gather returns for resident ids."""
    st = TieredEmbeddingStore(host, 32, quantize=True,
                              row_format=row_format)
    ids = np.arange(16)
    out = np.asarray(st.lookup(ids))
    res, n_default = st.lookup_resident(ids)
    assert n_default == 0
    np.testing.assert_array_equal(res, out)


def test_kernel_gather_matches_jit_gather(host):
    """use_kernel=True (interpret) and the default jitted dequant gather
    are bit-identical on the same residency state — the kernel path is a
    drop-in, not an approximation."""
    ids = np.concatenate((np.arange(24), [3, 3, 17]))  # dups + revisit
    st_jit = TieredEmbeddingStore(host, 32, quantize=True)
    st_ker = TieredEmbeddingStore(host, 32, quantize=True,
                                  use_kernel=True, kernel_interpret=True)
    assert st_ker.use_kernel
    out_jit = np.asarray(st_jit.lookup(ids))
    out_ker = np.asarray(st_ker.lookup(ids))
    np.testing.assert_array_equal(out_jit, out_ker)
    for k in ("batches", "lookups", "hits", "misses", "on_demand_rows",
              "evictions"):
        assert st_jit.stats.as_dict()[k] == st_ker.stats.as_dict()[k]
    # Overflow path (working set > capacity): where-select fold included.
    big = np.arange(60)
    np.testing.assert_array_equal(np.asarray(st_jit.lookup(big)),
                                  np.asarray(st_ker.lookup(big)))
    st_ker.check_invariants()


@pytest.mark.parametrize("backend,d,dtype,want", [
    ("tpu", 128, jnp.float32, True),
    ("tpu", 256, jnp.float32, True),
    ("tpu", 128, jnp.int8, False),  # quantized rows: XLA gather on TPU
    ("tpu", 128, jnp.float8_e4m3fn, False),
    ("tpu", 128, jnp.bfloat16, False),
    ("tpu", 96, jnp.float32, False),
    ("cpu", 128, jnp.float32, False),
])
def test_kernel_gather_rule(backend, d, dtype, want):
    """The documented rule (docs/architecture.md, "The quantized fast
    tier"): the compiled Pallas gather serves only 32-bit rows with
    D % 128 == 0 on a TPU."""
    from repro.core.tiered import kernel_gather_ok

    assert kernel_gather_ok(backend, d, dtype) is want


def test_fp8_store_roundtrip(host):
    st = TieredEmbeddingStore(host, 32, quantize=True, row_format="fp8",
                              warmup_batch=32)
    ids = np.array([0, 5, 9, 5])
    out = np.asarray(st.lookup(ids))
    amax = np.abs(host[ids]).max(axis=1)
    assert (np.abs(out - host[ids]).max(axis=1) <= amax / 16.0 + 1e-6).all()


def test_quantized_warmup_preserves_values(host):
    """Warmup re-quantizes slot 0's dequantized row through the fused
    scatter; resident values must survive (requantization maps each code
    back to itself)."""
    st = TieredEmbeddingStore(host, 16, quantize=True)
    ids = np.array([5, 9, 13])
    before = np.asarray(st.lookup(ids))
    st.warmup(64)
    after = np.asarray(st.lookup(ids))
    np.testing.assert_array_equal(before, after)
    assert st.stats.hits == ids.size  # warmup evicted nothing
