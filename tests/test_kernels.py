"""Pallas kernels vs pure-jnp oracles, swept over shapes/dtypes in
interpret mode (assignment deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.chamfer_kernel import chamfer
from repro.kernels.embedding_gather import (dequantize_rows_ref,
                                            gather_pool,
                                            gather_pool_dequant,
                                            gather_rows,
                                            gather_rows_dequant,
                                            quantize_rows,
                                            quantize_rows_ref)
from repro.kernels.flash_attention import flash_attention


@pytest.mark.parametrize("N,D,B,P", [
    (256, 128, 8, 4),
    (1000, 128, 16, 7),
    (512, 256, 4, 1),
    (64, 128, 32, 20),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gather_pool(N, D, B, P, dtype):
    key = jax.random.PRNGKey(0)
    table = jax.random.normal(key, (N, D), dtype)
    idx = jax.random.randint(jax.random.PRNGKey(1), (B, P), 0, N)
    out = gather_pool(table, idx, interpret=True)
    want = ref.gather_pool_ref(table, idx)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(out, want, rtol=tol, atol=tol)


@pytest.mark.parametrize("N,D,M", [
    (256, 128, 16),
    (1000, 128, 64),
    (64, 256, 1),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gather_rows(N, D, M, dtype):
    """Un-pooled row gather (the tiered store's device path): exact match
    with table[idx], duplicates included."""
    table = jax.random.normal(jax.random.PRNGKey(0), (N, D), dtype)
    idx = jax.random.randint(jax.random.PRNGKey(1), (M,), 0, N)
    idx = idx.at[0].set(idx[-1])  # force a duplicate
    out = gather_rows(table, idx, interpret=True)
    assert out.dtype == table.dtype
    np.testing.assert_array_equal(np.asarray(out), np.asarray(table[idx]))


@pytest.mark.parametrize("M,n_valid", [(16, 5), (1030, 1025), (2048, 0)])
def test_gather_rows_n_valid(M, n_valid):
    """Only the first ``n_valid`` rows are copied (a padded bucket pays for
    the rows it uses); those match table[idx] exactly, across SMEM index
    blocks, with ``n_valid`` traced."""
    table = jax.random.normal(jax.random.PRNGKey(0), (300, 128), jnp.float32)
    idx = jax.random.randint(jax.random.PRNGKey(1), (M,), 0, 300)
    out = jax.jit(lambda t, i, n: gather_rows(t, i, n, interpret=True))(
        table, idx, jnp.int32(n_valid))
    assert out.shape == (M, 128)
    np.testing.assert_array_equal(np.asarray(out[:n_valid]),
                                  np.asarray(table[idx[:n_valid]]))


def test_one_byte_rows_refused_on_compiled_path():
    """The TPU compiler refuses a one-row DMA of 1-byte rows, so the
    compiled path says so instead of failing inside Mosaic."""
    q, s = quantize_rows_ref(jnp.zeros((16, 128), jnp.float32), "int8")
    with pytest.raises(ValueError, match="32-bit rows"):
        gather_rows_dequant(q, s, jnp.zeros((4,), jnp.int32))
    with pytest.raises(ValueError, match="32-bit rows"):
        gather_rows(jnp.zeros((16, 128), jnp.bfloat16),
                    jnp.zeros((4,), jnp.int32))


@pytest.mark.parametrize("N,D,M", [
    (256, 128, 16),
    (64, 256, 33),
])
@pytest.mark.parametrize("row_format", ["int8", "fp8"])
def test_gather_rows_dequant(N, D, M, row_format):
    """Fused dequantizing gather == gather-then-dequantize oracle, bit
    for bit (both multiply the same codes by the same fp32 scales)."""
    rows = jax.random.normal(jax.random.PRNGKey(0), (N, D), jnp.float32)
    q, s = quantize_rows_ref(rows, row_format)
    idx = jax.random.randint(jax.random.PRNGKey(1), (M,), 0, N)
    idx = idx.at[0].set(idx[-1])  # force a duplicate
    out = gather_rows_dequant(q, s, idx, interpret=True)
    assert out.dtype == jnp.float32
    want = dequantize_rows_ref(q, s)[idx]
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("N,D,B,P", [
    (256, 128, 8, 4),
    (100, 128, 16, 7),
])
@pytest.mark.parametrize("row_format", ["int8", "fp8"])
def test_gather_pool_dequant(N, D, B, P, row_format):
    rows = jax.random.normal(jax.random.PRNGKey(0), (N, D), jnp.float32)
    q, s = quantize_rows_ref(rows, row_format)
    idx = jax.random.randint(jax.random.PRNGKey(1), (B, P), 0, N)
    out = gather_pool_dequant(q, s, idx, interpret=True)
    want = dequantize_rows_ref(q, s)[idx].sum(axis=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_lane_width_validated_on_compiled_path():
    """D % 128 != 0 must fail loudly on the non-interpret path (the docs
    promised the constraint; now it's checked) and still run under
    interpret mode."""
    table = jnp.zeros((16, 96), jnp.float32)
    idx = jnp.zeros((4,), jnp.int32)
    pooled_idx = jnp.zeros((4, 2), jnp.int32)
    q, s = quantize_rows_ref(table, "int8")
    for call in (lambda: gather_rows(table, idx),
                 lambda: gather_pool(table, pooled_idx),
                 lambda: gather_rows_dequant(q, s, idx),
                 lambda: gather_pool_dequant(q, s, pooled_idx),
                 lambda: quantize_rows(table)):
        with pytest.raises(ValueError, match="multiple of 128"):
            call()
    # interpret mode has no lane constraint
    out = gather_rows(table, idx, interpret=True)
    assert out.shape == (4, 96)


@pytest.mark.parametrize("B,P,W,F,block", [
    (64, 5, 15, 25, 32),
    (100, 5, 15, 25, 64),  # ragged batch vs block
    (16, 3, 9, 8, 16),
    (257, 7, 21, 16, 128),
])
def test_chamfer_kernel(B, P, W, F, block):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    po = jax.random.normal(k1, (B, P, F))
    w = jax.random.normal(k2, (B, W, F))
    out = chamfer(po, w, 0.7, block=block, interpret=True)
    want = ref.chamfer_ref(po, w, 0.7)
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("BH,S,hd,bq,bk", [
    (2, 128, 64, 64, 64),
    (4, 256, 64, 64, 128),
    (1, 512, 128, 128, 128),
    (3, 256, 32, 256, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_kernel(BH, S, hd, bq, bk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (BH, S, hd), dtype)
    k = jax.random.normal(ks[1], (BH, S, hd), dtype)
    v = jax.random.normal(ks[2], (BH, S, hd), dtype)
    out = flash_attention(q, k, v, bq=bq, bk=bk, interpret=True)
    want = ref.flash_attention_ref(q, k, v)
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


@pytest.mark.parametrize("B,In,H,block", [
    (64, 27, 40, 32),
    (100, 16, 64, 64),   # ragged batch
    (8, 8, 8, 8),
])
def test_lstm_cell_kernel(B, In, H, block):
    from repro.kernels.lstm_cell import lstm_cell

    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (B, In))
    h = jax.random.normal(ks[1], (B, H))
    c = jax.random.normal(ks[2], (B, H))
    w = jax.random.normal(ks[3], (In + H, 4 * H)) * 0.2
    b = jax.random.normal(ks[4], (4 * H,)) * 0.1
    h2, c2 = lstm_cell(x, h, c, w, b, block=block, interpret=True)
    h_ref, c_ref = ref.lstm_cell_ref(x, h, c, w, b)
    np.testing.assert_allclose(h2, h_ref, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(c2, c_ref, rtol=2e-5, atol=2e-5)


def test_lstm_cell_matches_core_lstm_step():
    from repro.core import lstm as LS
    from repro.kernels.lstm_cell import lstm_cell

    p = LS.lstm_init(jax.random.PRNGKey(0), 12, 16)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 12))
    h = jnp.zeros((4, 16))
    c = jnp.zeros((4, 16))
    (h_ref, c_ref), _ = LS.lstm_step(p, (h, c), x)
    h2, c2 = lstm_cell(x, h, c, p["w"], p["b"], block=4, interpret=True)
    np.testing.assert_allclose(h2, h_ref, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(c2, c_ref, rtol=2e-5, atol=2e-5)


def test_ops_wrappers_fall_back_on_cpu():
    from repro.kernels import ops

    table = jnp.ones((16, 128))
    idx = jnp.zeros((2, 3), jnp.int32)
    out = ops.gather_pool(table, idx, use_pallas=True)  # CPU -> jnp ref
    np.testing.assert_allclose(out, 3 * np.ones((2, 128)))
