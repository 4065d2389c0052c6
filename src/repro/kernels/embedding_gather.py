"""Embedding row-gather, pooled-gather and quantize Pallas TPU kernels.

This is the paper's hot spot: multi-hot lookups into large embedding tables
(TorchRec's fused kernels on GPU).  TPU-native formulation: the table stays
in HBM (``memory_space=ANY``) and every wanted row is copied by its own
DMA, addressed by an index read from SMEM.  Two layout rules of the TPU
compiler shape the kernels:

* A block's last two dimensions must be multiples of the dtype's tile
  (8 x 128 for 32-bit values), so no block is one row high.  Rows are
  copied one at a time by ``make_async_copy`` instead, into an HBM output
  (``gather_rows``) or an aligned VMEM block (the pooled gathers).
* A one-row DMA is accepted only for 32-bit rows: 1- and 2-byte dtypes
  pack several rows per sublane, and the compiler refuses a slice of one
  ("aligned to tiling").  So the compiled path takes float32 / int32
  rows; the quantized (1-byte) variants run in interpret mode only, and
  the tiered store routes quantized rows to the XLA gather on the TPU
  (rule in ``docs/architecture.md``, "The quantized fast tier").

The index vector is never one scalar-prefetch operand: SMEM holds 1 MiB,
and a full-width bucket (524,288 ids) needs 2 MiB.  Indices are instead
blocked ``(n_blocks, 1, K)`` and each grid step reads its ``(1, K)`` block
into SMEM.  At most ``_DMA_WINDOW`` row copies are in flight at a time.

The ``*_dequant`` variants serve the quantized fast tier (SDM's
capacity/precision trade): int8 or fp8 rows with one fp32 scale per row,
dequantized in VMEM.  ``quantize_rows`` is the populate-side kernel: per-row
absmax -> scale -> round/clip over 32-row tiles (the int8 sublane tile).
Row formats (``ROW_FORMATS``): ``int8`` (symmetric, +-127) and ``fp8``
(``float8_e4m3fn``, +-448).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# row format -> (storage dtype, largest representable magnitude the scale
# normalizes to).  Shared by the kernels, the jnp reference, and the store.
ROW_FORMATS = {
    "int8": (jnp.int8, 127.0),
    "fp8": (jnp.float8_e4m3fn, 448.0),
}

_ROW_BLOCK = 1024  # gather_rows: ids per grid step (one SMEM index block)
_POOL_BLOCK = 8  # pooled gathers: queries per grid step (f32 sublane tile)
_QUANT_TILE = 32  # quantize_rows: rows per grid step (int8 sublane tile)
_DMA_WINDOW = 64  # most row copies in flight at once


def _check_compiled_rows(table, interpret: bool, fn: str):
    """The compiled TPU path copies rows one DMA each into 128-lane tiles:
    D must be a multiple of 128 and rows must be 32-bit (narrower dtypes
    pack rows per sublane, and a one-row slice of them is refused).  Fail
    loudly instead; the interpret path has no such constraint."""
    if interpret:
        return
    d = table.shape[-1]
    if d % 128:
        raise ValueError(
            f"{fn}: embedding dim D={d} must be a multiple of 128 (TPU "
            "lane width) on the compiled path — pad the table to a "
            "multiple of 128 or pass interpret=True")
    if np.dtype(table.dtype).itemsize != 4:
        raise ValueError(
            f"{fn}: the compiled path copies one row per DMA, which the "
            f"TPU accepts only for 32-bit rows (got {table.dtype}); pass "
            "interpret=True or gather these rows with XLA")


def _index_blocks(idx: jax.Array, k: int) -> jax.Array:
    """Flat ids -> ``(n_blocks, 1, k)`` int32, zero-padded: one ``(1, k)``
    SMEM block per grid step (a block equal to the array's last two dims
    is tile-legal for any k)."""
    idx = idx.astype(jnp.int32).reshape(-1)
    nb = max(1, pl.cdiv(idx.size, k))
    return jnp.pad(idx, (0, nb * k - idx.size)).reshape(nb, 1, k)


def _smem_block(k: int):
    return pl.BlockSpec((None, 1, k), lambda i, *_: (i, 0, 0),
                        memory_space=pltpu.SMEM)


def _copy_rows(n, copy):
    """Run ``copy(j)`` for j in [0, n) with at most ``_DMA_WINDOW`` copies
    in flight.  All copies share one semaphore and one size, so each wait
    retires one copy's worth; after the drain every copy has landed."""
    def issue(j, carry):
        @pl.when(j >= _DMA_WINDOW)
        def _():
            copy(j - _DMA_WINDOW).wait()

        copy(j).start()
        return carry

    lax.fori_loop(0, n, issue, 0)

    def drain(j, carry):
        copy(j).wait()
        return carry

    lax.fori_loop(jnp.maximum(n - _DMA_WINDOW, 0), n, drain, 0)


def _gather_rows_kernel(n_ref, idx_ref, table_hbm, out_hbm, sem):
    base = pl.program_id(0) * _ROW_BLOCK
    n = jnp.clip(n_ref[0] - base, 0, _ROW_BLOCK)

    def copy(j):
        return pltpu.make_async_copy(
            table_hbm.at[pl.ds(idx_ref[0, j], 1)],
            out_hbm.at[pl.ds(base + j, 1)], sem)

    _copy_rows(n, copy)


def gather_rows(table: jax.Array, idx: jax.Array, n_valid=None, *,
                interpret: bool = False) -> jax.Array:
    """table: (N, D); idx: (M,) -> (M, D) with row m = table[idx[m]] for
    m < ``n_valid`` (default M), no pooling.

    The un-pooled gather the tiered serving buffer uses.  Each row is one
    HBM->HBM DMA; rows at and past ``n_valid`` (a traced scalar is fine)
    are not copied and hold unspecified values, so a caller that pads
    ``idx`` to a shape bucket pays only for the rows it uses.  The
    compiled path needs 32-bit rows and D % 128 == 0 (checked).
    """
    (M,) = idx.shape
    N, D = table.shape
    _check_compiled_rows(table, interpret, "gather_rows")
    blocks = _index_blocks(idx, _ROW_BLOCK)
    n = jnp.minimum(M if n_valid is None else n_valid, M)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(blocks.shape[0],),
        in_specs=[_smem_block(_ROW_BLOCK),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
    )
    return pl.pallas_call(
        _gather_rows_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, D), table.dtype),
        interpret=interpret,
    )(jnp.reshape(n, (1,)).astype(jnp.int32), blocks, table)


def _pooled_blocks(idx: jax.Array) -> jax.Array:
    """(B, P) ids -> (n_blocks, 1, P * _POOL_BLOCK), p-major inside each
    block of ``_POOL_BLOCK`` queries (entry j = p * 8 + b)."""
    B, P = idx.shape
    nb = max(1, pl.cdiv(B, _POOL_BLOCK))
    idx = jnp.pad(idx.astype(jnp.int32), ((0, nb * _POOL_BLOCK - B), (0, 0)))
    idx = idx.reshape(nb, _POOL_BLOCK, P).transpose(0, 2, 1)
    return idx.reshape(nb, 1, P * _POOL_BLOCK)


def _copy_pool_rows(idx_ref, table_hbm, rows, sem, n):
    """DMA the block's ``n = P * 8`` rows into ``rows`` (P, 8, D)."""
    def copy(j):
        return pltpu.make_async_copy(
            table_hbm.at[pl.ds(idx_ref[0, j], 1)],
            rows.at[j // _POOL_BLOCK, pl.ds(j % _POOL_BLOCK, 1)], sem)

    _copy_rows(n, copy)


def _gather_pool_kernel(idx_ref, table_hbm, out_ref, rows, sem):
    _copy_pool_rows(idx_ref, table_hbm, rows, sem,
                    rows.shape[0] * _POOL_BLOCK)
    out_ref[...] = jnp.sum(rows[...].astype(jnp.float32), axis=0)


def gather_pool(table: jax.Array, idx: jax.Array, *,
                interpret: bool = False) -> jax.Array:
    """table: (N, D); idx: (B, P) int32 -> pooled (B, D) = sum_p table[idx].

    Each grid step copies the P rows of 8 queries into a (P, 8, D) VMEM
    block and sums over P into an (8, D) output block.  The compiled path
    needs 32-bit rows and D % 128 == 0 (checked).
    """
    B, P = idx.shape
    N, D = table.shape
    _check_compiled_rows(table, interpret, "gather_pool")
    blocks = _pooled_blocks(idx)
    nb = blocks.shape[0]
    out = pl.pallas_call(
        _gather_pool_kernel,
        grid=(nb,),
        in_specs=[_smem_block(P * _POOL_BLOCK),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((_POOL_BLOCK, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * _POOL_BLOCK, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((P, _POOL_BLOCK, D), table.dtype),
                        pltpu.SemaphoreType.DMA(())],
        interpret=interpret,
    )(blocks, table)
    return out[:B]


# ---------------------------------------------------------------------------
# Quantized fast tier: fused dequantizing gathers + device-side quantizer.
# ---------------------------------------------------------------------------


def _gather_rows_dequant_kernel(idx_ref, sc_ref, table_hbm, out_ref, rows,
                                sem):
    def copy(j):
        return pltpu.make_async_copy(
            table_hbm.at[pl.ds(idx_ref[0, j], 1)], rows.at[pl.ds(j, 1)], sem)

    _copy_rows(_ROW_BLOCK, copy)
    out_ref[...] = rows[...].astype(jnp.float32) * sc_ref[...]


def gather_rows_dequant(table: jax.Array, scales: jax.Array, idx: jax.Array,
                        *, interpret: bool = False) -> jax.Array:
    """table: (N, D) int8/fp8; scales: (N,) fp32; idx: (M,) ->
    (M, D) fp32 = table[idx] * scales[idx, None], dequantized in-kernel.

    Each grid step copies ``_ROW_BLOCK`` quantized rows (D bytes each)
    into VMEM and multiplies them by their gathered scales there — the
    fp32 rows never exist in HBM.  A one-row DMA of 1-byte rows is refused
    by the TPU compiler, so this kernel runs in interpret mode; the
    compiled path raises (see the module docstring).
    """
    (M,) = idx.shape
    N, D = table.shape
    _check_compiled_rows(table, interpret, "gather_rows_dequant")
    blocks = _index_blocks(idx, _ROW_BLOCK)
    nb = blocks.shape[0]
    sc = scales[blocks.reshape(-1)].reshape(-1, 1)
    out = pl.pallas_call(
        _gather_rows_dequant_kernel,
        grid=(nb,),
        in_specs=[_smem_block(_ROW_BLOCK),
                  pl.BlockSpec((_ROW_BLOCK, 1), lambda i: (i, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((_ROW_BLOCK, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * _ROW_BLOCK, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((_ROW_BLOCK, D), table.dtype),
                        pltpu.SemaphoreType.DMA(())],
        interpret=interpret,
    )(blocks, sc, table)
    return out[:M]


def _gather_pool_dequant_kernel(idx_ref, sc_ref, table_hbm, out_ref, rows,
                                sem):
    _copy_pool_rows(idx_ref, table_hbm, rows, sem,
                    rows.shape[0] * _POOL_BLOCK)
    out_ref[...] = jnp.sum(rows[...].astype(jnp.float32) * sc_ref[...],
                           axis=0)


def gather_pool_dequant(table: jax.Array, scales: jax.Array, idx: jax.Array,
                        *, interpret: bool = False) -> jax.Array:
    """table: (N, D) int8/fp8; scales: (N,); idx: (B, P) ->
    (B, D) fp32 = sum_p table[idx] * scales[idx].

    The pooled variant sums *dequantized* rows in VMEM, so pooling never
    materialises per-hot fp32 rows in HBM.  Interpret mode only for 1-byte
    rows, like :func:`gather_rows_dequant`.
    """
    B, P = idx.shape
    N, D = table.shape
    _check_compiled_rows(table, interpret, "gather_pool_dequant")
    blocks = _pooled_blocks(idx)
    nb = blocks.shape[0]
    sc = scales[blocks].reshape(nb, P, _POOL_BLOCK, 1)
    out = pl.pallas_call(
        _gather_pool_dequant_kernel,
        grid=(nb,),
        in_specs=[_smem_block(P * _POOL_BLOCK),
                  pl.BlockSpec((None, P, _POOL_BLOCK, 1),
                               lambda i: (i, 0, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((_POOL_BLOCK, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * _POOL_BLOCK, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((P, _POOL_BLOCK, D), table.dtype),
                        pltpu.SemaphoreType.DMA(())],
        interpret=interpret,
    )(blocks, sc, table)
    return out[:B]


def _quantize_rows_kernel(rows_ref, q_ref, scale_ref, *, row_format):
    qdtype, qmax = ROW_FORMATS[row_format]
    rows = rows_ref[...].astype(jnp.float32)
    scale = jnp.max(jnp.abs(rows), axis=1, keepdims=True) / qmax + 1e-12
    y = rows / scale
    if row_format == "int8":
        # jnp.round is round-half-even, bit-identical to np.round — the
        # fidelity suite pins host/device quantizer parity on that.
        y = jnp.clip(jnp.round(y), -qmax, qmax)
    q_ref[...] = y.astype(qdtype)
    scale_ref[...] = scale


def quantize_rows(rows: jax.Array, *, row_format: str = "int8",
                  interpret: bool = False):
    """rows: (M, D) float -> ((M, D) quantized, (M,) fp32 per-row scales).

    The populate-side kernel: each grid step takes a 32-row tile (the
    int8 sublane tile; M is zero-padded to it), computes the per-row
    absmax, derives ``scale = absmax / qmax + 1e-12`` and round/clips
    (int8) or narrows (fp8) in VMEM — the device-side twin of the host
    NumPy quantizer.  D must be a multiple of 128 on the non-interpret
    path (checked).
    """
    if row_format not in ROW_FORMATS:
        raise ValueError(f"unknown row_format {row_format!r} "
                         f"(expected one of {sorted(ROW_FORMATS)})")
    M, D = rows.shape
    rows = rows.astype(jnp.float32)
    _check_compiled_rows(rows, interpret, "quantize_rows")
    qdtype, _ = ROW_FORMATS[row_format]
    mp = max(1, pl.cdiv(M, _QUANT_TILE)) * _QUANT_TILE
    q, scales = pl.pallas_call(
        functools.partial(_quantize_rows_kernel, row_format=row_format),
        grid=(mp // _QUANT_TILE,),
        in_specs=[pl.BlockSpec((_QUANT_TILE, D), lambda m: (m, 0))],
        out_specs=[pl.BlockSpec((_QUANT_TILE, D), lambda m: (m, 0)),
                   pl.BlockSpec((_QUANT_TILE, 1), lambda m: (m, 0))],
        out_shape=[jax.ShapeDtypeStruct((mp, D), qdtype),
                   jax.ShapeDtypeStruct((mp, 1), jnp.float32)],
        interpret=interpret,
    )(jnp.pad(rows, ((0, mp - M), (0, 0))))
    return q[:M], scales[:M, 0]


def quantize_rows_ref(rows: jax.Array, row_format: str = "int8"):
    """jnp reference for :func:`quantize_rows` (also the store's default
    device-side quantizer off the kernel path) — same scale derivation,
    same round-half-even, so host NumPy / jnp / Pallas agree bit-for-bit
    on fp32 inputs."""
    if row_format not in ROW_FORMATS:
        raise ValueError(f"unknown row_format {row_format!r} "
                         f"(expected one of {sorted(ROW_FORMATS)})")
    qdtype, qmax = ROW_FORMATS[row_format]
    rows = rows.astype(jnp.float32)
    scales = jnp.max(jnp.abs(rows), axis=1) / qmax + 1e-12
    y = rows / scales[:, None]
    if row_format == "int8":
        y = jnp.clip(jnp.round(y), -qmax, qmax)
    return y.astype(qdtype), scales


def dequantize_rows_ref(q: jax.Array, scales: jax.Array) -> jax.Array:
    """Dequantization oracle: (M, D) quantized + (M,) scales -> (M, D) fp32."""
    return q.astype(jnp.float32) * scales[:, None]
