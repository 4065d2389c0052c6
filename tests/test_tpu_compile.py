"""The serve path's TPU programs compile for a described v5e chip.

Nothing here runs on a chip: each test lowers and compiles one program at
the published width (D = 128) for one device of a described ``v5e:2x2``
topology, so the TPU compiler checks block tiling, SMEM and VMEM budgets
without a TPU attached.  The topology is described inside a fixture (never
at import), and the persistent compilation cache is off around the
compiles: entries compiled for a described chip cannot be read back here.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import tiered
from repro.kernels import embedding_gather as eg

D = 128
TABLES, POOLING, QUERIES = 856, 20, 16
# A 16-query batch at published widths is 16 * 856 * 20 = 273,920 ids; the
# store pads it to this power-of-two bucket.
FULL_BUCKET = tiered._bucket(QUERIES * TABLES * POOLING)
# The overflow operand a batch of that bucket ships when few rows overflow.
SMALL_OVERFLOW = tiered._small_overflow(FULL_BUCKET)
BUFFER_ROWS = 200_000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_bucket_is_full_width():
    assert FULL_BUCKET == 524_288
    assert SMALL_OVERFLOW == 8_192


def test_gather_rows_full_bucket(spec):
    text = _compile(lambda t, i, n: eg.gather_rows(t, i, n),
                    spec((BUFFER_ROWS, D), jnp.float32),
                    spec((FULL_BUCKET,), jnp.int32), spec((), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("overflow", [False, True])
def test_store_kernel_lookup_full_bucket(spec, overflow):
    """The store's compiled lookup program (kernel gather + unique->request
    expansion, with and without the overflow fold of a small overflow
    operand) at the full bucket."""
    g, gov = tiered._kernel_gathers(quantized=False)
    args = [spec((BUFFER_ROWS, D), jnp.float32),
            spec((2, FULL_BUCKET), jnp.int32)]
    if overflow:
        args.append(spec((SMALL_OVERFLOW, D), jnp.float32))
    text = (gov if overflow else g).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_gather_pool_published_batch(spec):
    text = _compile(eg.gather_pool, spec((BUFFER_ROWS, D), jnp.float32),
                    spec((QUERIES * TABLES, POOLING), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("row_format", ["int8", "fp8"])
def test_quantize_rows_full_bucket(spec, row_format):
    text = _compile(lambda r: eg.quantize_rows(r, row_format=row_format),
                    spec((FULL_BUCKET, D), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("row_format", ["int8", "fp8"])
def test_quantized_store_xla_rule_full_bucket(spec, row_format):
    """A quantized fast tier on the TPU takes the XLA gather
    (``kernel_gather_ok`` is False for 1-byte rows): its lookup programs
    compile at the full bucket, with no Pallas call in them."""
    qdtype = eg.ROW_FORMATS[row_format][0]
    assert not tiered.kernel_gather_ok("tpu", D, qdtype)
    buf = spec((BUFFER_ROWS, D), qdtype)
    sc = spec((BUFFER_ROWS,), jnp.float32)
    iv = spec((2, FULL_BUCKET), jnp.int32)
    texts = [
        tiered._JIT_GATHER_Q.lower(buf, sc, iv).compile().as_text(),
        tiered._JIT_GATHER_Q_OV.lower(
            buf, sc, iv,
            spec((SMALL_OVERFLOW, D), jnp.float32)).compile().as_text(),
    ]
    assert not any("tpu_custom_call" in t for t in texts)
