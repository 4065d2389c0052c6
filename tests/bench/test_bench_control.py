"""The check's lower-precision control: at dlrm-recmg's published widths
and pooling, with its 856 tables cut to 26 so that a test run holds the top
MLP, the program's bf16 forward passes ``logit_err`` and the reference
computed in fp8 fails it."""
import jax
import numpy as np
import pytest

from bench import spec
from bench.check import compare, judge, passed
from bench.window import Kept

CELL = "dlrm-recmg.recmg_steady"
TABLES = 26


@pytest.fixture(scope="module")
def batch():
    from repro.launch.serve import _dense_forward

    cell = spec.load_cell(CELL)
    cfg, ref = dict(cell.config, n_tables=TABLES), spec.reference_module(cell)
    params = ref.init_params(cfg, 2**31 + 77)
    rng = np.random.default_rng(3)
    n_rows = sum(spec.table_rows(cfg))
    # Under 2**18 rows: the slow tier's regeneration is one block.
    ids = rng.integers(0, n_rows, size=64 * TABLES * cfg["multi_hot"])
    rows = ref.slow_tier_rows(n_rows, cfg["emb_dim"], ids)
    dense = rng.standard_normal((64, cfg["dense_features"])).astype(np.float32)
    logits = jax.jit(lambda p, d, e: _dense_forward(
        p, spec.model_config(cfg), d, e))(params, dense, ref.pool(rows, cfg))
    return cfg, ref, params, [Kept(0, ids, rows, dense, logits)]


def test_program_passes(batch):
    cfg, ref, params, kept = batch
    r = compare(kept, cfg, params, ref)
    checks = judge(r, cfg)
    assert passed(checks, r), checks


def test_fp8_control_fails(batch):
    cfg, ref, params, kept = batch
    program = compare(kept, cfg, params, ref)["logit_err"]
    r = compare(kept, cfg, params, ref, precision="fp8")
    assert not passed(judge(r, cfg), r)
    assert r["logit_err"] >= 3 * program
