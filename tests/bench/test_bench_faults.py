"""With the timed path broken underneath, a run comes out not correct:
once for each fault a serving cell can have."""
import jax.numpy as jnp
import pytest

from bench import run
from repro.core import tiered
from repro.launch import serve

CELL = "tiny-dlrm.fixture_mix.lru"


def _stale_state(mp):
    # The store admits misses but never writes their rows: state unchanged.
    mp.setattr(tiered.TieredEmbeddingStore, "_write_rows",
               lambda self, slots, rows: None)


def _half_batch(mp):
    # Half of every batch's rows are never gathered.
    real = tiered.TieredEmbeddingStore.lookup

    def lookup(self, ids):
        out = real(self, ids)
        return out.at[out.shape[0] // 2:].set(0.0)

    mp.setattr(tiered.TieredEmbeddingStore, "lookup", lookup)


def _altered_row(mp):
    # One served value changes where the gather produces it.
    real = tiered.TieredEmbeddingStore.lookup

    def lookup(self, ids):
        out = real(self, ids)
        return out.at[0, 0].add(1e-3)

    mp.setattr(tiered.TieredEmbeddingStore, "lookup", lookup)


def _altered_logit(mp):
    # One query's answer changes where the forward produces it.
    real = serve._dense_forward

    def fwd(params, cfg, dense, pooled):
        out = real(params, cfg, dense, pooled)
        return out.at[0].add(jnp.max(jnp.abs(out)) * 0.5 + 1.0)

    mp.setattr(serve, "_dense_forward", fwd)


@pytest.mark.parametrize("fault", [_stale_state, _half_batch, _altered_row,
                                   _altered_logit])
def test_fault_is_not_correct(fixture_root, monkeypatch, fault):
    fault(monkeypatch)
    res = run.run_cell(CELL, 2**31 + 21, 0.3, False, root=fixture_root,
                       bench_dir=fixture_root / "bench", require_tpu=False)
    assert res["correct"] is False
    assert res["failed"] > 0


def test_sound_run_is_correct(fixture_root):
    res = run.run_cell(CELL, 2**31 + 21, 0.3, False, root=fixture_root,
                       bench_dir=fixture_root / "bench", require_tpu=False)
    assert res["correct"] is True, res["checks"]
