"""The serve launcher at published widths: what it builds on the device,
how it sizes the trace and the host table, and what the probe sees."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.trace import TraceGenConfig, generate_trace
from repro.launch import serve
from repro.models.dlrm import init_dlrm, init_dlrm_dense


@pytest.fixture
def no_cache_change(monkeypatch, tmp_path):
    """``serve.main`` leaves JAX's cache alone when this variable is set,
    so a test run never turns the persistent cache on for its process."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def test_dense_init_builds_no_embedding_table():
    cfg = get_config("dlrm-recmg").reduced()
    dense = init_dlrm_dense(jax.random.PRNGKey(0), cfg)
    full = init_dlrm(jax.random.PRNGKey(0), cfg)
    assert set(dense) == {"bottom", "top"}
    for part in ("bottom", "top"):
        for a, b in zip(jax.tree.leaves(dense[part]),
                        jax.tree.leaves(full[part])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_serve_main_params_have_no_emb(no_cache_change):
    seen = []
    serve.main(["--policy", "lru", "--batches", "2", "--batch-queries", "4"],
               probe=lambda rec: seen.append(sorted(rec.params)))
    assert seen and all(keys == ["bottom", "top"] for keys in seen)


def test_batches_size_the_trace(no_cache_change):
    cfg = get_config("dlrm-recmg").reduced()
    res = serve.main(["--policy", "lru", "--batches", "3",
                      "--batch-queries", "8"])
    assert res["batches"] == 3
    assert res["lookups"] == 3 * 8 * cfg.n_tables * cfg.multi_hot


def test_batch_wider_than_old_default_trace():
    """At published T and P one 16-query batch holds more ids than the old
    200,000-access default trace; a trace sized in batches serves them."""
    pub = get_config("dlrm-recmg")
    assert 16 * pub.n_tables * pub.multi_hot > 200_000
    cfg = dataclasses.replace(pub.reduced(), n_tables=100, multi_hot=130,
                              rows_per_table=64)
    per_batch = 16 * cfg.n_tables * cfg.multi_hot
    assert per_batch > 200_000
    tr = generate_trace(TraceGenConfig(
        n_tables=cfg.n_tables, rows_per_table=cfg.rows_per_table,
        n_accesses=2 * per_batch, drift_every=10**9))
    params = init_dlrm_dense(jax.random.PRNGKey(0), cfg)
    res = serve.serve_trace(cfg, params, tr, 512, "lru", None,
                            batch_queries=16)
    assert res["batches"] == 2


def test_trace_shorter_than_a_batch_raises():
    cfg = get_config("dlrm-recmg").reduced()
    tr = generate_trace(TraceGenConfig(
        n_tables=cfg.n_tables, rows_per_table=cfg.rows_per_table,
        n_accesses=100, drift_every=10**9))
    params = init_dlrm_dense(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="fewer than one batch"):
        serve.serve_trace(cfg, params, tr, 64, "lru", None, batch_queries=8)


def test_host_table_float32_and_seeded():
    a = serve.make_host_table(5000, 16, seed=3)
    b = serve.make_host_table(5000, 16, seed=3)
    c = serve.make_host_table(5000, 16, seed=4)
    assert a.dtype == np.float32 and a.shape == (5000, 16)
    np.testing.assert_array_equal(a.view(np.uint32), b.view(np.uint32))
    assert not np.array_equal(a, c)
    assert abs(float(a.mean())) < 0.02 and abs(float(a.std()) - 1) < 0.02


@pytest.mark.parametrize("mem_gib,want", [
    (40, serve.CUT_ROWS_PER_TABLE),  # one-chip machine: 31.9 GB won't fit
    (64, 72_704),
    (None, serve.CUT_ROWS_PER_TABLE),
])
def test_published_rows_per_table(mem_gib, want):
    mem = None if mem_gib is None else mem_gib << 30
    assert serve.published_rows_per_table(get_config("dlrm-recmg"),
                                          mem) == want


@pytest.mark.parametrize("async_prefetch", [False, True])
def test_probe_sees_served_rows(async_prefetch):
    """Every batch the probe sees holds exactly ``host[ids]`` and the ids
    follow the trace in order, on the sync and the pipelined path."""
    cfg = get_config("dlrm-recmg").reduced()
    tr = generate_trace(TraceGenConfig(
        n_tables=cfg.n_tables, rows_per_table=cfg.rows_per_table,
        n_accesses=5 * 8 * cfg.n_tables * cfg.multi_hot, drift_every=10**9))
    params = init_dlrm_dense(jax.random.PRNGKey(0), cfg)
    seen = []

    def probe(rec):
        np.testing.assert_array_equal(np.asarray(rec.rows),
                                      rec.host[rec.ids])
        assert rec.logits.shape == (8,)
        seen.append(rec.ids)

    res = serve.serve_trace(cfg, params, tr, 64, "lru", None,
                            batch_queries=8, async_prefetch=async_prefetch,
                            probe=probe)
    assert len(seen) == res["batches"] == 5
    np.testing.assert_array_equal(np.concatenate(seen), tr.global_id)
