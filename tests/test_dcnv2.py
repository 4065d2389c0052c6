"""DLRM-DCNv2 in the program: per-feature pooling in ``pool_bags`` (one
bag size pools as it always did, bit for bit), the low-rank cross network
against a per-layer loop, the forward's dispatch on ``cfg.interaction``,
and the pooling's counter and span arguments, which reconcile."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.launch.serve import pool_bags
from repro.models.dlrm import (forward_pooled, init_dlrm_dense,
                               low_rank_cross, top_inputs)

POOLING = (1, 3, 1, 8)


def _reshape_sum(rows, queries, tables, bag):
    """``pool_bags`` as it pooled one bag size for every table: pad, then
    one reshape-sum."""
    pad = queries * tables * bag - rows.shape[0]
    if pad:
        rows = jnp.concatenate([rows, jnp.zeros((pad, rows.shape[1]),
                                                rows.dtype)])
    return rows.reshape(queries, tables, bag, rows.shape[1]).sum(axis=2)


@pytest.mark.parametrize("queries,tables,bag,served", [
    (4, 856, 20, 4 * 856 * 20), (3, 5, 2, 3 * 5 * 2), (6, 4, 3, 50)])
def test_one_bag_size_pools_bit_for_bit_as_before(queries, tables, bag,
                                                  served):
    rows = jax.random.normal(jax.random.key(queries), (served, 16),
                             jnp.float32) * 7.0
    got = pool_bags(rows, queries, (bag,) * tables)
    want = jax.jit(_reshape_sum, static_argnums=(1, 2, 3))(
        rows, queries, tables, bag)
    assert got.shape == (queries, tables, 16)
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


@pytest.mark.parametrize("queries,served", [(5, 5 * 13), (5, 3 * 13 + 4)])
def test_uneven_bags_are_segment_sums(queries, served):
    """Each query lists table 0's 1 row, then table 1's 3, table 2's 1 and
    table 3's 8; a partial batch is zero-padded."""
    rng = np.random.default_rng(0)
    rows = rng.standard_normal((served, 8)).astype(np.float32)
    got = np.asarray(pool_bags(jnp.asarray(rows), queries, POOLING))
    full = np.zeros((queries * 13, 8), np.float64)
    full[:served] = rows
    starts = np.concatenate([[0], np.cumsum(POOLING)[:-1]])
    want = np.add.reduceat(full.reshape(queries, 13, 8), starts, axis=1)
    assert got.shape == (queries, 4, 8)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_low_rank_cross_is_the_per_layer_loop():
    rng = np.random.default_rng(1)
    x_w, r, n = 24, 5, 3
    cross = {"v": [rng.standard_normal((x_w, r)) / 5 for _ in range(n)],
             "w": [rng.standard_normal((r, x_w)) / 3 for _ in range(n)],
             "b": [rng.standard_normal(x_w) / 10 for _ in range(n)]}
    x0 = rng.standard_normal((7, x_w))
    want = x0.copy()
    for v, w, b in zip(cross["v"], cross["w"], cross["b"]):
        want = x0 * ((want @ v) @ w + b) + want
    f32 = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                 cross)
    with jax.default_matmul_precision("highest"):
        got = low_rank_cross(f32, jnp.asarray(x0, jnp.float32))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)


def _tiny(**kw):
    base = dict(name="tiny-dcnv2", family="dlrm", n_tables=4,
                rows_per_table=400, emb_dim=8, multi_hot=POOLING,
                dense_features=5, bottom_mlp=(16, 8), top_mlp=(16, 1),
                interaction="dcnv2", cross_layers=2, cross_rank=4,
                param_dtype="float32", compute_dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


def test_config_defaults_keep_the_dot_model():
    cfg = ModelConfig(name="d", family="dlrm", n_tables=3, multi_hot=4)
    assert (cfg.interaction, cfg.cross_layers, cfg.cross_rank) == ("dot",
                                                                   0, 0)
    assert cfg.pooling == (4, 4, 4) and cfg.ids_per_query == 12
    assert _tiny().pooling == POOLING and _tiny().ids_per_query == 13


def test_an_unknown_interaction_is_refused():
    with pytest.raises(ValueError, match="interaction 'dcn'"):
        _tiny(interaction="dcn")
    with pytest.raises(ValueError, match="interaction"):
        dataclasses.replace(_tiny(), interaction="")


def test_forward_dispatches_on_the_interaction():
    """The ``dcnv2`` forward is bottom MLP (ReLU after every layer), the
    cross network over ``x0`` = bottom output then the bags, top MLP; the
    ``dot`` forward of the same shapes reads no cross weights."""
    cfg = _tiny()
    params = init_dlrm_dense(jax.random.key(0), cfg)
    x = top_inputs(cfg)
    assert x == 5 * 8
    assert [v.shape for v in params["cross"]["v"]] == [(x, 4)] * 2
    assert [w.shape for w in params["cross"]["w"]] == [(4, x)] * 2
    assert params["top"]["w"][0].shape == (x, 16)
    rng = np.random.default_rng(2)
    dense = jnp.asarray(rng.standard_normal((6, 5)), jnp.float32)
    pooled = jnp.asarray(rng.standard_normal((6, 4, 8)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = forward_pooled(params, cfg, dense, pooled)
        p = jax.tree_util.tree_map(np.asarray, params)
        bot = np.asarray(dense)
        for w, b in zip(p["bottom"]["w"], p["bottom"]["b"]):
            bot = np.maximum(bot @ w + b, 0.0)
        x0 = np.concatenate([bot[:, None], np.asarray(pooled)],
                            axis=1).reshape(6, -1)
        h = x0
        for v, w, b in zip(p["cross"]["v"], p["cross"]["w"],
                           p["cross"]["b"]):
            h = x0 * ((h @ v) @ w + b) + h
        h = np.maximum(h @ p["top"]["w"][0] + p["top"]["b"][0], 0.0)
        want = (h @ p["top"]["w"][1] + p["top"]["b"][1])[:, 0]
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)
    dot = dataclasses.replace(cfg, interaction="dot", cross_layers=0,
                              cross_rank=0)
    dparams = init_dlrm_dense(jax.random.key(0), dot)
    assert "cross" not in dparams
    assert dparams["top"]["w"][0].shape == (8 + 10, 16)
    assert forward_pooled(dparams, dot, dense, pooled).shape == (6,)


@pytest.fixture(scope="module")
def served_traced():
    from repro.core.trace import Trace
    from repro.launch.serve import serve_trace
    from repro.obs import SpanTracer, install_tracer

    cfg = _tiny(rows_per_table=50)
    rows = np.array([3, 50, 7, 40], np.int64)
    rng = np.random.default_rng(3)
    n_q = 40
    tab = np.tile(np.repeat(np.arange(4), POOLING), n_q).astype(np.int32)
    row = rng.integers(0, rows[tab])
    params = init_dlrm_dense(jax.random.key(1), cfg)
    recs = []
    tr = install_tracer(SpanTracer())
    try:
        res = serve_trace(cfg, params, Trace(tab, row, rows), 20, "lru",
                          None, batch_queries=8, probe=recs.append)
    finally:
        install_tracer(None)
    return res, recs, tr


def test_pool_counter_and_spans_reconcile(served_traced):
    from repro.obs import reconcile

    res, recs, tr = served_traced
    assert len(recs) == 5
    assert all(r.steps["pool_rows"] == 8 * 13 for r in recs)
    assert all(np.asarray(r.rows).shape == (8 * 13, 8) for r in recs)
    spans = tr.spans("serve", "pool")
    assert len(spans) == 5
    assert {(s["args"]["segments"], s["args"]["longest_bag"],
             s["args"]["rows"]) for s in spans} == {(8 * 4, 8, 8 * 13)}
    flat = res["metrics"]
    trace = tr.chrome_trace()
    assert reconcile(metrics=flat, trace=trace, strict=False) == []
    spans[0]["args"]["rows"] -= 1
    problems = reconcile(metrics=flat, trace=tr.chrome_trace(),
                         strict=False)
    assert any("serve.pool_rows" in p for p in problems), problems
