"""The paper's own architecture: DLRM (embeddings + interaction + MLPs).

Matches the open-source DLRM reference [arXiv:1906.00091] that the paper's
evaluation uses: a bottom MLP projects dense features to emb_dim, sparse
categorical features gather+sum-pool multi-hot rows from per-table EMBs,
pairwise dot-product interaction feeds the top MLP, sigmoid CTR output.
``cfg.interaction == "dcnv2"`` is MLPerf's DLRM-DCNv2 instead (TorchRec's
``DLRM_DCN``, DCN V2 [arXiv:2008.13535]): the bottom output and the pooled
bags, concatenated, go through a low-rank cross network to the top MLP.

At dry-run scale the stacked EMB tensor (856 x 72704 x 128) is row-sharded
across the whole mesh; at serving time on real tiered memory the EMBs live on
the host tier behind the RecMG-managed device buffer (src/repro/core) — that
path is exercised by the examples and benchmarks, not by the dry-run.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.sharding.partition import constrain_batch


def _init_mlp(key, dims, dt):
    ks = jax.random.split(key, len(dims) - 1)
    ws, bs = [], []
    for i, k in enumerate(ks):
        fan_in = dims[i]
        ws.append(
            (jax.random.normal(k, (dims[i], dims[i + 1])) / math.sqrt(fan_in)).astype(dt)
        )
        bs.append(jnp.zeros((dims[i + 1],), dt))
    return {"w": ws, "b": bs}


def _mlp(p, x, final_act=None):
    n = len(p["w"])
    for i in range(n):
        x = x @ p["w"][i].astype(x.dtype) + p["b"][i].astype(x.dtype)
        if i < n - 1:
            x = jax.nn.relu(x)
    return final_act(x) if final_act else x


def num_interactions(cfg: ModelConfig) -> int:
    f = cfg.n_tables + 1
    return f * (f - 1) // 2


def top_inputs(cfg: ModelConfig) -> int:
    """Width of the top MLP's input: the bottom output and every pairwise
    dot, or for ``dcnv2`` the cross network's width, every feature's row."""
    if cfg.interaction == "dcnv2":
        return (cfg.n_tables + 1) * cfg.emb_dim
    return cfg.emb_dim + num_interactions(cfg)


def init_dlrm_dense(key, cfg: ModelConfig):
    """The dense half of :func:`init_dlrm`: bottom and top MLPs, and the
    cross layers of a ``dcnv2`` model, the same values for the same key.
    Serving reads embedding rows from the host tier, so it builds no device
    table (856 x 72,704 x 128 would be 31.9 GB in fp32)."""
    _, kb, ktop = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.param_dtype)
    bot_dims = (cfg.dense_features,) + tuple(cfg.bottom_mlp)
    top_dims = (top_inputs(cfg),) + tuple(cfg.top_mlp)
    params = {
        "bottom": _init_mlp(kb, bot_dims, dt),
        "top": _init_mlp(ktop, top_dims, dt),
    }
    if cfg.interaction == "dcnv2":
        x, r = top_inputs(cfg), cfg.cross_rank
        ks = jax.random.split(jax.random.fold_in(key, 3), 2 * cfg.cross_layers)
        params["cross"] = {
            "v": [(jax.random.normal(k, (x, r)) / math.sqrt(x)).astype(dt)
                  for k in ks[0::2]],
            "w": [(jax.random.normal(k, (r, x)) / math.sqrt(r)).astype(dt)
                  for k in ks[1::2]],
            "b": [jnp.zeros((x,), dt) for _ in range(cfg.cross_layers)],
        }
    return params


def init_dlrm(key, cfg: ModelConfig):
    kt = jax.random.split(key, 3)[0]
    dt = jnp.dtype(cfg.param_dtype)
    emb = (
        jax.random.normal(kt, (cfg.n_tables, cfg.rows_per_table, cfg.emb_dim))
        * (1.0 / math.sqrt(cfg.emb_dim))
    ).astype(dt)
    return {"emb": emb, **init_dlrm_dense(key, cfg)}


def embedding_lookup_rowsharded(emb, sparse_idx, mesh):
    """Pool-before-reduce lookup for EMB rows sharded on the *model* axis.

    GSPMD resolves the naive gather from row-sharded tables by exchanging
    the UNPOOLED (B, T, P, D) partials — 20x (the pooling factor) more
    collective traffic than necessary.  This shard_map version pools each
    device's owned rows locally and psums only the (B_local, T, D) result
    — the TorchRec row-wise-sharding communication pattern.  §Perf.
    """
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.sharding.partition import data_axes

    T, R, D = emb.shape
    n_m = mesh.shape["model"]
    shard_rows = R // n_m
    dp = data_axes(mesh)

    def local(emb_l, idx_l):
        m = jax.lax.axis_index("model")
        rel = idx_l - m * shard_rows
        ok = (rel >= 0) & (rel < shard_rows)
        relc = jnp.clip(rel, 0, shard_rows - 1)

        def per_table(tab, ix, okx):  # tab (Rs, D); ix/okx (B, P)
            rows = tab[ix]  # (B, P, D)
            return jnp.where(okx[..., None], rows, 0).sum(axis=1)

        pooled = jax.vmap(per_table, in_axes=(0, 1, 1), out_axes=1)(
            emb_l, relc, ok
        )  # (B_local, T, D)
        return jax.lax.psum(pooled, "model")

    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P(None, "model", None), P(dp, None, None)),
        out_specs=P(dp, None, None),
        check_rep=False,
    )
    return fn(emb, sparse_idx)


def embedding_lookup(emb, sparse_idx):
    """emb: (T, R, D); sparse_idx: (B, T, P) -> pooled (B, T, D).

    Per-table multi-hot gather + sum pooling — the operation the paper's
    entire memory system optimizes.  The Pallas fused version lives in
    repro/kernels/embedding_gather.py; this is the XLA path.
    """
    # (B, T, P, D): gather rows per table via take_along_axis on a vmap.
    def per_table(table, idx):  # table (R, D), idx (B, P)
        return table[idx].sum(axis=1)  # (B, D)

    pooled = jax.vmap(per_table, in_axes=(0, 1), out_axes=1)(
        emb, sparse_idx
    )  # (B, T, D)
    return pooled


def dot_interaction(bot, pooled):
    """DLRM's pairwise dot interaction: the bottom output ``(B, D)`` and the
    pooled bags ``(B, T, D)`` -> the top MLP's input ``(B, D + F(F-1)/2)``
    in float32, the bottom output then every pair's dot product."""
    z = jnp.concatenate([bot[:, None, :], pooled], axis=1)  # (B, F, D)
    zz = jnp.einsum("bfd,bgd->bfg", z, z, preferred_element_type=jnp.float32)
    f = z.shape[1]
    # NumPy indices are constants of the program; jnp.triu_indices is
    # computed in it, and at 857 features takes ~50 s to compile for a v5e.
    iu, ju = np.triu_indices(f, k=1)
    inter = zz[:, iu, ju]  # (B, F*(F-1)/2)
    return jnp.concatenate([bot.astype(jnp.float32), inter], axis=1)


def low_rank_cross(cross, x0):
    """DCN V2's low-rank cross network (TorchRec ``LowRankCrossNet``):
    ``x_{l+1} = x0 * (W_l (V_l x_l) + b_l) + x_l`` from ``x_0 = x0``, with
    ``cross["v"][l]`` ``(X, r)``, ``cross["w"][l]`` ``(r, X)`` and
    ``cross["b"][l]`` ``(X,)``, in ``x0``'s dtype."""
    x, dt = x0, x0.dtype
    for v, w, b in zip(cross["v"], cross["w"], cross["b"]):
        x = x0 * ((x @ v.astype(dt)) @ w.astype(dt) + b.astype(dt)) + x
    return x


def forward_pooled(params, cfg: ModelConfig, dense, pooled):
    """Logits ``(B,)`` from the dense input and the pooled bags ``(B, T,
    D)``: the bottom MLP, ``cfg.interaction`` and the top MLP, in
    ``cfg.compute_dtype``.  For ``dcnv2`` the bottom MLP ends in a ReLU, as
    TorchRec's ``DenseArch`` does, and the cross network reads ``x0``, the
    bottom output then every table's bag."""
    ct = jnp.dtype(cfg.compute_dtype)
    if cfg.interaction == "dcnv2":
        bot = _mlp(params["bottom"], dense.astype(ct), jax.nn.relu)
        x0 = jnp.concatenate([bot[:, None, :], pooled.astype(ct)], axis=1)
        top_in = low_rank_cross(params["cross"],
                                x0.reshape(x0.shape[0], -1))
    else:
        bot = _mlp(params["bottom"], dense.astype(ct))
        top_in = dot_interaction(bot, pooled.astype(ct))
    return _mlp(params["top"], top_in.astype(ct))[:, 0]


def dlrm_forward(params, cfg: ModelConfig, dense, sparse_idx,
                 sharded_lookup: bool = False):
    """dense: (B, F_dense) f32; sparse_idx: (B, T, P) int32 -> logits (B,)."""
    ct = jnp.dtype(cfg.compute_dtype)
    if sharded_lookup:
        from repro.sharding import partition as _p

        assert _p._ACT_MESH is not None, "sharded lookup needs a mesh scope"
        pooled = embedding_lookup_rowsharded(
            params["emb"].astype(ct), sparse_idx, _p._ACT_MESH
        )
    else:
        pooled = constrain_batch(
            embedding_lookup(params["emb"].astype(ct), sparse_idx)
        )  # (B,T,D)
    return forward_pooled(params, cfg, dense, pooled).astype(jnp.float32)


def dlrm_loss(params, cfg: ModelConfig, dense, sparse_idx, labels,
              sharded_lookup: bool = False):
    logit = dlrm_forward(params, cfg, dense, sparse_idx, sharded_lookup)
    # Numerically-stable BCE with logits.
    loss = jnp.maximum(logit, 0.0) - logit * labels + jnp.log1p(
        jnp.exp(-jnp.abs(logit))
    )
    return loss.mean()
