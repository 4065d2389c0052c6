"""The paper's own architecture: DLRM (embeddings + interaction + MLPs).

Matches the open-source DLRM reference [arXiv:1906.00091] that the paper's
evaluation uses: a bottom MLP projects dense features to emb_dim, sparse
categorical features gather+sum-pool multi-hot rows from per-table EMBs,
pairwise dot-product interaction feeds the top MLP, sigmoid CTR output.

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


def init_dlrm_dense(key, cfg: ModelConfig):
    """The dense half of :func:`init_dlrm`: bottom and top MLPs only, the
    same values for the same key.  Serving reads embedding rows from the
    host tier, so it builds no device table (856 x 72,704 x 128 would be
    31.9 GB in fp32)."""
    _, kb, ktop = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.param_dtype)
    bot_dims = (cfg.dense_features,) + tuple(cfg.bottom_mlp)
    top_in = cfg.emb_dim + num_interactions(cfg)
    top_dims = (top_in,) + tuple(cfg.top_mlp)
    return {
        "bottom": _init_mlp(kb, bot_dims, dt),
        "top": _init_mlp(ktop, top_dims, dt),
    }


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


def dlrm_forward(params, cfg: ModelConfig, dense, sparse_idx,
                 sharded_lookup: bool = False):
    """dense: (B, F_dense) f32; sparse_idx: (B, T, P) int32 -> logits (B,)."""
    ct = jnp.dtype(cfg.compute_dtype)
    bot = _mlp(params["bottom"], dense.astype(ct))  # (B, emb_dim)
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
    z = jnp.concatenate([bot[:, None, :], pooled], axis=1)  # (B, F, D)
    zz = jnp.einsum("bfd,bgd->bfg", z, z, preferred_element_type=jnp.float32)
    f = z.shape[1]
    # NumPy indices are constants of the program; jnp.triu_indices is
    # computed in it, and at 857 features takes ~50 s to compile for a v5e.
    iu, ju = np.triu_indices(f, k=1)
    inter = zz[:, iu, ju]  # (B, F*(F-1)/2)
    top_in = jnp.concatenate([bot.astype(jnp.float32), inter], axis=1)
    logit = _mlp(params["top"], top_in.astype(ct))[:, 0]
    return logit.astype(jnp.float32)


def dlrm_loss(params, cfg: ModelConfig, dense, sparse_idx, labels,
              sharded_lookup: bool = False):
    logit = dlrm_forward(params, cfg, dense, sparse_idx, sharded_lookup)
    # Numerically-stable BCE with logits.
    loss = jnp.maximum(logit, 0.0) - logit * labels + jnp.log1p(
        jnp.exp(-jnp.abs(logit))
    )
    return loss.mean()
