"""Operations and bytes the served work needs, from the configuration's shapes.

The yardstick for every roofline share and for ``step.mfu``.  Counts are
what the model needs, not what the program happens to compute: the
interaction counts the F(F-1)/2 pairwise dot products, not the full F x F
product the program forms.  A multiply-add is two operations.
"""
from __future__ import annotations

import numpy as np

_ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def n_features(cfg: dict) -> int:
    """Interaction features: the bottom MLP's output plus one per table."""
    return int(cfg["n_tables"]) + 1


def top_inputs(cfg: dict) -> int:
    """Width of the top MLP's input: the bottom output and every pair."""
    f = n_features(cfg)
    return int(cfg["emb_dim"]) + f * (f - 1) // 2


def mlp_dims(cfg: dict):
    bottom = [int(cfg["dense_features"])] + [int(x) for x in cfg["bottom_mlp"]]
    top = [top_inputs(cfg)] + [int(x) for x in cfg["top_mlp"]]
    return bottom, top


def macs_per_query(cfg: dict) -> int:
    """Multiply-adds of one query: both MLPs and the pairwise dots."""
    bottom, top = mlp_dims(cfg)
    mlp = sum(a * b for dims in (bottom, top) for a, b in zip(dims, dims[1:]))
    f = n_features(cfg)
    return mlp + f * (f - 1) // 2 * int(cfg["emb_dim"])


def flops_per_query(cfg: dict) -> int:
    return 2 * macs_per_query(cfg)


def weight_bytes(cfg: dict) -> int:
    """Bytes of the dense MLPs' weights and biases in the served dtype."""
    item = _ITEMSIZE[cfg["param_dtype"]]
    bottom, top = mlp_dims(cfg)
    n = sum(a * b + b for dims in (bottom, top) for a, b in zip(dims, dims[1:]))
    return n * item


def forward_bytes(cfg: dict, batch: int) -> int:
    """Least bytes one dense forward of ``batch`` queries moves: its
    weights once, the pooled float32 embeddings and dense inputs in, the
    logits out."""
    t, d = int(cfg["n_tables"]), int(cfg["emb_dim"])
    act = batch * (t * d + int(cfg["dense_features"]) + 1) * 4
    return weight_bytes(cfg) + act


def forward_seconds_bound(cfg: dict, batch: int, peak: dict) -> float:
    """Least time one forward takes: the larger of its bytes over the
    memory bandwidth and its operations over the peak rate."""
    return max(forward_bytes(cfg, batch) / peak["hbm_bytes_per_s"],
               flops_per_query(cfg) * batch / peak["flops_bf16"])


def gather_bytes(unique_rows, cfg: dict) -> int:
    """Bytes the row gather moves: each unique row read once from the fast
    tier and written once to the output."""
    item = _ITEMSIZE[cfg.get("row_dtype", "float32")]
    return int(np.sum(unique_rows)) * int(cfg["emb_dim"]) * item * 2
