"""Operations and bytes of MLPerf's DLRM-DCNv2 (``dlrm-dcnv2.*``), from the
configuration's shapes: the yardstick of its cells' roofline shares and of
``step.mfu``, beside the functions ``bench/costs.py`` gives the dot
interaction's DLRM, plus the counts of its two kernels of its own: the
ragged pooling (``pool_bytes``) and the cross network (``cross_flops``,
``cross_bytes``, ``cross_seconds_bound``).

Counts are what the model needs: the cross layers count their two matmuls
a layer, not the elementwise product and sums around them.  A multiply-add
is two operations.
"""
from __future__ import annotations

from bench.costs import _ITEMSIZE
# The row gather moves what it moves for any DLRM: ``gather_rows_roofline``
# reads the same count here.
from bench.costs import gather_bytes  # noqa: F401


def top_inputs(cfg: dict) -> int:
    """Width of the cross network and of the top MLP's input: the bottom
    output and every table's bag, ``(T + 1) * D``."""
    return (int(cfg["n_tables"]) + 1) * int(cfg["emb_dim"])


def mlp_dims(cfg: dict):
    bottom = [int(cfg["dense_features"])] + [int(x) for x in cfg["bottom_mlp"]]
    top = [top_inputs(cfg)] + [int(x) for x in cfg["top_mlp"]]
    return bottom, top


def cross_macs_per_query(cfg: dict) -> int:
    """Multiply-adds of the cross layers: ``V_l`` and ``W_l`` a layer."""
    return (int(cfg["cross_layers"]) * 2 * top_inputs(cfg)
            * int(cfg["cross_rank"]))


def macs_per_query(cfg: dict) -> int:
    """Multiply-adds of one query: both MLPs and the cross layers."""
    bottom, top = mlp_dims(cfg)
    mlp = sum(a * b for dims in (bottom, top) for a, b in zip(dims, dims[1:]))
    return mlp + cross_macs_per_query(cfg)


def flops_per_query(cfg: dict) -> int:
    return 2 * macs_per_query(cfg)


def cross_weight_bytes(cfg: dict) -> int:
    """Bytes of the cross layers' ``V``, ``W`` and ``b`` in the served
    dtype."""
    x, r = top_inputs(cfg), int(cfg["cross_rank"])
    n = int(cfg["cross_layers"]) * (2 * x * r + x)
    return n * _ITEMSIZE[cfg["param_dtype"]]


def weight_bytes(cfg: dict) -> int:
    """Bytes of the dense weights and biases in the served dtype: both
    MLPs and the cross layers."""
    bottom, top = mlp_dims(cfg)
    n = sum(a * b + b for dims in (bottom, top) for a, b in zip(dims, dims[1:]))
    return n * _ITEMSIZE[cfg["param_dtype"]] + cross_weight_bytes(cfg)


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


def pool_bytes(cfg: dict, batch: int) -> int:
    """Least bytes the pooling of ``batch`` queries moves: every served row
    read once, every bag written once, in the rows' dtype."""
    d, item = int(cfg["emb_dim"]), _ITEMSIZE[cfg.get("row_dtype", "float32")]
    per = cfg["multi_hot"]
    ids = sum(per) if isinstance(per, list) else int(per) * int(cfg["n_tables"])
    return batch * (ids + int(cfg["n_tables"])) * d * item


def cross_flops(cfg: dict, batch: int) -> int:
    return 2 * cross_macs_per_query(cfg) * batch


def cross_bytes(cfg: dict, batch: int) -> int:
    """Least bytes the cross network moves for ``batch`` queries: its
    weights once, ``x0`` in and the last layer's output out, in the compute
    dtype."""
    act = 2 * batch * top_inputs(cfg) * _ITEMSIZE[cfg["compute_dtype"]]
    return cross_weight_bytes(cfg) + act


def cross_seconds_bound(cfg: dict, batch: int, peak: dict) -> float:
    """Least time the cross network takes for ``batch`` queries."""
    return max(cross_bytes(cfg, batch) / peak["hbm_bytes_per_s"],
               cross_flops(cfg, batch) / peak["flops_bf16"])
