"""Config system for the repro framework.

ModelConfig describes an architecture (all families in the assigned pool:
dense GQA transformers, MoE, SSM (mamba-1), hybrid attn+SSM, encoder-decoder
(whisper), VLM backbone with a stub vision frontend, and the paper's DLRM).

ShapeConfig describes one input-shape cell (train / prefill / decode /
long-decode).  RunConfig carries runtime knobs (microbatching, remat policy,
dtypes, sharding variant) that the perf loop iterates on.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Tuple, Union

# ---------------------------------------------------------------------------
# Model config
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | audio | vlm | dlrm

    # Transformer backbone.
    n_layers: int = 0
    d_model: int = 0
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0  # 0 -> d_model // n_heads
    d_ff: int = 0
    vocab: int = 0
    qkv_bias: bool = False
    qk_norm: bool = False
    tie_embeddings: bool = False
    rope_theta: float = 1_000_000.0
    norm_eps: float = 1e-5

    # Attention variant.
    attn_type: str = "full"  # full | sliding
    window: int = 4096  # sliding-window size when attn_type == "sliding"

    # MoE.
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0  # per-expert FFN width (0 -> use d_ff)
    capacity_factor: float = 1.25

    # SSM (mamba-1).
    ssm_state: int = 0
    d_inner: int = 0  # 0 -> 2 * d_model
    dt_rank: int = 0  # 0 -> ceil(d_model / 16)
    conv_width: int = 4
    ssm_chunk: int = 256  # chunked selective-scan block length

    # Encoder-decoder (whisper-style).
    enc_dec: bool = False
    n_enc_layers: int = 0
    enc_len: int = 1500  # stub frame count fed to the encoder

    # Modality frontend stub: number of precomputed patch/frame embeddings
    # spliced into the decoder input sequence ("vlm") or fed to the encoder
    # ("audio").  0 -> no frontend.
    frontend: str = ""  # "" | vision | audio
    n_frontend_tokens: int = 0

    # DLRM (paper's own architecture).
    n_tables: int = 0
    rows_per_table: int = 0
    emb_dim: int = 0
    # Ids pooled per table: one for every table, or one per table.
    multi_hot: Union[int, Tuple[int, ...]] = 0
    dense_features: int = 0
    bottom_mlp: Tuple[int, ...] = ()
    top_mlp: Tuple[int, ...] = ()
    # Feature interaction: "dot" (DLRM's pairwise dots) or "dcnv2" (DCN V2's
    # low-rank cross network over the concatenated features).
    interaction: str = "dot"
    cross_layers: int = 0  # dcnv2: cross layers
    cross_rank: int = 0  # dcnv2: rank of each layer's V and W

    # Dtypes / runtime defaults (overridable via RunConfig).
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"

    source: str = ""  # provenance note: [source; verified-tier]

    def __post_init__(self):
        if self.interaction not in ("dot", "dcnv2"):
            raise ValueError(f"interaction {self.interaction!r}: 'dot' or "
                             "'dcnv2'")

    # ---------------- derived helpers ----------------
    @property
    def hd(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // max(self.n_heads, 1)

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def inner(self) -> int:
        return self.d_inner or 2 * self.d_model

    @property
    def dtrank(self) -> int:
        return self.dt_rank or max(1, (self.d_model + 15) // 16)

    @property
    def expert_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def pooling(self) -> Tuple[int, ...]:
        """Ids each table pools a query, one per table."""
        if isinstance(self.multi_hot, int):
            return (self.multi_hot,) * self.n_tables
        return tuple(self.multi_hot)

    @property
    def ids_per_query(self) -> int:
        return sum(self.pooling)

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def sub_quadratic(self) -> bool:
        """Can this arch decode with a bounded-size per-step state at 500k?"""
        return self.family in ("ssm", "hybrid")

    def reduced(self) -> "ModelConfig":
        """A tiny same-family config for CPU smoke tests."""
        kw = dict(
            name=self.name + "-reduced",
            n_layers=min(self.n_layers, 2) or 0,
            d_model=min(self.d_model, 64) if self.d_model else 0,
            d_ff=min(self.d_ff, 128) if self.d_ff else 0,
            vocab=min(self.vocab, 512) if self.vocab else 0,
            param_dtype="float32",
            compute_dtype="float32",
        )
        if self.n_heads:
            # Preserve GQA structure with small heads.
            kw["n_heads"] = 4
            kw["n_kv_heads"] = 2 if self.kv_heads < self.n_heads else 4
            kw["head_dim"] = 16
        if self.n_experts:
            kw["n_experts"] = 4
            kw["top_k"] = min(self.top_k, 2)
            kw["moe_d_ff"] = 64
            kw["capacity_factor"] = 8.0  # droppless at smoke scale
        if self.ssm_state:
            kw["ssm_state"] = 8
            kw["d_inner"] = 128
            kw["dt_rank"] = 4
            kw["ssm_chunk"] = 16
        if self.enc_dec:
            kw["n_enc_layers"] = 2
            kw["enc_len"] = 16
        if self.frontend:
            kw["n_frontend_tokens"] = 8
        if self.family == "dlrm":
            kw.update(
                n_tables=8,
                rows_per_table=256,
                emb_dim=16,
                multi_hot=4,
                dense_features=8,
                bottom_mlp=(32, 16),
                top_mlp=(32, 16, 1),
            )
        return replace(self, **kw)


# ---------------------------------------------------------------------------
# Shape cells
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: str  # train | prefill | decode
    seq_len: int
    global_batch: int


LM_SHAPES = {
    "train_4k": ShapeConfig("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524288, 1),
}

# The paper's DLRM has its own serving-style shapes (Table I batch sizes).
DLRM_SHAPES = {
    "infer_6k": ShapeConfig("infer_6k", "prefill", 0, 6144),
    "infer_18k": ShapeConfig("infer_18k", "prefill", 0, 18432),
    "train_6k": ShapeConfig("train_6k", "train", 0, 6144),
}


def shapes_for(cfg: ModelConfig):
    if cfg.family == "dlrm":
        return dict(DLRM_SHAPES)
    return dict(LM_SHAPES)


def shape_applicable(cfg: ModelConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Whether a (arch, shape) cell runs, and why not if it doesn't."""
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return False, (
            "pure full-attention arch: 500k-token decode requires "
            "sub-quadratic attention (skip per assignment; see DESIGN.md)"
        )
    return True, ""


# ---------------------------------------------------------------------------
# Runtime knobs (the perf loop iterates these)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class RunConfig:
    microbatches: int = 0  # 0 -> auto-size to fit HBM
    remat: str = "full"  # full | dots | none
    # Sharding variant: "fsdp_tp" (default), "tp" (no FSDP), "dp" (pure
    # data), "fsdp" (params over every axis, no TP), "fsdp_seq" (fsdp +
    # sequence dim on the model axis — small-batch prefill).
    sharding: str = "fsdp_tp"
    # §Perf knobs (see EXPERIMENTS.md):
    constrain_grads: bool = False  # pin grad accumulator to FSDP shards
    emb_rows: str = "all"  # DLRM EMB row sharding: "all" | "model"
    dlrm_sharded_lookup: bool = False  # pool-before-reduce shard_map lookup
    moe_local_dispatch: bool = False  # data-local MoE capacity buffers
    #   (halves the dispatch all-reduce but multiplies FSDP weight gathers;
    #   net-negative with FSDP'd experts — kept for EP-style setups. §Perf)
    # Shard decode KV-cache sequence dim on `model` (else KV heads if divisible).
    shard_kv_seq: bool = True
    opt_dtype: str = "float32"  # adam moment dtype
    grad_compression: str = ""  # "" | int8_ef
    logits_chunk: int = 0  # 0 -> whole-seq logits; else chunked loss
    attn_block_q: int = 512
    attn_block_kv: int = 512
    use_pallas: bool = False  # TPU-only fast path; CPU tests use XLA path


def auto_microbatches(cfg: ModelConfig, shape: ShapeConfig, n_data_shards: int,
                      tokens_budget: int = 4096) -> int:
    """Pick a microbatch count so per-device microbatch tokens <= budget."""
    if shape.kind != "train" or cfg.family == "dlrm":
        return 1
    per_dev_seqs = max(1, shape.global_batch // max(n_data_shards, 1))
    per_dev_tokens = per_dev_seqs * shape.seq_len
    mb = 1
    while per_dev_tokens // mb > tokens_budget and mb < per_dev_seqs:
        mb *= 2
    return mb
