"""Plain reference of MLPerf's DLRM-DCNv2, which ``dlrm-dcnv2.*`` serves.

The model is TorchRec's ``DLRM_DCN`` as MLPerf Training's
``recommendation_v2/torchrec_dlrm`` and MLPerf Inference's
``recommendation/dlrm_v2`` run it, with the cross network of DCN V2
(arXiv:2008.13535, TorchRec ``LowRankCrossNet``):

* ``DenseArch``: the bottom MLP, a ReLU after every layer;
* ``SparseArch``: each table's multi-hot ids sum-pooled into one bag;
* ``InteractionDCNArch``: ``x0`` is the bottom output, then table 0's bag,
  table 1's, ..., concatenated, ``(T + 1) * D`` wide; ``L`` low-rank cross
  layers ``x_{l+1} = x0 * (W_l (V_l x_l) + b_l) + x_l``, ``V_l`` ``X -> r``
  without bias, ``W_l`` ``r -> X`` with bias ``b_l``;
* ``OverArch``: the top MLP, a ReLU after every layer but the last; the
  logit is taken before the sigmoid.

Everything the correctness check compares against is made here, from the
configuration and the seed, and nothing is taken from the program:

* :func:`init_params` draws ``{"bottom": {"w", "b"}, "cross": {"v", "w",
  "b"}, "top": {"w", "b"}}`` on the device in one jitted call, in the
  configuration's parameter dtype.  The harness serves these, and the
  reference reads the same values back in float32.
* :func:`slow_tier_rows` regenerates rows of the slow tier by the recipe
  the serve path documents (``standard_normal`` float32 rows from
  ``numpy.random.default_rng(0)``, row after row), one block at a time: the
  recipe of ``dlrm_reference``.
* :func:`pool` sums each table's segment of a query's rows in float32.
* :func:`logits` is the forward above in float32 at the highest matmul
  precision.  With ``precision="fp8"`` every matmul operand is first scaled
  per tensor onto the range of ``float8_e4m3fn`` and rounded to it: the
  control, one step below the configuration's bfloat16, that the check
  must refuse.

Departures from the MLPerf model, none of which changes a shape: random
weights from the seed (``torch.nn.Linear``'s default, uniform in
``+-1/sqrt(fan_in)``, for every weight and bias) in place of trained ones;
embedding rows N(0, 1) in float32 from the slow tier's recipe in place of
TorchRec's uniform initialisation; bfloat16 dense weights and compute, as
the program serves them, where MLPerf's reference computes in float32.
"""
from __future__ import annotations

import numpy as np

SLOW_TIER_SEED = 0
_BLOCK_ROWS = 1 << 18


def _dims(cfg: dict):
    """Bottom MLP dims, the cross network's width ``X`` and rank, top MLP
    dims."""
    x = (int(cfg["n_tables"]) + 1) * int(cfg["emb_dim"])
    bottom = [int(cfg["dense_features"])] + [int(v) for v in cfg["bottom_mlp"]]
    top = [x] + [int(v) for v in cfg["top_mlp"]]
    return bottom, x, int(cfg["cross_rank"]), top


def init_params(cfg: dict, seed: int):
    """The parameters on the device, in ``cfg["param_dtype"]``: every
    weight and bias uniform in ``+-1/sqrt(fan_in)``."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(cfg["param_dtype"])
    bottom, x, r, top = _dims(cfg)
    n_cross = int(cfg["cross_layers"])

    def uniform(key, shape, fan_in):
        a = 1.0 / np.sqrt(fan_in)
        return jax.random.uniform(key, shape, jnp.float32, -a, a).astype(dt)

    def mlp(key, d):
        ks = jax.random.split(key, 2 * (len(d) - 1))
        return {"w": [uniform(ks[2 * i], (a, b), a)
                      for i, (a, b) in enumerate(zip(d, d[1:]))],
                "b": [uniform(ks[2 * i + 1], (b,), a)
                      for i, (a, b) in enumerate(zip(d, d[1:]))]}

    def make(key):
        kb, kc, kt = jax.random.split(key, 3)
        ks = jax.random.split(kc, 3 * n_cross)
        cross = {"v": [uniform(ks[3 * i], (x, r), x) for i in range(n_cross)],
                 "w": [uniform(ks[3 * i + 1], (r, x), r)
                       for i in range(n_cross)],
                 "b": [uniform(ks[3 * i + 2], (x,), r)
                       for i in range(n_cross)]}
        return {"bottom": mlp(kb, bottom), "cross": cross, "top": mlp(kt, top)}

    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.jit(lambda s: make(jax.random.key(s)))(np.uint32(word))


def slow_tier_rows(n_rows: int, d: int, ids: np.ndarray) -> np.ndarray:
    """Rows ``ids`` of the ``(n_rows, d)`` float32 slow tier, drawn block by
    block from one generator, up to the largest id, so only one block is
    held at a time."""
    ids = np.asarray(ids, np.int64).ravel()
    uniq, inv = np.unique(ids, return_inverse=True)
    out = np.empty((uniq.size, d), np.float32)
    rng = np.random.default_rng(SLOW_TIER_SEED)
    block = np.empty((_BLOCK_ROWS, d), np.float32)
    last = int(uniq[-1]) + 1 if uniq.size else 0
    if last > n_rows:
        raise IndexError(f"row {last - 1} of a {n_rows}-row slow tier")
    for lo in range(0, last, _BLOCK_ROWS):
        hi = min(lo + _BLOCK_ROWS, n_rows)
        buf = block[: hi - lo]
        rng.standard_normal(out=buf, dtype=np.float32)
        a, b = np.searchsorted(uniq, [lo, hi])
        out[a:b] = buf[uniq[a:b] - lo]
    return out[inv]


def pool(rows: np.ndarray, cfg: dict) -> np.ndarray:
    """Sum-pool ``(B * sum(P_t), D)`` rows, each query's ``P_0`` rows of
    table 0 then ``P_1`` of table 1 and so on, into ``(B, T, D)`` bags in
    float32."""
    t, d = int(cfg["n_tables"]), int(cfg["emb_dim"])
    per = np.broadcast_to(np.asarray(cfg["multi_hot"], np.int64), (t,))
    q = rows.reshape(-1, int(per.sum()), d).astype(np.float32)
    starts = np.concatenate([[0], np.cumsum(per)[:-1]])
    return np.add.reduceat(q, starts, axis=1, dtype=np.float32)


def logits(params, dense, pooled, precision: str = "f32"):
    """``(B,)`` logits of the plain forward; see the module docstring."""
    import jax
    import jax.numpy as jnp

    def q(x):
        x = x.astype(jnp.float32)
        if precision == "fp8":  # per-tensor scale onto e4m3's +-448
            s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
            x = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
        return x

    def linear(x, w, b=None):
        y = q(x) @ q(w)
        return y if b is None else y + b.astype(jnp.float32)

    def fwd(params, dense, pooled):
        bot = dense
        for w, b in zip(params["bottom"]["w"], params["bottom"]["b"]):
            bot = jnp.maximum(linear(bot, w, b), 0.0)
        x0 = jnp.concatenate([bot[:, None, :], pooled.astype(jnp.float32)],
                             axis=1).reshape(bot.shape[0], -1)
        x = x0
        c = params["cross"]
        for v, w, b in zip(c["v"], c["w"], c["b"]):
            x = x0 * linear(linear(x, v), w, b) + x
        top = params["top"]
        n = len(top["w"])
        for i, (w, b) in enumerate(zip(top["w"], top["b"])):
            x = linear(x, w, b)
            if i < n - 1:
                x = jnp.maximum(x, 0.0)
        return x[:, 0]

    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(fwd)(params, jnp.asarray(dense),
                                       jnp.asarray(pooled)))
