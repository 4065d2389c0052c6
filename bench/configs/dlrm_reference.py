"""Plain reference of the DLRM that the ``dlrm-*`` configurations serve.

Everything the correctness check compares against is made here, from the
configuration and the seed, and nothing is taken from the program:

* :func:`init_params` draws the dense MLPs' weights and biases on the device
  in one jitted call, in the configuration's parameter dtype.  The harness
  serves these, and the reference reads the same values back in float32.
* :func:`slow_tier_rows` regenerates rows of the slow tier by the recipe
  the serve path documents (``standard_normal`` float32 rows from
  ``numpy.random.default_rng(0)``, row after row), one block at a time.
* :func:`logits` is the DLRM forward of arXiv:1906.00091 in float32 at the
  highest matmul precision: bottom MLP, pairwise dot interaction of the
  bottom output with every table's pooled row, top MLP.  With
  ``precision="fp8"`` every matmul operand is first scaled per tensor onto
  the range of ``float8_e4m3fn`` and rounded to it: the control, one step
  below the configuration's bfloat16, that the check must refuse.
"""
from __future__ import annotations

import numpy as np

SLOW_TIER_SEED = 0
_BLOCK_ROWS = 1 << 18


def init_params(cfg: dict, seed: int):
    """``{"bottom": {"w", "b"}, "top": {"w", "b"}}`` on the device: weights
    N(0, 1/fan_in), biases N(0, 0.01), in ``cfg["param_dtype"]``."""
    import jax
    import jax.numpy as jnp

    from bench.costs import mlp_dims

    dt = jnp.dtype(cfg["param_dtype"])
    dims = mlp_dims(cfg)

    def make(key):
        out = {}
        for name, d, k in zip(("bottom", "top"), dims,
                              jax.random.split(key, 2)):
            ks = jax.random.split(k, 2 * (len(d) - 1))
            ws, bs = [], []
            for i, (a, b) in enumerate(zip(d, d[1:])):
                ws.append((jax.random.normal(ks[2 * i], (a, b), jnp.float32)
                           / np.sqrt(a)).astype(dt))
                bs.append((0.1 * jax.random.normal(ks[2 * i + 1], (b,),
                                                   jnp.float32)).astype(dt))
            out[name] = {"w": ws, "b": bs}
        return out

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
    """Sum-pool ``(B*T*P, D)`` rows into ``(B, T, D)`` bags in float32."""
    t, p, d = int(cfg["n_tables"]), int(cfg["multi_hot"]), int(cfg["emb_dim"])
    return rows.reshape(-1, t, p, d).sum(axis=2, dtype=np.float32)


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

    def mlp(p, x):
        n = len(p["w"])
        for i, (w, b) in enumerate(zip(p["w"], p["b"])):
            x = q(x) @ q(w) + b.astype(jnp.float32)
            if i < n - 1:
                x = jnp.maximum(x, 0.0)
        return x

    def fwd(params, dense, pooled):
        bot = mlp(params["bottom"], dense)
        z = jnp.concatenate([bot[:, None, :], pooled.astype(jnp.float32)],
                            axis=1)
        zz = jnp.einsum("bfd,bgd->bfg", q(z), q(z))
        iu, ju = np.triu_indices(z.shape[1], k=1)
        top_in = jnp.concatenate([bot, zz[:, iu, ju]], axis=1)
        return mlp(params["top"], top_in)[:, 0]

    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(fwd)(params, jnp.asarray(dense),
                                       jnp.asarray(pooled)))
