"""Model-regression layer for the trained RecMG duo: the losses'
gradients are checked against finite differences in float64, and a tiny
end-to-end training run pins loss descent + bit-exact seeded
reproducibility for both models.

The prefetch loss stop-gradients its target representations (the
anti-collapse detach, §V-B) — so its analytic parameter gradient must
equal the finite difference of a *detached-target* reference loss (the
targets precomputed at the evaluation point and held fixed), not of the
loss itself: FD of the raw loss would differentiate straight through the
target branch the detach is there to cut.  The chamfer / truncated-L2 /
diversity terms are additionally FD-checked directly with respect to the
predicted points, where no detach is involved.
"""
import dataclasses
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from repro.core.caching_model import (CachingModelConfig, bce_loss,
                                      init_caching_model,
                                      train_caching_model)
from repro.core.chamfer import chamfer_bidirectional_vec, l2_truncated_vec
from repro.core.features import ROW_BUCKETS, make_windows
from repro.core.prefetch_model import (PrefetchModelConfig, access_reps,
                                       init_prefetch_model,
                                       make_prefetch_data, prefetch_loss,
                                       prefetch_predict_batch,
                                       train_prefetch_model)

# Tiny model dims: the FD check is O(params) per direction and the point
# is gradient *correctness*, not capacity.
N_TABLES, IN_LEN, OUT_LEN, HIDDEN = 3, 6, 3, 8


def _fd_check(loss_fn, params, n_dirs=3, eps=1e-5, tol=1e-6, seed=0):
    """Directional finite differences vs the analytic gradient, in f64.

    Central differences with eps=1e-5 leave ~1e-10 truncation error, so a
    1e-6 relative tolerance only passes when the gradient is genuinely
    right (f32 would drown the comparison in rounding noise).
    """
    flat, unravel = ravel_pytree(params)
    assert flat.dtype == jnp.float64  # params must be built under x64
    g = ravel_pytree(jax.grad(loss_fn)(params))[0]
    assert bool(jnp.all(jnp.isfinite(g)))
    rng = np.random.default_rng(seed)
    for _ in range(n_dirs):
        v = rng.normal(size=flat.shape)
        v = jnp.asarray(v / np.linalg.norm(v))
        lp = float(loss_fn(unravel(flat + eps * v)))
        lm = float(loss_fn(unravel(flat - eps * v)))
        fd = (lp - lm) / (2 * eps)
        an = float(g @ v)
        assert abs(fd - an) <= tol * max(1.0, abs(an)), (fd, an)


def _int_batch(rng, b, t):
    return {
        "xt": jnp.asarray(rng.integers(0, N_TABLES, (b, t)), jnp.int32),
        "xr1": jnp.asarray(rng.integers(0, ROW_BUCKETS[0], (b, t)),
                           jnp.int32),
        "xr2": jnp.asarray(rng.integers(0, ROW_BUCKETS[1], (b, t)),
                           jnp.int32),
        "xn": jnp.asarray(rng.uniform(0, 1, (b, t))),
        "xf": jnp.asarray(rng.uniform(0, 1, (b, t))),
        "xrc": jnp.asarray(rng.uniform(0, 1, (b, t))),
    }


def test_bce_loss_gradient_matches_finite_differences():
    with jax.enable_x64(True):
        cfg = CachingModelConfig(n_tables=N_TABLES, table_emb=4, row_emb=4,
                                 hidden=HIDDEN, in_len=IN_LEN)
        params = init_caching_model(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(1)
        batch = _int_batch(rng, 2, IN_LEN)
        batch["y"] = jnp.asarray(
            rng.integers(0, 2, (2, IN_LEN)).astype(np.float64))
        _fd_check(lambda p: bce_loss(p, batch), params)


def _prefetch_case(loss):
    cfg = PrefetchModelConfig(n_tables=N_TABLES, table_emb=4, row_emb=4,
                              hidden=HIDDEN, in_len=IN_LEN, out_len=OUT_LEN,
                              window=3 * OUT_LEN, loss=loss)
    params = init_prefetch_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    batch = _int_batch(rng, 2, IN_LEN)
    wlen = cfg.window
    w = _int_batch(rng, 2, wlen)
    batch.update(wt=w["xt"], wr1=w["xr1"], wr2=w["xr2"], wn=w["xn"])
    return cfg, params, batch


@pytest.mark.parametrize("loss", ["chamfer", "l2"])
def test_prefetch_loss_gradient_matches_detached_target_fd(loss):
    """grad of the real loss (targets stop-gradiented) == FD of the
    detached-target reference: the prediction branch's gradient is right
    AND the detach really cuts the target branch (if it leaked, the
    analytic grad would pick up the extra embedding-table terms and the
    comparison would blow past the f64 tolerance)."""
    with jax.enable_x64(True):
        cfg, params, batch = _prefetch_case(loss)
        wlen = cfg.window if loss == "chamfer" else cfg.out_len
        w0 = jax.lax.stop_gradient(access_reps(
            params, cfg, batch["wt"][:, :wlen], batch["wr1"][:, :wlen],
            batch["wr2"][:, :wlen], batch["wn"][:, :wlen]))

        def loss_fixed(p):
            po = prefetch_predict_batch(
                p, cfg, batch["xt"], batch["xr1"], batch["xr2"],
                batch["xn"], batch["xf"], batch["xrc"])
            if loss == "l2":
                return l2_truncated_vec(po, w0).mean()
            out = chamfer_bidirectional_vec(po, w0, cfg.alpha).mean()
            d = po[:, :, None, :] - po[:, None, :, :]
            d2 = (d * d).sum(-1)
            P = po.shape[1]
            off = 1.0 - jnp.eye(P)
            rep = ((jnp.exp(-d2 / cfg.diversity_tau) * off).sum(-1).sum(-1)
                   / (P * (P - 1)))
            return out + cfg.diversity_weight * rep.mean()

        g_real = ravel_pytree(
            jax.grad(lambda p: prefetch_loss(p, cfg, batch))(params))[0]
        g_fix = ravel_pytree(jax.grad(loss_fixed)(params))[0]
        np.testing.assert_allclose(np.asarray(g_real), np.asarray(g_fix),
                                   rtol=1e-12, atol=1e-12)
        _fd_check(loss_fixed, params)


@pytest.mark.parametrize("term", ["chamfer", "l2", "diversity"])
def test_set_loss_terms_gradient_wrt_points(term):
    """The chamfer / truncated-L2 / diversity terms FD-checked directly
    with respect to the predicted point set (no model, no detach)."""
    with jax.enable_x64(True):
        rng = np.random.default_rng(3)
        po0 = jnp.asarray(rng.normal(size=(2, OUT_LEN, 5)))
        w = jnp.asarray(rng.normal(size=(2, 3 * OUT_LEN, 5)))

        def f(po):
            if term == "chamfer":
                return chamfer_bidirectional_vec(po, w, 0.7).mean()
            if term == "l2":
                return l2_truncated_vec(po, w[:, :OUT_LEN]).mean()
            d = po[:, :, None, :] - po[:, None, :, :]
            d2 = (d * d).sum(-1)
            off = 1.0 - jnp.eye(OUT_LEN)
            return (jnp.exp(-d2 / 0.5) * off).sum(-1).sum(-1).mean()

        _fd_check(f, po0)


# ---------------------------------------------------------------------------
# Tiny end-to-end training: descent + bit-exact seeded reproducibility
# ---------------------------------------------------------------------------


@lru_cache(maxsize=None)
def _train_trace():
    from repro.core.trace import TraceGenConfig, generate_trace

    return generate_trace(TraceGenConfig(
        n_tables=3, rows_per_table=64, n_accesses=2000, seed=0,
        drift_every=10**9))


def _train_caching():
    from repro.core.belady import belady_labels

    tr = _train_trace()
    labels, _, _ = belady_labels(tr.global_id, 48)
    data = make_windows(tr, labels=labels, stride=5)
    cfg = CachingModelConfig(n_tables=3, hidden=16)
    return train_caching_model(data, cfg, epochs=4, batch_size=64, lr=1e-2)


def _train_prefetch():
    tr = _train_trace()
    data = make_prefetch_data(tr, stride=5)
    cfg = PrefetchModelConfig(n_tables=3, hidden=16)
    return train_prefetch_model(data, cfg, epochs=2, batch_size=64, lr=3e-3)


@pytest.mark.parametrize("train", [_train_caching, _train_prefetch],
                         ids=["caching", "prefetch"])
def test_tiny_training_descends_and_reproduces(train):
    """~20 optimizer steps on a 2000-access trace: the loss goes down,
    and a second same-seed run reproduces every parameter byte (the
    guarantee the learned golden files and the drift fine-tune's
    determinism contract both sit on)."""
    p1, losses = train()
    assert len(losses) >= 10
    assert np.mean(losses[-5:]) < np.mean(losses[:5]), losses
    assert np.all(np.isfinite(losses))
    p2, losses2 = train()
    assert losses == losses2
    f1 = np.asarray(ravel_pytree(p1)[0])
    f2 = np.asarray(ravel_pytree(p2)[0])
    assert np.array_equal(f1, f2)  # byte-identical, not just allclose
