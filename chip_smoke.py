#!/usr/bin/env python3
"""Chip smoke: serve dlrm-recmg at its published widths on one TPU.

    python chip_smoke.py

Drives ``python -m repro.launch.serve --published`` (856 tables, D=128,
P=20, the published MLPs; 8,192 rows per table unless host RAM holds the
full 72,704) in this one process, 16 queries per batch, 10 batches:

  (a) ``--policy lru``, synchronous;
  (b) ``--policy recmg --model learned --train-epochs 1 --async-prefetch``;
  (c) on the first batch of each phase, a correctness check: the served
      rows equal ``host[ids]`` bit for bit, and the served logits agree
      with a float32 forward under ``default_matmul_precision("highest")``
      within ``LOGIT_TOL`` (below).

For each phase it prints the device kind, which gather served the lookups
(``kernel`` when the compiled lookup program holds a ``tpu_custom_call``,
else ``xla``), the seconds spent compiling (persistent-cache reads
included), p50/p99 batch ms, hit rate, on-demand rows and the device's
``peak_bytes_in_use``.  On a TPU at D=128 the kernel path must have served.

The last line is ``{"ok": true, "device": {...}}``, printed only when every
phase passed.  Without a TPU, or when any phase fails, the script exits
nonzero and prints no such line; it never falls back to the CPU.
"""
from __future__ import annotations

import gc
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

BATCHES = 10
BATCH_QUERIES = 16
PHASES = {
    "lru_sync": ["--policy", "lru"],
    "recmg_learned_async": ["--policy", "recmg", "--model", "learned",
                            "--train-epochs", "1", "--async-prefetch"],
}
# Served logits vs the float32 reference: |served - ref| <= LOGIT_TOL *
# max|ref| over the batch.  The served forward computes in bfloat16 (the
# config's compute dtype): the inputs of the interaction and of each of the
# eight MLP layers on the logit's path are rounded to bf16, unit roundoff
# 2^-9, so about 9 * 2^-9 = 1.8e-2 if those errors add up, before any
# cancellation in the final dot product.  5e-2 leaves that a factor of
# about three; wrong rows, tables or pooling give errors of order 1.
LOGIT_TOL = 5e-2


def reference_logits(params, dense, pooled):
    """Plain float32 DLRM forward at the highest matmul precision: bottom
    MLP, pairwise dot interaction over [bottom, tables], top MLP."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def mlp(p, x):
        n = len(p["w"])
        for i, (w, b) in enumerate(zip(p["w"], p["b"])):
            x = x @ w.astype(jnp.float32) + b.astype(jnp.float32)
            if i < n - 1:
                x = jnp.maximum(x, 0.0)
        return x

    with jax.default_matmul_precision("highest"):
        bot = mlp(params["bottom"], dense.astype(jnp.float32))
        z = jnp.concatenate([bot[:, None, :], pooled], axis=1)
        zz = jnp.einsum("bfd,bgd->bfg", z, z)
        iu, ju = np.triu_indices(z.shape[1], k=1)
        top_in = jnp.concatenate([bot, zz[:, iu, ju]], axis=1)
        return mlp(params["top"], top_in)[:, 0]


class CompileMeter:
    """Seconds JAX spends in backend compilation (persistent-cache reads
    included) and how many programs it compiled or read back."""

    def __init__(self):
        from jax import monitoring

        self.seconds, self.programs, self.cache_hits = 0.0, 0, 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.seconds, self.programs, self.cache_hits


class FirstBatchCheck:
    """``serve_trace`` probe: checks batch 0 (rows bit for bit, logits
    within ``LOGIT_TOL``) and records which gather served it."""

    def __init__(self):
        self.result = None
        self.shape = None

    def __call__(self, rec):
        if rec.index != 0:
            return
        import jax
        import numpy as np

        cfg, store = rec.cfg, rec.store
        self.shape = {"tables": cfg.n_tables, "D": cfg.emb_dim,
                      "P": cfg.multi_hot, "rows_per_table": cfg.rows_per_table,
                      "queries_per_batch": int(rec.dense.shape[0])}
        served = np.asarray(rec.rows)
        want = rec.host[rec.ids]
        rows_equal = served.shape == want.shape and bool(
            np.array_equal(served.view(np.uint32), want.view(np.uint32)))
        pooled = want.reshape(-1, cfg.n_tables, cfg.multi_hot, cfg.emb_dim)
        pooled = pooled.sum(axis=2, dtype=np.float32)
        ref = np.asarray(jax.jit(reference_logits)(
            rec.params, rec.dense, jax.numpy.asarray(pooled)))
        got = np.asarray(rec.logits, np.float32)
        err = float(np.max(np.abs(got - ref)))
        scale = float(np.max(np.abs(ref)))
        text = store.gather_program_text(rec.ids.size)
        self.result = {
            "rows_bit_equal": rows_equal,
            "logit_max_abs_err": err, "logit_ref_max_abs": scale,
            "logit_ok": bool(np.isfinite(got).all() and got.shape == ref.shape
                             and err <= LOGIT_TOL * scale),
            "gather": "kernel" if "tpu_custom_call" in text else "xla",
            "store_use_kernel": bool(store.use_kernel),
        }


def run_phase(name, extra, meter, device):
    from repro.launch import serve

    check = FirstBatchCheck()
    c0 = meter.snapshot()
    t0 = time.perf_counter()
    argv = ["--published", "--batches", str(BATCHES),
            "--batch-queries", str(BATCH_QUERIES)] + extra
    res = serve.main(argv, probe=check)
    wall = time.perf_counter() - t0
    c1 = meter.snapshot()
    stats = device.memory_stats() or {}
    out = {
        "phase": name, "device_kind": device.device_kind,
        **(check.result or {"gather": "none", "rows_bit_equal": False,
                            "logit_ok": False}),
        "compile_s": c1[0] - c0[0], "compiled_programs": c1[1] - c0[1],
        "cache_hits": c1[2] - c0[2],
        "p50_batch_ms": res["p50_batch_ms"],
        "p99_batch_ms": res["p99_batch_ms"],
        "hit_rate": res["hit_rate"], "on_demand_rows": res["on_demand_rows"],
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "phase_wall_s": wall, "batches": res["batches"],
        **(check.shape or {}),
    }
    problems = []
    if check.result is None:
        problems.append("no batch was served")
    else:
        if not check.result["rows_bit_equal"]:
            problems.append("served rows differ from host[ids]")
        if not check.result["logit_ok"]:
            problems.append("logits outside LOGIT_TOL of the f32 reference")
        if check.result["gather"] != "kernel":
            problems.append("the Pallas gather did not serve the lookups")
    if res["batches"] < 8:
        problems.append(f"only {res['batches']} batches measured")
    out["problems"] = problems
    print(f"PHASE {json.dumps(out, sort_keys=True)}", flush=True)
    return not problems


def main() -> int:
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {device.platform!r}",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    print(f"device: {device.platform} {device.device_kind} "
          f"x{len(jax.devices())}; compile cache: {cache}", flush=True)
    meter = CompileMeter()
    ok = True
    for name, extra in PHASES.items():
        try:
            ok = run_phase(name, extra, meter, device) and ok
        except Exception:  # report this phase, run the next, exit nonzero
            traceback.print_exc()
            print(f"PHASE {name} FAILED", flush=True)
            ok = False
        gc.collect()
    seconds, programs, hits = meter.snapshot()
    print(f"total compile: {seconds:.3f} s over {programs} programs "
          f"({hits} persistent-cache hits)", flush=True)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
