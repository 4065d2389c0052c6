#!/usr/bin/env python3
"""Benchmark harness: one cell, one seed, one process.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell named in ``BENCHMARK.json`` (configuration with its tiering
policy, traffic mix and cell parameters, each from a file of its own; see ``bench/spec.py``), warms
up, serves through the program's ``repro.launch.serve.serve_trace`` for
``--seconds`` seconds (``bench/window.py``), checks what the window served
against the plain reference (``bench/check.py``) and prints one JSON line
last on standard output.  ``--trace 0`` reports the cell's end-to-end
metrics; ``--trace 1`` reports its per-layer metrics, with ``busy_s``,
``window_s`` and a ``breakdown``, from three parts of the window:

* labelled: the first two batches, profiled with the program's
  ``SpanTracer`` on; only the breakdown's idle gaps are read here, each
  labelled by the host span that covers it;
* profiled: the next ``traced_batches``, profiled with the tracer off;
  the device metrics, ``busy_s``, ``window_s`` and the top device ops;
* untraced: the rest; the metrics read from the store's counters.

JAX's persistent compilation cache is kept in ``.jax_cache/`` inside the
checkout, whatever the environment says.

It needs a TPU: without one, or with fewer chips than the cell asks for, it
exits 1 and prints no result.
"""
from __future__ import annotations

import os
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import hashlib  # noqa: E402
import json  # noqa: E402
import pickle  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench import check, costs, profile, spec  # noqa: E402
from bench.traffic import World, generate, load_mix  # noqa: E402
from bench.window import (COUNTERS, Window, WindowClosed,  # noqa: E402
                          counters)

# Stream seeds of the profiling trace and of the served trace.  The served
# ids are the cell's own, the same for every ``--seed``: runs with one seed
# agree within about 1% and runs with different seeds did not, so a seed
# that drew the ids changed the work.  ``--seed`` draws the weights and the
# window batches the check compares.
PROFILE_STREAM = (0, 0)
SERVED_STREAM = (0, 1)
LABELLED_BATCHES = 2  # window batches traced with the program's spans


class NoChip(RuntimeError):
    pass


class GcMeter:
    """Garbage collections, and the seconds they took, while ``on``."""

    def __init__(self):
        self.on, self.count, self.full, self.seconds = False, 0, 0, 0.0
        self._t = 0.0
        gc.callbacks.append(self.record)

    def record(self, phase, info):
        if not self.on:
            return
        if phase == "start":
            self._t = time.perf_counter()
            return
        self.count += 1
        self.full += info["generation"] == 2
        self.seconds += time.perf_counter() - self._t


class CompileMeter:
    """Programs JAX compiled (or read back from its persistent cache)."""

    def __init__(self):
        from jax import monitoring

        self.seconds, self.programs = 0.0, 0
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def _trace(table_id, row_id, rows):
    from repro.core.trace import Trace

    return Trace(table_id.astype(np.int32), row_id.astype(np.int64),
                 np.asarray(rows, np.int64))


def learned_model(cell, world, rows, capacity, cache_dir: Path):
    """The RecMG models, trained on a profiling trace of the same world
    that is never served; kept in ``cache_dir`` for the next run of the
    cell, keyed by everything the training reads."""
    from repro.core.model_runtime import (LearnedModelConfig,
                                          LearnedRecMGModel)

    p = cell.params
    lcfg = LearnedModelConfig(**p["learned"])
    key = hashlib.sha256(json.dumps(
        [cell.config, world.mix, p["learned"], p["profile_batches"],
         p["batch_queries"], capacity], sort_keys=True).encode()).hexdigest()
    path = cache_dir / f"{cell.name}-{key[:16]}.pkl"
    n_q = int(p["profile_batches"]) * int(p["batch_queries"])
    if path.exists():
        with open(path, "rb") as f:
            saved = pickle.load(f)
        return LearnedRecMGModel(lcfg, saved["mcfg"], saved["pcfg"],
                                 saved["cparams"], saved["pparams"],
                                 saved["cand"], capacity, None), "cached"
    tab, row = generate(world, cell.config["multi_hot"], n_q, PROFILE_STREAM)
    model = LearnedRecMGModel.train_from_trace(
        _trace(tab, row, rows), capacity, lcfg)
    import jax

    saved = {"mcfg": model.mcfg, "pcfg": model.pcfg,
             "cparams": jax.device_get(model.cparams),
             "pparams": jax.device_get(model.pparams),
             "cand": model.cand_ids}
    cache_dir.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(".tmp")
    with open(tmp, "wb") as f:
        pickle.dump(saved, f)
    tmp.replace(path)
    return model, "trained"


def run_cell(workload: str, seed: int, seconds: float, traced: bool, *,
             root: Path = ROOT, bench_dir: Path = spec.BENCH_DIR,
             require_tpu: bool = True) -> dict:
    cell = spec.load_cell(workload, root, bench_dir)
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu" or len(devices) < cell.chips):
        raise NoChip(f"cell {workload} needs {cell.chips} TPU chip(s); JAX "
                     f"found {len(devices)} {dev.platform} device(s)")
    peak = spec.peaks(dev.device_kind, bench_dir) if require_tpu else None
    if dev.platform == "tpu":
        from repro.launch.compile_cache import enable_compile_cache

        log(f"compile cache: {enable_compile_cache()}")
        # Every program is cached, however fast it compiled.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    meter, gcm = CompileMeter(), GcMeter()
    from repro.launch.serve import serve_trace

    p, config = cell.params, cell.config
    ref = spec.reference_module(cell)
    cfg = spec.model_config(config)
    rows = spec.table_rows(config)
    b = int(p["batch_queries"])
    capacity = int(float(config["fast_tier_frac"]) * sum(rows))
    t0 = time.perf_counter()
    params = jax.block_until_ready(ref.init_params(config, seed))
    t_params = time.perf_counter() - t0

    t0 = time.perf_counter()
    world = World(load_mix(cell.traffic, bench_dir / "traffic"), rows)
    n_q = b * (int(p["warmup_batches"]) + int(p["window_batches"]))
    tab, row = generate(world, config["multi_hot"], n_q, SERVED_STREAM,
                        query_offset=b * int(p.get("profile_batches", 0)))
    trace = _trace(tab, row, rows)
    del tab, row
    t_traffic = time.perf_counter() - t0

    outputs, how, t_model, t_outputs = None, "none", 0.0, 0.0
    if config["policy"] == "recmg":
        t0 = time.perf_counter()
        model, how = learned_model(cell, world, rows, capacity,
                                   root / ".bench_cache")
        model.geom = trace
        t_model = time.perf_counter() - t0
        t0 = time.perf_counter()
        outputs = model.outputs_for(trace)
        t_outputs = time.perf_counter() - t0
        del model
    del world
    log(f"setup: params {t_params:.3f} s, traffic {t_traffic:.3f} s "
        f"({len(trace)} ids, {n_q} queries), model {how} {t_model:.3f} s, "
        f"outputs {t_outputs:.3f} s; capacity {capacity} rows")

    tracer = prof_dir = None
    labelled = LABELLED_BATCHES
    traced_batches = int(p.get("traced_batches", 3))
    prof = SimpleNamespace(phase="off", ids=[], at=0, stats=None)
    if traced:
        from repro.obs.tracing import SpanTracer, install_tracer

        tracer = install_tracer(SpanTracer())
        tracer.enabled = False  # on for the labelled batches only
        prof_dir = tempfile.mkdtemp(prefix="bench-profile-")

    def mark(name):
        from jax.profiler import TraceAnnotation

        with TraceAnnotation(name):
            pass

    def on_start(w):
        w.compiles_at_start = meter.programs
        # The set-up's objects are kept for the whole run: move them out of
        # the collector's way before the window, as a server does once up.
        gc.collect()
        gc.freeze()
        gcm.on = True
        if not traced:
            return
        from jax.profiler import ProfileOptions, TraceAnnotation

        opts = ProfileOptions()
        opts.python_tracer_level = 0  # it would slow every host step
        jax.profiler.start_trace(prof_dir, profiler_options=opts)
        with TraceAnnotation(profile.CLOCK_MARK):
            prof.clock = time.perf_counter()
        mark(profile.LABEL_START)
        tracer.enabled = True
        prof.phase = "labelled"

    def advance(w):
        """Move the traced run to its next part at a batch boundary."""
        if prof.phase == "labelled":
            tracer.enabled = False
            mark(profile.LABEL_END)
            mark(profile.WINDOW_START)
            prof.phase, prof.at = "profiled", w.batches
        elif prof.phase == "profiled":
            mark(profile.WINDOW_END)
            jax.profiler.stop_trace()
            prof.phase, prof.at = "untraced", w.batches
            prof.stats = counters(w.store)

    def on_batch(w, rec):
        if prof.phase == "labelled" and w.batches >= labelled:
            advance(w)
        elif prof.phase == "profiled":
            prof.ids.append(rec.ids)
            if w.batches - prof.at >= traced_batches:
                advance(w)

    window = Window(seconds, int(p["warmup_batches"]), int(p["kept_batches"]),
                    seed, on_start=on_start, on_batch=on_batch)
    exhausted = False
    try:
        serve_trace(cfg, params, trace, capacity, config["policy"], outputs,
                    batch_queries=b, probe=window)
        exhausted = True
    except WindowClosed:
        pass
    if window.t_start is None:
        raise RuntimeError("the trace ended inside the warm-up")
    if exhausted:
        window.close()
        log(f"the trace ran out after {window.batches} window batches: "
            f"the window ended at {window.window_s:.3f} s")
    gc.callbacks.remove(gcm.record)
    gc.unfreeze()
    while prof.phase in ("labelled", "profiled"):
        advance(window)
    compiles = meter.programs - window.compiles_at_start
    log(f"window: {window.batches} batches, {window.queries} queries, "
        f"{window.window_s:.6f} s after {window.warmup_served} warm-up "
        f"batches, {window.resident_at_start} of {capacity} fast-tier rows "
        f"resident; compiles inside the window: {compiles}; garbage "
        f"collections inside it: {gcm.count} ({gcm.full} full), "
        f"{gcm.seconds:.6f} s")
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}

    store = window.store
    pallas = None
    if dev.platform == "tpu":
        text = store.gather_program_text(b * cfg.n_tables * cfg.multi_hot)
        pallas = bool(store.use_kernel and "tpu_custom_call" in text)
        log(f"gather: {'pallas kernel' if pallas else 'xla'}")
    problems = []
    if pallas is False:
        problems.append("the Pallas gather did not serve the window")

    lat = np.asarray(window.latencies)
    result = {"attempted": window.queries, "failed": 0}
    if not traced:
        values = {
            "qps": window.queries / window.window_s,
            "batch_ms_p50": float(np.percentile(lat, 50) * 1e3),
            "batch_ms_p90": float(np.percentile(lat, 90) * 1e3),
            "setup_s": window.t_start - T_PROCESS,
        }
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}
    else:
        from repro.obs import MetricsRegistry, reconcile
        from repro.obs.tracing import install_tracer

        reg = MetricsRegistry()
        store.publish_metrics(reg)
        problems += [f"reconcile: {x}"
                     for x in reconcile(metrics=reg.snapshot(), strict=False)]
        install_tracer(None)
        lat = window.latencies
        parts = {"labelled": lat[:labelled],
                 "profiled": lat[labelled:labelled + traced_batches],
                 "untraced": lat[labelled + traced_batches:]}
        log("ms per batch: " + ", ".join(
            f"{k} {np.mean(v) * 1e3:.3f} over {len(v)}" if v else f"{k} -"
            for k, v in parts.items()))
        pr = profile.load(profile.find_xplane(prof_dir))
        shutil.rmtree(prof_dir, ignore_errors=True)
        spans = profile.host_spans(tracer.chrome_trace()["traceEvents"],
                                   pr.marks[profile.CLOCK_MARK]
                                   - prof.clock * 1e9)
        n_untraced = window.batches - prof.at
        rest = (window.delta() if prof.stats is None else
                {k: window.stats_end[k] - prof.stats[k] for k in COUNTERS})
        busy = profile.busy_s(pr)
        ctx = SimpleNamespace(
            cell=cell, config=config, policy=config["policy"], peak=peak,
            costs=costs, profile=pr, busy_s=busy,
            profile_seconds=lambda pred: profile.op_seconds(pr, pred),
            program_seconds=lambda pred: profile.run_seconds(pr, pred),
            window=SimpleNamespace(batches=n_untraced,
                                   queries=n_untraced * b,
                                   seconds=float(sum(parts["untraced"])),
                                   delta=rest),
            profiled=SimpleNamespace(
                batches=len(prof.ids), queries=len(prof.ids) * b,
                unique_rows=[np.unique(np.asarray(i)).size
                             for i in prof.ids],
                batch_queries=b))
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"], bench_dir)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        result["metrics"] = metrics
        device.update(busy_s=busy, window_s=pr.window_s)
        result["breakdown"] = {
            "device_ops": profile.top_ops(pr),
            "idle_gaps": profile.idle_gaps(
                pr, spans, window=(pr.marks[profile.LABEL_START],
                                   pr.marks[profile.LABEL_END]))}
        del spans, tracer
    result["device"] = device

    # Free the program's state before the reference runs on the device.
    kept = window.kept
    window.store = window.kept = store = None
    del trace, outputs, window
    gc.collect()
    t0 = time.perf_counter()
    readings = check.compare(kept, config, params, ref)
    checks = check.judge(readings, config)
    ok = check.passed(checks, readings) and not problems
    log(f"check: {readings['batches']} kept batches, {readings['queries']} "
        f"queries, {time.perf_counter() - t0:.3f} s")
    for x in problems:
        log(f"problem: {x}")
    if not ok:
        result["failed"] = readings["queries"]
    result["correct"] = ok
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    try:
        res = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        log(f"no result: {e}")
        return 1
    checks = res.pop("checks")
    for name, c in checks.items():
        log(f"{name} {c['value']!r} limit {c['limit']!r}")
    res = {"correct": res.pop("correct"), **res, "checks": checks}
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
