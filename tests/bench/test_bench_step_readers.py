"""The per-layer readers of named device programs, on a synthetic profile:
they sum their programs' executions in the window per profiled batch, and
read nothing where no program carries their names.  The readers of the
served batches' steps average them over the untraced batches."""
import importlib.util
from types import SimpleNamespace

import pytest

from bench import profile, spec
from bench.profile import DeviceOp, Profile

TPU = "/device:TPU:0"


def _reader(name):
    s = importlib.util.spec_from_file_location(
        name, spec.BENCH_DIR / "metrics" / f"{name}.py")
    m = importlib.util.module_from_spec(s)
    s.loader.exec_module(m)
    return m.read


def _ctx(modules, batches=2):
    """One execution per ``(module, start, dur)``, each holding one op;
    the window is [0, 10,000) ns."""
    runs, ops = [], []
    for i, (mod, start, dur) in enumerate(modules):
        runs.append(DeviceOp(f"{mod}(123)", start, dur, mod, TPU, i))
        ops.append(DeviceOp(f"fusion.{i}", start, dur, mod, TPU, i))
    prof = Profile(ops, {profile.WINDOW_START: 0.0,
                         profile.WINDOW_END: 10_000.0}, runs)
    return SimpleNamespace(
        profile=prof,
        program_seconds=lambda pred: profile.run_seconds(prof, pred),
        profiled=SimpleNamespace(batches=batches))


PROGRAMS = [("jit_store_write", 100, 300), ("jit_g", 400, 500),
            ("jit_pool_bags", 900, 100), ("jit_dense_forward", 1000, 2000),
            ("jit_store_write_quant", 5000, 200),
            ("jit_dense_forward", 9500, 1000)]  # cut at the window's end


@pytest.mark.parametrize("name,ns", [
    ("forward.device_ms_per_batch", 100 + 2000 + 500),
    ("store.write_device_ms_per_batch", 300 + 200),
])
def test_reader_sums_its_programs_per_batch(name, ns):
    assert _reader(name)(_ctx(PROGRAMS)) == pytest.approx(ns * 1e-6 / 2)


@pytest.mark.parametrize("name", ["forward.device_ms_per_batch",
                                  "store.write_device_ms_per_batch"])
def test_reader_is_silent_without_its_programs(name):
    # A program that names its programs otherwise (the forward and the
    # scatter as jit__lambda), no profile, or no profiled batch.
    ctx = _ctx([("jit__lambda", 100, 300), ("jit_g", 400, 500)])
    assert _reader(name)(ctx) is None
    assert _reader(name)(SimpleNamespace(profile=None)) is None
    assert _reader(name)(_ctx(PROGRAMS, batches=0)) is None


def _steps_ctx(steps, batches):
    return SimpleNamespace(window=SimpleNamespace(batches=batches,
                                                  steps=steps))


STEPS = {"h2d_bytes": 3 * 72_500_000, "overflow_rows": 66, "lookup_s": 0.2}


@pytest.mark.parametrize("name,value", [
    ("serve.h2d_mb_per_batch", 72.5),
    ("store.overflow_rows_per_batch", 22.0),
])
def test_step_reader_averages_the_untraced_batches(name, value):
    assert _reader(name)(_steps_ctx(STEPS, 3)) == pytest.approx(value)


@pytest.mark.parametrize("name", ["serve.h2d_mb_per_batch",
                                  "store.overflow_rows_per_batch"])
def test_step_reader_is_silent_without_its_steps(name):
    assert _reader(name)(_steps_ctx(STEPS, 0)) is None
    assert _reader(name)(_steps_ctx({"lookup_s": 0.2}, 3)) is None
