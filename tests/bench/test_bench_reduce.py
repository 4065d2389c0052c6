"""Trace reduction: busy union, op times, gaps labelled by host spans."""
import numpy as np
import pytest

from bench import profile
from bench.profile import DeviceOp, Profile


def _prof():
    ops = [DeviceOp("a", 100, 50, "m1", "/device:TPU:0"),
           DeviceOp("b", 120, 60, "m1", "/device:TPU:0"),   # overlaps a
           DeviceOp("a", 400, 100, "m2", "/device:TPU:0"),
           DeviceOp("c", 950, 100, "m2", "/device:TPU:0")]  # cut at 1000
    marks = {profile.WINDOW_START: 0.0, profile.WINDOW_END: 1000.0,
             profile.CLOCK_MARK: 0.0}
    return Profile(ops, marks)


def test_busy_union_and_window():
    p = _prof()
    assert p.window_s == pytest.approx(1e-6)
    # [100, 180) + [400, 500) + [950, 1000) = 230 ns
    assert profile.busy_s(p) == pytest.approx(230e-9)


def test_op_seconds_and_top_ops():
    p = _prof()
    assert profile.op_seconds(p, lambda o: o.name == "a") == pytest.approx(
        150e-9)
    top = profile.top_ops(p)
    assert [n for n, _ in top] == ["a", "b", "c"]
    assert top[0][1] == pytest.approx(150e-9)


def test_idle_gaps_labelled_by_the_span_covering_most():
    p = _prof()
    spans = [("store.lookup", 180, 420), ("store.admit", 200, 300),
             ("store.populate", 520, 560), ("store.populate", 600, 940),
             ("store.gather", 10, 30)]
    gaps = profile.idle_gaps(p, spans)
    # gaps: [0,100) [180,400) [500,950): longest first
    assert [round(s * 1e9) for _, s in gaps] == [450, 220, 100]
    # [500,950) is 380/450 populate; [180,400) all lookup, half admit (the
    # lookup covers more); [0,100) only 20% gather.
    assert [g[0] for g in gaps] == ["store.populate", "store.lookup",
                                    "between batches"]


def test_nested_spans_tie_to_the_innermost():
    p = _prof()
    spans = [("store.lookup", 150, 420), ("store.admit", 180, 400)]
    assert profile.idle_gaps(p, spans, n=2)[1][0] == "store.admit"


def test_host_spans_move_onto_the_profiler_clock():
    ev = [{"ph": "X", "cat": "store", "name": "gather", "ts": 2.0, "dur": 1.5},
          {"ph": "i", "cat": "pf", "name": "late", "ts": 3.0}]
    assert profile.host_spans(ev, 1000.0) == [("store.gather", 3000.0,
                                               4500.0)]


def test_union_merges_touching_intervals():
    u = profile.union(np.array([[5, 7], [1, 3], [3, 4], [6, 9]], float))
    assert u.tolist() == [[1, 4], [5, 9]]


# A trace recorded on one TPU v5e: the first three window batches of
# dlrm-recmg.zipf_mid.lru (8 queries a batch).  Its XLA Modules line holds
# 18 program executions inside the bench.window_start/end marks, at
# 42,856,265 and 742,566,373 ns: jit_gov (the gather with overflow rows)
# 3 x about 5.17 ms, the forward jit__lambda 3 x about 3.53 ms, scatters,
# slices and the pooling sum.  The numbers below are those durations summed
# by hand from the trace viewer's list of events.
FIXTURE = (__import__("pathlib").Path(__file__).parent / "fixtures"
           / "lru_window.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return profile.load(str(FIXTURE))


def test_recorded_window_and_busy_time(recorded):
    assert recorded.window_s == pytest.approx(0.699710108)
    assert len(recorded.runs) == 18
    assert all(o.run >= 0 for o in recorded.ops)
    # The device ops tile their programs: busy = the 18 executions' sum.
    assert profile.busy_s(recorded) == pytest.approx(0.047770752)


def test_recorded_kernel_and_forward_times(recorded):
    import importlib.util
    import json
    from types import SimpleNamespace

    from bench import costs, spec

    def reader(name):
        s = importlib.util.spec_from_file_location(
            name, spec.BENCH_DIR / "metrics" / f"{name}.py")
        m = importlib.util.module_from_spec(s)
        s.loader.exec_module(m)
        return m.read

    cfg = json.loads((spec.BENCH_DIR / "configs" / "dlrm-recmg.json")
                     .read_text())
    peak = spec.peaks("TPU v5 lite")
    ctx = SimpleNamespace(
        config=cfg, peak=peak, costs=costs, profile=recorded,
        busy_s=profile.busy_s(recorded),
        profile_seconds=lambda p: profile.op_seconds(recorded, p),
        program_seconds=lambda p: profile.run_seconds(recorded, p),
        profiled=SimpleNamespace(batches=3, queries=24, batch_queries=8,
                                 unique_rows=[100_000] * 3))
    kernel = 0.003245333  # 3 x the tpu_custom_call inside jit_gov
    assert ctx.profile_seconds(
        lambda o: o.module == "jit_gov" and "tpu_custom_call" in o.name
    ) == pytest.approx(kernel)
    assert reader("gather_rows_roofline")(ctx) == pytest.approx(
        100 * 3e5 * 128 * 4 * 2 / 819e9 / kernel)
    fwd = 0.0105893  # 3 executions of the forward program
    assert ctx.program_seconds(lambda o: "[366924,1024]" in o.name) == (
        pytest.approx(fwd), 3)
    assert reader("dense_forward_roofline")(ctx) == pytest.approx(
        100 * 3 * costs.forward_bytes(cfg, 8) / 819e9 / fwd)
    assert reader("device.idle_share")(ctx) == pytest.approx(
        100 * (1 - 0.047770752 / 0.699710108))
    assert reader("step.mfu")(ctx) == pytest.approx(
        100 * 2 * 424_554_752 * 24 / 0.699710108 / 197e12)
