"""Reduction of a JAX profiler trace to the device's busy time, its ops and
its idle gaps.

The traced run brackets its profiled batches with two host annotations,
``bench.window_start`` and ``bench.window_end``; their times on the
profiler's clock bound the window that the device metrics read.  The batches
before it, between ``bench.label_start`` and ``bench.label_end``, run with
the program's own ``SpanTracer`` on, whose spans label the idle gaps there.
Those spans are on ``time.perf_counter`` and are moved onto the profiler's
clock by one more annotation, ``bench.clock``, recorded beside a
``perf_counter`` reading.

Device operations are the events of the ``XLA Ops`` lines of the
``/device:`` planes (on a TPU an op's name is its HLO text), each assigned
the program execution of the ``XLA Modules`` line that it ran inside.
Where a trace has no device plane (the CPU backend), the host events that
carry an ``hlo_op`` stat stand in, so the reduction runs on a CPU trace
too.
"""
from __future__ import annotations

import glob
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

WINDOW_START = "bench.window_start"
WINDOW_END = "bench.window_end"
LABEL_START = "bench.label_start"
LABEL_END = "bench.label_end"
CLOCK_MARK = "bench.clock"


@dataclass
class DeviceOp:
    name: str  # on a TPU, the op's HLO text
    start_ns: float
    dur_ns: float
    module: str = ""  # the program it ran in, without its fingerprint
    plane: str = ""
    run: int = -1  # index of that program's execution in Profile.runs


@dataclass
class Profile:
    ops: List[DeviceOp]
    marks: Dict[str, float]
    runs: List[DeviceOp] = field(default_factory=list)

    @property
    def window(self) -> Tuple[float, float]:
        return self.marks[WINDOW_START], self.marks[WINDOW_END]

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) * 1e-9


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except Exception:  # a stat the binding cannot convert
        return {}


def _short(module: str) -> str:
    """``jit_g(1810...)`` -> ``jit_g``."""
    return module.split("(", 1)[0]


def load(path: str) -> Profile:
    """Device ops, program executions and ``bench.*`` marks of one trace.
    Each op is assigned the program execution (``XLA Modules`` event of its
    plane) that it ran inside."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: List[DeviceOp] = []
    runs: List[DeviceOp] = []
    host_ops: List[DeviceOp] = []
    marks: Dict[str, float] = {}
    for plane in data.planes:
        dev = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if dev and line.name == "XLA Ops":
                    ops.append(DeviceOp(name, ev.start_ns, ev.duration_ns,
                                        plane=plane.name))
                elif dev and line.name == "XLA Modules":
                    runs.append(DeviceOp(name, ev.start_ns, ev.duration_ns,
                                         _short(name), plane.name))
                elif dev:
                    continue
                elif name.startswith("bench."):
                    marks.setdefault(name, ev.start_ns)
                elif not name.startswith("end: "):
                    st = _stats(ev)
                    if "hlo_op" in st:  # CPU backend: ops run on host threads
                        host_ops.append(DeviceOp(
                            name, ev.start_ns, ev.duration_ns,
                            _short(str(st.get("hlo_module", ""))), "host"))
    if not ops:
        ops = host_ops
    ops.sort(key=lambda o: o.start_ns)
    runs.sort(key=lambda r: r.start_ns)
    for i, r in enumerate(runs):
        r.run = i
    for plane in {r.plane for r in runs}:
        mine = [r for r in runs if r.plane == plane]
        starts = np.array([r.start_ns for r in mine])
        for o in ops:
            if o.plane != plane:
                continue
            j = int(np.searchsorted(starts, o.start_ns, side="right")) - 1
            if j >= 0 and o.start_ns < mine[j].start_ns + mine[j].dur_ns:
                o.module, o.run = mine[j].module, mine[j].run
    return Profile(ops, marks, runs)


def clip(ops: Sequence[DeviceOp], lo: float, hi: float) -> np.ndarray:
    """``(n, 2)`` intervals of ``ops`` cut to ``[lo, hi]``."""
    iv = np.array([(o.start_ns, o.start_ns + o.dur_ns) for o in ops],
                  np.float64).reshape(-1, 2)
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def union(iv: np.ndarray) -> np.ndarray:
    """Merged, sorted, disjoint intervals."""
    if not len(iv):
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0])]
    out = [list(iv[0])]
    for a, b in iv[1:]:
        if a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return np.asarray(out)


def busy_s(prof: Profile, ops: Optional[Sequence[DeviceOp]] = None) -> float:
    """Seconds of the window in which some device op ran, averaged over
    the device planes (the chips) of the trace."""
    lo, hi = prof.window
    ops = prof.ops if ops is None else ops
    per_plane = []
    for plane in sorted({o.plane for o in ops}) or [""]:
        u = union(clip([o for o in ops if o.plane == plane], lo, hi))
        per_plane.append(float(np.sum(u[:, 1] - u[:, 0])) * 1e-9)
    return float(np.mean(per_plane))


def op_seconds(prof: Profile, pred) -> float:
    """Summed device seconds of the window's ops for which ``pred(op)``."""
    lo, hi = prof.window
    iv = clip([o for o in prof.ops if pred(o)], lo, hi)
    return float(np.sum(iv[:, 1] - iv[:, 0])) * 1e-9


def run_seconds(prof: Profile, pred) -> Tuple[float, int]:
    """Device seconds and count of the window's program executions that
    hold an op for which ``pred(op)``."""
    lo, hi = prof.window
    hit = sorted({o.run for o in prof.ops if o.run >= 0 and pred(o)})
    iv = clip([prof.runs[i] for i in hit], lo, hi)
    return float(np.sum(iv[:, 1] - iv[:, 0])) * 1e-9, len(iv)


def top_ops(prof: Profile, n: int = 10) -> List[list]:
    """The ``n`` device ops (by name) that took most time in the window."""
    lo, hi = prof.window
    tot: Dict[str, float] = {}
    for o in prof.ops:
        a, b = max(o.start_ns, lo), min(o.start_ns + o.dur_ns, hi)
        if b > a:
            tot[o.name] = tot.get(o.name, 0.0) + (b - a) * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(prof: Profile, spans: Sequence[Tuple[str, float, float]],
              n: int = 10, window: Optional[Tuple[float, float]] = None
              ) -> List[list]:
    """The ``n`` longest gaps of ``window`` (by default the profile's) in
    which no device op ran, each labelled by the host span name
    (``(label, start_ns, end_ns)`` on the profiler's clock) whose spans
    cover most of it, the innermost on a tie, else ``"between batches"``
    where spans cover less than half of it."""
    lo, hi = prof.window if window is None else window
    u = union(clip(prof.ops, lo, hi))
    edges = np.concatenate([[lo], u.ravel(), [hi]]).reshape(-1, 2)
    gaps = sorted(((a, b) for a, b in edges if b > a),
                  key=lambda g: g[0] - g[1])[:n]
    by_label: Dict[str, np.ndarray] = {}
    for label, s, e in spans:
        by_label.setdefault(label, []).append((s, e))
    by_label = {k: union(np.asarray(v, np.float64))
                for k, v in by_label.items()}
    out = []
    for a, b in gaps:
        best = (0.0, 0.0, "between batches")
        for label, iv in by_label.items():
            c = np.clip(iv, a, b)
            cover = float(np.sum(c[:, 1] - c[:, 0]))
            if cover <= 0:
                continue
            inside = iv[(iv[:, 1] > a) & (iv[:, 0] < b)]
            extent = float(np.sum(inside[:, 1] - inside[:, 0]))
            best = max(best, (cover, -extent, label))
        label = best[2] if best[0] >= 0.5 * (b - a) else "between batches"
        out.append([label, (b - a) * 1e-9])
    return out


def host_spans(events: Sequence[dict], offset_ns: float
               ) -> List[Tuple[str, float, float]]:
    """``SpanTracer`` complete events (microseconds of ``perf_counter``) as
    ``(cat.name, start_ns, end_ns)`` on the profiler's clock."""
    out = []
    for e in events:
        if e.get("ph") != "X":
            continue
        s = e["ts"] * 1e3 + offset_ns
        out.append((f"{e['cat']}.{e['name']}", s, s + e["dur"] * 1e3))
    return out
