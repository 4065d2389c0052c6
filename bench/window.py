"""The measured window, driven through ``serve_trace``'s ``probe`` hook.

``serve_trace`` calls the probe once per served batch, after the batch's
lookup and forward (which ends in ``block_until_ready``) and before the
RecMG outputs of that batch are staged and flushed.  So the time between two
probe calls is one batch as its client sees it in a closed loop: the
previous batch's staging and flush, then this batch's lookup, pooling and
forward.

* Warm-up: the first batches, until the fast tier is full or
  ``warmup_batches`` have been served.  The window starts when the probe
  of the last warm-up batch returns.
* Window: each later probe call closes one batch.  The first call at or
  after ``seconds`` closes the window by raising :class:`WindowClosed`.
* Kept batches: a reservoir of ``keep`` window batches, drawn from the
  seed, held by reference (device arrays) for the check after the window.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import numpy as np

COUNTERS = ("batches", "lookups", "hits", "misses", "prefetch_hits",
            "on_demand_rows", "evictions", "fetch_s", "gather_s", "model_s")


class WindowClosed(Exception):
    """Raised from the probe to end ``serve_trace`` when the window closes."""


def counters(store) -> dict:
    return {k: getattr(store.stats, k) for k in COUNTERS}


@dataclass
class Kept:
    index: int
    ids: np.ndarray
    rows: object
    dense: object
    logits: object


@dataclass
class Window:
    seconds: float
    warmup_batches: int
    keep: int
    seed: int
    on_start: Optional[Callable[["Window"], None]] = None
    on_batch: Optional[Callable[["Window", object], None]] = None
    store: object = None
    warmup_served: int = 0
    resident_at_start: int = 0
    t_start: Optional[float] = None
    t_end: Optional[float] = None
    latencies: List[float] = field(default_factory=list)
    kept: List[Kept] = field(default_factory=list)
    stats_start: dict = field(default_factory=dict)
    stats_end: dict = field(default_factory=dict)
    queries_per_batch: int = 0
    compiles_at_start: int = 0

    def __post_init__(self):
        self._rng = np.random.default_rng([int(self.seed), 7])
        self._t_prev = None

    def __call__(self, rec):
        t = time.perf_counter()
        if self.t_start is None:
            self.store = rec.store
            self.queries_per_batch = int(rec.dense.shape[0])
            full = rec.store.n_resident >= rec.store.capacity
            if full or rec.index + 1 >= self.warmup_batches:
                self.warmup_served = rec.index + 1
                self.resident_at_start = int(rec.store.n_resident)
                self.stats_start = counters(rec.store)
                if self.on_start is not None:
                    self.on_start(self)
                self.t_start = self._t_prev = time.perf_counter()
            return
        self.latencies.append(t - self._t_prev)
        self._t_prev = t
        self._reservoir(rec)
        if self.on_batch is not None:
            self.on_batch(self, rec)
        if t - self.t_start >= self.seconds:
            self.close(t)
            raise WindowClosed

    def close(self, t: Optional[float] = None):
        """End the window at ``t``, by default at the last probe call (when
        the trace ran out before ``seconds``)."""
        self.t_end = self._t_prev if t is None else t
        self.stats_end = counters(self.store)

    def _reservoir(self, rec):
        n = len(self.latencies)  # window batches so far, this one included
        item = Kept(rec.index, rec.ids, rec.rows, rec.dense, rec.logits)
        if len(self.kept) < self.keep:
            self.kept.append(item)
            return
        j = int(self._rng.integers(0, n))
        if j < self.keep:
            self.kept[j] = item

    # ---------------- results ----------------

    @property
    def batches(self) -> int:
        return len(self.latencies)

    @property
    def queries(self) -> int:
        return self.batches * self.queries_per_batch

    @property
    def window_s(self) -> float:
        return float(sum(self.latencies))

    def delta(self) -> dict:
        return {k: self.stats_end[k] - self.stats_start[k] for k in COUNTERS}
