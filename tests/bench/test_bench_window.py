"""The measured window keeps the running sum of the served batches' steps
over the window's batches, and over none of the warm-up's."""
from types import SimpleNamespace

import numpy as np
import pytest

from bench.window import COUNTERS, Window, WindowClosed


def _rec(index, steps, resident=10):
    store = SimpleNamespace(n_resident=resident, capacity=100,
                            stats=SimpleNamespace(**dict.fromkeys(COUNTERS,
                                                                  0)))
    return SimpleNamespace(index=index, store=store, ids=np.arange(4),
                           rows=None, dense=np.zeros((2, 3)), logits=None,
                           steps=steps)


def test_steps_sum_the_window_batches_only():
    seen = []
    w = Window(60.0, 3, 1, 2**31 + 1,
               on_batch=lambda w, rec: seen.append(dict(w.steps)))
    for i in range(3):  # warm-up: its steps are not the window's
        w(_rec(i, {"h2d_bytes": 1000, "lookup_s": 9.0}))
    assert w.t_start is not None and w.steps == {}
    for i in range(3, 7):
        w(_rec(i, {"h2d_bytes": i, "lookup_s": 0.5, "overflow_rows": 2}))
    assert w.batches == 4
    assert w.steps == {"h2d_bytes": 3 + 4 + 5 + 6, "lookup_s": 2.0,
                       "overflow_rows": 8}
    # A reader of the untraced part takes the sums less a snapshot.
    assert seen[1] == {"h2d_bytes": 7, "lookup_s": 1.0, "overflow_rows": 4}


def test_the_closing_batch_is_summed():
    w = Window(0.0, 1, 1, 5)
    w(_rec(0, {"h2d_bytes": 1}))
    with pytest.raises(WindowClosed):
        w(_rec(1, {"h2d_bytes": 7}))
    assert w.batches == 1 and w.steps == {"h2d_bytes": 7}
