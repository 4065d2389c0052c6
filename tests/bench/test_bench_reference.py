"""The reference's slow tier follows the serve path's documented recipe,
block boundaries included, without taking the program's table."""
import numpy as np
import pytest

from bench import spec
from repro.launch.serve import make_host_table


def test_slow_tier_rows_match_the_recipe():
    ref = spec.reference_module(spec.load_cell("dlrm-recmg.recmg_steady"))
    n, d = 300_000, 4  # spans two of the reference's blocks
    host = make_host_table(n, d)
    ids = np.array([0, 5, 262_143, 262_144, 299_999, 5, 131_072])
    assert np.array_equal(ref.slow_tier_rows(n, d, ids), host[ids])
    with pytest.raises(IndexError):
        ref.slow_tier_rows(n, d, [n])
