"""Traffic generator: one general generator, one data file per mix.

A mix (``bench/traffic/<name>.json``) holds only parameters.  Every query
draws ``multi_hot`` ids from every table, in table order, so the served id
stream reshapes to ``(queries, tables, multi_hot)`` and ``serve_trace``'s
``(B, T, P, D)`` reshape pools real per-table bags.  Each id comes from one
of four components, as in the program's synthetic trace generator:

* ``popular``: a zipf rank over the table's rows, through a keyed
  permutation ``(rank * 2654435761 + salt) % rows`` (the hot rows);
* ``cluster``: a row of the query's user-cluster profile; consecutive
  queries stay in one cluster with probability ``session_stay``;
* ``successor``: the previous id in the same table plus that table's jump
  (a chain of successors resolves to ``base + k * jump`` in one pass);
* ``stream``: an advancing front per table plus a small jitter (ids with
  long reuse distances).

The *world* (salts, cluster profiles, jumps, stream fronts) comes from the
mix's ``world_seed`` alone.  The stream seed draws only the ids served, so
two stream seeds over one world share their hot rows, the way a deployment's
model is trained on history from the same population it then serves.
Everything is vectorized: no per-access Python.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

MIX_DIR = Path(__file__).resolve().parent
_KEY_MUL = 2654435761  # odd: a bijection modulo any power of two
_COMPONENTS = ("popular", "cluster", "successor", "stream")


def load_mix(name: str, mix_dir: Path = MIX_DIR) -> dict:
    """The mix ``name``: the parameters in ``<mix_dir>/<name>.json``."""
    mix = json.loads((Path(mix_dir) / f"{name}.json").read_text())
    shares = [float(mix["components"][c]) for c in _COMPONENTS]
    if min(shares) < 0 or abs(sum(shares) - 1.0) > 1e-9:
        raise ValueError(f"mix {name}: component shares {shares} must be "
                         "non-negative and sum to 1")
    return mix


def _zipf_cdf(a: float, n: int) -> np.ndarray:
    w = np.arange(1, n + 1, dtype=np.float64) ** (-a)
    cdf = np.cumsum(w)
    return cdf / cdf[-1]


class World:
    """What every stream of one mix over one set of tables shares."""

    def __init__(self, mix: dict, rows_per_table):
        self.mix = mix
        self.rows = np.asarray(rows_per_table, np.int64)
        t = self.rows.size
        rng = np.random.default_rng(int(mix["world_seed"]))
        self.salt = rng.integers(0, 2**31, size=t)
        nc, cs = int(mix["n_clusters"]), int(mix["cluster_size"])
        # Profile rows drawn in [0, rows[t]) per table, kept as int32.
        self.cluster_rows = (rng.random((nc, t, cs), np.float32)
                             * self.rows[None, :, None]).astype(np.int32)
        np.minimum(self.cluster_rows, (self.rows - 1)[None, :, None],
                   out=self.cluster_rows)
        lo_f, hi_f = mix["successor_jump"]
        lo = np.maximum(1, (self.rows * lo_f).astype(np.int64))
        hi = np.maximum(lo + 1, (self.rows * hi_f).astype(np.int64))
        self.jump = lo + (rng.random(t) * (hi - lo)).astype(np.int64)
        self.stream_base = (rng.random(t) * self.rows).astype(np.int64)
        self._cdfs: dict = {}

    def cdf(self, n: int) -> np.ndarray:
        if n not in self._cdfs:
            self._cdfs[n] = _zipf_cdf(float(self.mix["popular_zipf"]), n)
        return self._cdfs[n]


def generate(world: World, multi_hot: int, n_queries: int, stream_seed,
             query_offset: int = 0):
    """``(table_id, row_id)`` of ``n_queries`` queries, flattened from the
    ``(n_queries, tables, multi_hot)`` layout; ``stream_seed`` is any
    non-negative int (or a tuple of them)."""
    mix, rows = world.mix, world.rows
    t, p, q = rows.size, int(multi_hot), int(n_queries)
    seed = (stream_seed if isinstance(stream_seed, (tuple, list))
            else (int(stream_seed),))
    rng = np.random.default_rng([int(mix["world_seed"]), *map(int, seed)])
    shape = (q, t, p)
    tab = np.broadcast_to(np.arange(t, dtype=np.int32)[None, :, None], shape)
    r = rows[tab]  # rows of each access's table

    cum = np.cumsum([float(mix["components"][c]) for c in _COMPONENTS])
    u = rng.random(shape)
    comp = np.searchsorted(cum[:-1], u, side="right").astype(np.int8)
    row = np.zeros(shape, np.int64)

    # popular: zipf rank through the table's keyed permutation, one
    # inverse-CDF per distinct table size.
    pop = comp == 0
    for n in np.unique(rows):
        m = pop & (r == n)
        k = int(m.sum())
        if k:
            ranks = np.searchsorted(world.cdf(int(n)), rng.random(k))
            ranks = np.minimum(ranks, n - 1)
            row[m] = (ranks * _KEY_MUL + world.salt[tab[m]]) % n

    # cluster: session-smoothed cluster per query, a random profile row.
    nc = world.cluster_rows.shape[0]
    draw = np.searchsorted(_zipf_cdf(float(mix["cluster_zipf"]), nc),
                           rng.random(q))
    stay = rng.random(q) < float(mix["session_stay"])
    stay[0] = False
    head = np.maximum.accumulate(np.where(stay, 0, np.arange(q)))
    q_cluster = np.minimum(draw[head], nc - 1)
    cl = comp == 1
    qi, ti, _ = np.nonzero(cl)
    pick = rng.integers(0, world.cluster_rows.shape[2], size=qi.size)
    row[cl] = world.cluster_rows[q_cluster[qi], ti, pick]

    # stream: an advancing front per table, jittered.
    st = comp == 3
    qi, ti, _ = np.nonzero(st)
    front = world.stream_base[ti] + (query_offset + qi) * int(
        mix["stream_step"])
    jit = rng.integers(0, int(mix["stream_jitter"]), size=qi.size)
    row[st] = (front + jit) % rows[ti]

    # successor: along each table's lane of ids (queries x slots in
    # order), a run of successors after a base id b at lane position s
    # resolves to b + (i - s) * jump; a run with no base starts from 0.
    lane_row = row.transpose(1, 0, 2).reshape(t, q * p)
    lane_suc = (comp == 2).transpose(1, 0, 2).reshape(t, q * p)
    pos = np.broadcast_to(np.arange(q * p), lane_row.shape)
    base_pos = np.maximum.accumulate(np.where(lane_suc, -1, pos), axis=1)
    base = np.where(base_pos >= 0,
                    np.take_along_axis(lane_row, np.maximum(base_pos, 0),
                                       axis=1), 0)
    steps = pos - base_pos
    val = (base + steps * world.jump[:, None]) % rows[:, None]
    lane_row = np.where(lane_suc, val, lane_row)
    row = lane_row.reshape(t, q, p).transpose(1, 0, 2)

    return (np.ascontiguousarray(tab).ravel(),
            np.ascontiguousarray(row).ravel())
