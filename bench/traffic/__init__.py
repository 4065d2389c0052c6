"""Traffic generator: one general generator, one data file per mix.

A mix (``bench/traffic/<name>.json``) holds only parameters.  Every query
draws ``P_t`` ids from each table ``t``, in table order: table 0's ``P_0``
ids, then table 1's ``P_1`` ids, and so on, ``sum(P_t)`` ids a query.  With
one pooling ``P`` for every table the stream reshapes to ``(queries, tables,
P)``, and ``serve_trace``'s ``(B, T, P, D)`` reshape pools real per-table
bags.  Each id comes from one of four components, as in the program's
synthetic trace generator:

* ``popular``: a zipf rank over the table's rows, through a keyed
  permutation ``(rank * 2654435761 + salt) % rows`` (the hot rows);
* ``cluster``: a row of the query's user-cluster profile; consecutive
  queries stay in one cluster with probability ``session_stay``;
* ``successor``: the previous id in the same table plus that table's jump,
  along the table's lane of ``queries x P_t`` slots (a chain of successors
  resolves to ``base + k * jump`` in one pass over the lane);
* ``stream``: an advancing front per table plus a small jitter (ids with
  long reuse distances).

The *world* (salts, cluster profiles, jumps, stream fronts) comes from the
mix's ``world_seed`` alone.  The stream seed draws only the ids served, so
two stream seeds over one world share their hot rows, the way a deployment's
model is trained on history from the same population it then serves.
Everything is vectorized: no per-access Python, one pass per table
for the successor lanes.
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


def generate(world: World, pooling, n_queries: int, stream_seed,
             query_offset: int = 0):
    """``(table_id, row_id)`` of ``n_queries`` queries, flattened from the
    ``(n_queries, sum(P_t))`` layout.  ``pooling`` is one ``P`` for every
    table or a list of one ``P_t`` per table (``bench.spec.pooling``); one
    int gives the same stream as the list of it.  ``stream_seed`` is any
    non-negative int (or a tuple of them)."""
    mix, rows = world.mix, world.rows
    t, q = rows.size, int(n_queries)
    per = np.broadcast_to(np.asarray(pooling, np.int64), (t,))
    off = np.concatenate([[0], np.cumsum(per)])  # each table's first slot
    slot_table = np.repeat(np.arange(t, dtype=np.int32), per)
    seed = (stream_seed if isinstance(stream_seed, (tuple, list))
            else (int(stream_seed),))
    rng = np.random.default_rng([int(mix["world_seed"]), *map(int, seed)])
    shape = (q, slot_table.size)
    tab = np.broadcast_to(slot_table[None, :], shape)

    cum = np.cumsum([float(mix["components"][c]) for c in _COMPONENTS])
    comp = np.searchsorted(cum[:-1], rng.random(shape),
                           side="right").astype(np.int8)
    row = np.zeros(shape, np.int64)

    # popular: zipf rank through the table's keyed permutation, one
    # inverse-CDF per distinct table size.
    pop = comp == 0
    slot_rows = rows[slot_table]
    for n in np.unique(rows):
        m = pop & (slot_rows == n)[None, :]
        k = int(m.sum())
        if k:
            ranks = np.searchsorted(world.cdf(int(n)), rng.random(k))
            ranks = np.minimum(ranks, n - 1)
            row[m] = (ranks * _KEY_MUL + world.salt[tab[m]]) % n
    del pop

    # cluster: session-smoothed cluster per query, a random profile row.
    nc = world.cluster_rows.shape[0]
    draw = np.searchsorted(_zipf_cdf(float(mix["cluster_zipf"]), nc),
                           rng.random(q))
    stay = rng.random(q) < float(mix["session_stay"])
    stay[0] = False
    head = np.maximum.accumulate(np.where(stay, 0, np.arange(q)))
    q_cluster = np.minimum(draw[head], nc - 1)
    cl = comp == 1
    qi, si = np.nonzero(cl)
    ti = slot_table[si]
    pick = rng.integers(0, world.cluster_rows.shape[2], size=qi.size)
    row[cl] = world.cluster_rows[q_cluster[qi], ti, pick]
    del cl

    # stream: an advancing front per table, jittered.
    st = comp == 3
    qi, si = np.nonzero(st)
    ti = slot_table[si]
    front = world.stream_base[ti] + (query_offset + qi) * int(
        mix["stream_step"])
    jit = rng.integers(0, int(mix["stream_jitter"]), size=qi.size)
    row[st] = (front + jit) % rows[ti]
    del st, qi, si, ti, front, jit

    # successor: along each table's lane of ids (queries x its slots in
    # order), a run of successors after a base id b at lane position s
    # resolves to b + (i - s) * jump; a run with no base starts from 0.
    for j in range(t):
        a, b = off[j], off[j + 1]
        suc = (comp[:, a:b] == 2).ravel()
        if not suc.any():
            continue
        lane = row[:, a:b].ravel()
        pos = np.arange(lane.size)
        base_pos = np.maximum.accumulate(np.where(suc, -1, pos))
        base = np.where(base_pos >= 0, lane[np.maximum(base_pos, 0)], 0)
        val = (base + (pos - base_pos) * world.jump[j]) % rows[j]
        row[:, a:b] = np.where(suc, val, lane).reshape(q, b - a)

    return np.ascontiguousarray(tab).ravel(), row.ravel()
