"""The traffic generator: determinism, layout, per-table pooling, skew,
shared world, the cells' streams as served before per-table pooling, and
each mix's published statistics at the sizes of the cells that serve it."""
import hashlib
import json
from collections import OrderedDict
from pathlib import Path

import numpy as np
import pytest

from bench import run, spec
from bench.traffic import MIX_DIR, World, generate, load_mix

ROWS = [2000, 37, 3, 5000, 800, 64, 2000, 1200]
MIXES = sorted(f.stem for f in MIX_DIR.glob("*.json"))
BENCH = spec.load_benchmark()
FIXTURES = Path(__file__).resolve().parent / "fixtures"

# sha256 of ``_digest`` over each cell's streams, as the generator drew them
# with one ``multi_hot`` int for every table, before it took a per-table
# list: (cell, stream, queries, query_offset) -> digest.
STREAM_PINS = {
    ("dlrm-recmg.recmg_steady", "served", 1200, 80):
        "ae48fe98294a9b51fb34b96ac478ff0a16f320c79f9bc94883586d1b49b5bf87",
    ("dlrm-recmg.recmg_steady", "profile", 80, 0):
        "dbea8c5050ab9412e33bfc95af5e02032d9e5429b6c0a2a85c3324adcc521e1a",
    ("dlrm-recmg.recmg_steady.lru", "served", 3700, 0):
        "bd5065954c99ab24a65deb2d073d13109ec34fe235b6c5f5a5f3912aa8171094",
}


def _digest(tab, row):
    h = hashlib.sha256()
    h.update(repr((tab.dtype.str, tab.shape, row.dtype.str,
                   row.shape)).encode())
    h.update(tab.tobytes())
    h.update(row.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("key", sorted(STREAM_PINS))
def test_cell_streams_are_the_ones_served_before(key):
    """Each cell's served and profiling streams, at the sizes and query
    offsets ``bench/run.py`` draws them with, are the pinned bytes."""
    name, stream, n_q, offset = key
    c = spec.load_cell(name)
    p, b = c.params, int(c.params["batch_queries"])
    if stream == "served":
        seed = run.SERVED_STREAM
        assert n_q == b * (int(p["warmup_batches"])
                           + int(p["window_batches"]))
        assert offset == b * int(p.get("profile_batches", 0))
    else:
        seed = run.PROFILE_STREAM
        assert n_q == b * int(p["profile_batches"]) and offset == 0
    world = World(load_mix(c.traffic), spec.table_rows(c.config))
    tab, row = generate(world, spec.pooling(c.config), n_q, seed,
                        query_offset=offset)
    assert _digest(tab, row) == STREAM_PINS[key]


def _world(name="recmg_steady", rows=ROWS):
    return World(load_mix(name), rows)


def test_same_seed_same_bytes():
    w = _world()
    a = generate(w, 4, 50, (2**31 + 11, 1))
    b = generate(_world(), 4, 50, (2**31 + 11, 1))
    assert all(x.tobytes() == y.tobytes() for x, y in zip(a, b))
    c = generate(w, 4, 50, (2**31 + 12, 1))
    assert a[1].tobytes() != c[1].tobytes()


@pytest.mark.parametrize("mix", MIXES)
def test_bounds_and_layout(mix):
    p, q = 5, 60
    tab, row = generate(_world(mix), p, q, (7, 1))
    assert tab.shape == row.shape == (q * len(ROWS) * p,)
    lay = tab.reshape(q, len(ROWS), p)
    assert np.array_equal(lay, np.broadcast_to(
        np.arange(len(ROWS))[None, :, None], lay.shape))
    r = np.asarray(ROWS)[tab]
    assert row.min() >= 0 and np.all(row < r)


def _lru_hit_rate(ids, capacity):
    cache, hits = OrderedDict(), 0
    for k in ids.tolist():
        if k in cache:
            hits += 1
            cache.move_to_end(k)
        else:
            cache[k] = None
            if len(cache) > capacity:
                cache.popitem(last=False)
    return hits / len(ids)


def test_hot_mix_hits_more_under_lru():
    rows = [4000] * 10
    cap = int(0.2 * sum(rows))
    off = np.concatenate([[0], np.cumsum(rows)[:-1]])
    base = load_mix("recmg_steady")
    hot = dict(base, popular_zipf=base["popular_zipf"] + 0.35,
               components=dict(base["components"], popular=0.6, stream=0.0))
    rates = {}
    for name, mix in (("steady", base), ("hot", hot)):
        tab, row = generate(World(mix, rows), 5, 600, (3, 1))
        rates[name] = _lru_hit_rate(off[tab] + row, cap)
    assert rates["hot"] > rates["steady"] + 0.05, rates


def test_one_world_two_streams_share_hot_rows():
    w = _world()
    off = np.concatenate([[0], np.cumsum(ROWS)[:-1]])

    def hot(seed, k=50):
        tab, row = generate(w, 5, 400, (seed, 1))
        v, c = np.unique(off[tab] + row, return_counts=True)
        return set(v[np.argsort(-c)[:k]].tolist())

    a, b = hot(1), hot(2)
    assert len(a & b) >= 40
    other = World(dict(load_mix("recmg_steady"), world_seed=99), ROWS)
    tab, row = generate(other, 5, 400, (1, 1))
    v, c = np.unique(off[tab] + row, return_counts=True)
    assert len(a & set(v[np.argsort(-c)[:50]].tolist())) < 40


@pytest.mark.parametrize("pooling", [3, [3, 1, 5]])
def test_successor_chains_match_a_sequential_walk(pooling):
    mix = dict(load_mix("recmg_steady"),
               components={"popular": 0.3, "cluster": 0.0,
                           "successor": 0.7, "stream": 0.0})
    rows = [97, 1000, 5]
    w = World(mix, rows)
    per = np.broadcast_to(pooling, (len(rows),))
    q = 40
    tab, row = generate(w, pooling, q, (5, 1))
    lay, tabs = row.reshape(q, -1), tab.reshape(q, -1)
    # Re-walk each table's lane: an id that is its predecessor plus the jump
    # is a successor; the rest are popular draws.  Most ids must chain.
    chained = 0
    for t, r in enumerate(rows):
        lane = lay[:, tabs[0] == t].ravel()
        assert lane.size == q * per[t]
        chained += np.count_nonzero(
            lane[1:] == (lane[:-1] + w.jump[t]) % r)
    assert chained >= 0.6 * q * per.sum()


def test_one_pooling_is_the_list_of_it():
    w = _world()
    a = generate(w, 4, 30, (2**31 + 17, 1), query_offset=9)
    b = generate(w, [4] * len(ROWS), 30, (2**31 + 17, 1), query_offset=9)
    assert all(x.tobytes() == y.tobytes() for x, y in zip(a, b))


@pytest.mark.parametrize("mix", MIXES)
def test_per_table_pooling_layout(mix):
    """MLPerf DLRM-DCNv2's 26 multi-hot sizes (214 ids a query): each query
    lists table 0's P_0 ids, then table 1's P_1, and so on, each id within
    its table's rows."""
    cfg = json.loads((FIXTURES / "configs" / "dlrm-dcnv2-pooling.json")
                     .read_text())
    per, rows = spec.pooling(cfg), spec.table_rows(cfg)
    assert len(per) == 26 and spec.ids_per_query(cfg) == 214
    q = 50
    tab, row = generate(World(load_mix(mix), rows), per, q, (2**31 + 3, 1))
    assert tab.shape == row.shape == (q * 214,)
    want = np.repeat(np.arange(26), per)
    assert np.array_equal(tab.reshape(q, 214),
                          np.broadcast_to(want, (q, 214)))
    assert row.min() >= 0 and np.all(row < np.asarray(rows)[tab])


def _long_reuse_share(key, block, threshold):
    """Lower and upper bounds on the share of accesses whose reuse distance
    (distinct keys since the key's last access) exceeds ``threshold``,
    counted at the granularity of ``block`` accesses: a reuse is surely
    long where the whole blocks between the two accesses hold more than
    ``threshold`` distinct keys, and may be where the blocks holding them
    do.  First accesses count as neither."""
    n = key.size
    order = np.argsort(key, kind="stable")
    same = key[order[1:]] == key[order[:-1]]
    prev = np.full(n, -1, np.int64)
    prev[order[1:][same]] = order[:-1][same]
    nb = n // block
    # lo[b]: the first block a such that blocks a..b-1 hold at most
    # ``threshold`` distinct keys (two pointers over blocks).
    count = np.zeros(int(key.max()) + 1, np.int32)
    lo, distinct, a = np.zeros(nb, np.int64), 0, 0
    for b in range(nb):
        while distinct > threshold:
            ids = key[a * block:(a + 1) * block]
            np.subtract.at(count, ids, 1)
            distinct -= int(np.count_nonzero(count[np.unique(ids)] == 0))
            a += 1
        lo[b] = a
        ids = key[b * block:(b + 1) * block]
        distinct += int(np.count_nonzero(count[np.unique(ids)] == 0))
        np.add.at(count, ids, 1)
    i = np.nonzero(prev[: nb * block] >= 0)[0]
    bi, bj = i // block, prev[i] // block
    return (np.count_nonzero(bj + 1 < lo[bi]) / n,
            np.count_nonzero(bj < lo[bi]) / n)


def _sizes(w):
    """What the generator reads of a cell's configuration: table sizes and
    pooling; configurations that differ only in policy share them."""
    cfg = spec.load_cell(w["name"]).config
    return (tuple(spec.table_rows(cfg)), tuple(spec.pooling(cfg)),
            w["traffic"])


CALIBRATED = sorted({_sizes(w): w["name"] for w in BENCH["workloads"]
                     if "calibration" in load_mix(w["traffic"])}.values())


@pytest.mark.parametrize("cell", CALIBRATED)
def test_mix_reproduces_published_statistics(cell):
    """At the cell's table sizes and pooling, over the mix's calibration
    horizon, the published statistics hold within the mix's calibration
    ranges: the share of accesses the top vectors take, and the share with
    a reuse distance above the published one, scaled by the served over the
    published vectors.  One test per table sizes, pooling and mix."""
    c = spec.load_cell(cell)
    mix = load_mix(c.traffic)
    pub, cal = mix["published"], mix["calibration"]
    rows = spec.table_rows(c.config)
    published = c.config.get("published", {}).get("rows_per_table",
                                                 c.config["rows_per_table"])
    published = (np.sum(published) if isinstance(published, list)
                 else published * len(rows))
    tab, row = generate(World(mix, rows), spec.pooling(c.config),
                        int(cal["queries"]), (2**31 + 5, 1))
    key = np.concatenate([[0], np.cumsum(rows)[:-1]])[tab] + row
    del tab, row
    counts = np.sort(np.bincount(key))[::-1]
    counts = counts[counts > 0]
    top = counts[: int(pub["top_vectors"] * counts.size)].sum() / key.size
    lo, hi = cal["their_access_share"]
    assert lo <= top <= hi, top
    threshold = pub["reuse_distance"] * sum(rows) / published
    sure, maybe = _long_reuse_share(key, spec.ids_per_query(c.config),
                                    threshold)
    lo, hi = cal["long_reuse_share"]
    assert lo <= sure <= maybe <= hi, (sure, maybe)
