"""MLPerf's DLRM-DCNv2 in the benchmark: a tiny DCNv2 served through
``serve_trace`` agrees with the configuration's plain reference (rows bit
for bit, logits within ``LOGIT_TOL``) and the reference's fp8 control does
not; the cost module's counts are the hand counts; the two new readers
read their kernels' shares from a profile and nothing without them; and a
whole harness run of a DCNv2 cell, from files in a root of its own, is
correct."""
import json
import shutil
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import check, profile, run, spec
from bench.profile import DeviceOp, Profile
from bench.traffic import World, generate, load_mix
from bench.window import Kept

CONFIG = "dlrm-dcnv2.lru"
TINY = {
    "name": "tiny-dcnv2", "source": "a test: DLRM-DCNv2 at CPU sizes",
    "reference": "dlrm_dcnv2_reference", "costs": "dlrm_dcnv2_costs",
    "n_tables": 4, "rows_per_table": [3, 50, 7, 400], "emb_dim": 16,
    "multi_hot": [1, 3, 1, 8], "dense_features": 13, "bottom_mlp": [32, 16],
    "interaction": "dcnv2", "cross_layers": 2, "cross_rank": 8,
    "top_mlp": [32, 16, 1], "param_dtype": "bfloat16",
    "compute_dtype": "bfloat16", "row_dtype": "float32",
    "fast_tier_frac": 0.2, "policy": "lru",
}
# The program serves bfloat16 weights and compute against the reference's
# float32.  Over ten seeds of this fixture its largest logit gap read
# 0.0036-0.027 of the largest reference logit, and the reference computed
# in fp8 read 0.049-0.27: 0.04 passes bfloat16 and refuses the precision
# below it.
LOGIT_TOL = 0.04
TINY["check"] = {"logit_err_limit": LOGIT_TOL}


def _config(name=CONFIG):
    return json.loads((spec.BENCH_DIR / "configs" / f"{name}.json")
                      .read_text())


def _ref():
    return spec.load_module(spec.BENCH_DIR / "configs"
                            / "dlrm_dcnv2_reference.py", "dcnv2_reference")


def _costs():
    return spec.load_module(spec.BENCH_DIR / "configs"
                            / "dlrm_dcnv2_costs.py", "dcnv2_costs")


@pytest.fixture(scope="module", params=[2**31 + 5, 2**33 + 17])
def served(request):
    """The tiny DCNv2 under LRU, a 30-row fast tier, 8 queries a batch of
    the ``dcnv2_steady`` mix; every batch kept."""
    from repro.core.trace import Trace
    from repro.launch.serve import serve_trace

    seed = request.param
    ref = _ref()
    rows = spec.table_rows(TINY)
    tab, row = generate(World(load_mix("dcnv2_steady"), rows),
                        spec.pooling(TINY), 8 * 6, (seed, 1))
    params = ref.init_params(TINY, seed)
    recs = []
    serve_trace(spec.model_config(TINY), params,
                Trace(tab.astype(np.int32), row, np.asarray(rows)), 30,
                "lru", None, batch_queries=8, probe=recs.append)
    kept = [Kept(r.index, r.ids, r.rows, r.dense, r.logits) for r in recs]
    return ref, params, kept


def test_served_rows_and_logits_match_the_reference(served):
    ref, params, kept = served
    assert len(kept) == 6 and np.asarray(kept[0].ids).size == 8 * 13
    r = check.compare(kept, TINY, params, ref)
    assert r["rows_mismatched"] == 0
    assert r["logit_err"] <= LOGIT_TOL, r
    assert check.passed(check.judge(r, TINY), r)


def test_fp8_control_fails_the_tolerance(served):
    ref, params, kept = served
    program = check.compare(kept, TINY, params, ref)["logit_err"]
    r = check.compare(kept, TINY, params, ref, precision="fp8")
    assert r["logit_err"] > LOGIT_TOL >= program
    assert not check.passed(check.judge(r, TINY), r)


def test_reference_pools_each_feature():
    rows = np.arange(2 * 13 * 2, dtype=np.float32).reshape(26, 2)
    got = _ref().pool(rows, dict(TINY, emb_dim=2))
    q = rows.reshape(2, 13, 2)
    want = np.stack([q[:, 0], q[:, 1:4].sum(1), q[:, 4], q[:, 5:].sum(1)],
                    axis=1)
    np.testing.assert_array_equal(got, want)


def test_program_and_reference_draw_the_same_layout():
    """The program's ``init_dlrm_dense`` and the reference's
    ``init_params`` give the same tree of the same shapes at the
    published widths."""
    from repro.models.dlrm import init_dlrm_dense

    cfg = _config()
    shapes = jax.eval_shape(lambda: _ref().init_params(cfg, 7))
    mine = jax.eval_shape(lambda: init_dlrm_dense(
        jax.random.key(0), spec.model_config(cfg)))
    assert (jax.tree_util.tree_structure(shapes)
            == jax.tree_util.tree_structure(mine))
    assert jax.tree_util.tree_map(lambda a: a.shape, shapes) \
        == jax.tree_util.tree_map(lambda a: a.shape, mine)


def test_configuration_reaches_the_program():
    cfg = _config()
    mc = spec.model_config(cfg)
    assert (mc.interaction, mc.cross_layers, mc.cross_rank) == ("dcnv2", 3,
                                                                512)
    assert mc.pooling == tuple(cfg["multi_hot"]) and mc.ids_per_query == 214
    rows = spec.table_rows(cfg)
    assert sum(rows) == 24_184_588 and max(rows) == 4_000_000
    assert int(cfg["fast_tier_frac"] * sum(rows)) == 1_209_229
    published = cfg["published"]["rows_per_table"]
    assert sum(published) == 204_184_588
    assert [min(p, 4_000_000) for p in published] == rows


def test_costs_are_the_hand_counts():
    c, cfg = _costs(), _config()
    assert c.top_inputs(cfg) == 27 * 128 == 3456
    assert c.cross_macs_per_query(cfg) == 3 * 2 * 3456 * 512 == 10_616_832
    bottom = 13 * 512 + 512 * 256 + 256 * 128
    top = 3456 * 1024 + 1024 * 1024 + 1024 * 512 + 512 * 256 + 256
    assert (bottom, top) == (170_496, 5_243_136)
    assert c.macs_per_query(cfg) == 16_030_464
    assert c.flops_per_query(cfg) == 2 * 16_030_464
    assert c.pool_bytes(cfg, 512) == 512 * (214 + 26) * 128 * 4 == 62_914_560
    assert c.cross_flops(cfg, 512) == 2 * 10_616_832 * 512
    cross_w = 3 * (2 * 3456 * 512 + 3456) * 2
    assert c.cross_bytes(cfg, 512) == cross_w + 2 * 512 * 3456 * 2
    mlp_w = (bottom + 512 + 256 + 128 + top + 1024 + 1024 + 512 + 256 + 1) * 2
    assert c.weight_bytes(cfg) == mlp_w + cross_w
    peak = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    # Compute-bound: 10.9 GFLOP, 55 us at the peak rate.
    assert c.cross_seconds_bound(cfg, 512, peak) == pytest.approx(
        2 * 10_616_832 * 512 / 197e12)
    assert c.gather_bytes([10, 20], cfg) == 30 * 128 * 4 * 2


TPU = "/device:TPU:0"
PEAK = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def _ctx(ops, costs, batches=2, queries=512):
    """Each op ``(module, name, start, dur)`` runs as its own execution in
    a [0, 10,000,000) ns window."""
    runs, dev = [], []
    for i, (mod, name, start, dur) in enumerate(ops):
        runs.append(DeviceOp(f"{mod}(1)", start, dur, mod, TPU, i))
        dev.append(DeviceOp(name, start, dur, mod, TPU, i))
    prof = Profile(dev, {profile.WINDOW_START: 0.0,
                         profile.WINDOW_END: 1e7}, runs)
    return SimpleNamespace(
        config=_config(), costs=costs, peak=PEAK, profile=prof,
        profile_seconds=lambda pred: profile.op_seconds(prof, pred),
        program_seconds=lambda pred: profile.run_seconds(prof, pred),
        profiled=SimpleNamespace(batches=batches, batch_queries=queries))


OPS = [("jit_pool_bags", "fusion.1 = f32[512,26,128] reduce(...)", 0, 200e3),
       ("jit_dense_forward",
        "fusion.2 = bf16[512,512] convolution(bf16[512,3456] %x, "
        "bf16[3456,512] %v)", 300e3, 60e3),
       ("jit_dense_forward",
        "fusion.3 = bf16[512,3456] convolution(bf16[512,512] %h, "
        "bf16[512,3456] %w)", 400e3, 40e3),
       ("jit_dense_forward",
        "fusion.4 = bf16[512,1024] convolution(bf16[512,3456] %x, "
        "bf16[3456,1024] %t)", 500e3, 30e3),
       ("jit_dense_forward", "fusion.5 = bf16[512,128] fusion(...)",
        600e3, 10e3),
       ("jit_pool_bags", "fusion.1 = f32[512,26,128] reduce(...)",
        700e3, 200e3)]


def test_pool_bags_roofline_reads_the_pooling_programs():
    ctx = _ctx(OPS, _costs())
    want = 100.0 * 62_914_560 * 2 / 819e9 / 400e-6
    assert spec.metric_reader("pool_bags_roofline")(ctx) == pytest.approx(want)


def test_cross_network_roofline_reads_the_cross_ops_only():
    """The ops holding ``V``'s or ``W``'s shape, not the top MLP's first
    matmul, which reads an activation of ``W``'s shape at 512 queries."""
    ctx = _ctx(OPS, _costs())
    bound = 2 * 10_616_832 * 512 / 197e12
    want = 100.0 * bound * 2 / 100e-6
    assert spec.metric_reader("cross_network_roofline")(ctx) == pytest.approx(want)


@pytest.mark.parametrize("name", ["pool_bags_roofline",
                                  "cross_network_roofline"])
def test_new_readers_are_silent_without_their_counts_or_kernels(name):
    from bench import costs

    assert spec.metric_reader(name)(_ctx(OPS, costs)) is None  # the dot model's
    assert spec.metric_reader(name)(_ctx(OPS[4:5], _costs())) is None
    ctx = _ctx(OPS, _costs())
    ctx.profile = None
    assert spec.metric_reader(name)(ctx) is None


@pytest.fixture(scope="module")
def dcnv2_root(tmp_path_factory):
    """A checkout-like root: the benchmark's ``bench/`` with the tiny
    DCNv2 as a configuration of its own, served under ``dcnv2_steady``."""
    root = tmp_path_factory.mktemp("dcnv2_root")
    shutil.copytree(spec.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench" / "configs" / "tiny-dcnv2.json").write_text(
        json.dumps(TINY))
    (root / "bench" / "cells" / "tiny-dcnv2.dcnv2_steady.json").write_text(
        json.dumps({"batch_queries": 8, "warmup_batches": 3,
                    "window_batches": 30, "kept_batches": 3,
                    "traced_batches": 3}))
    bench = spec.load_benchmark()
    bench["configs"] = [{"name": "tiny-dcnv2", "source": "a test",
                         "file": "bench/configs/tiny-dcnv2.json",
                         "reduced": [], "why": "CPU sizes"}]
    bench["workloads"] = [{"name": "tiny-dcnv2.dcnv2_steady",
                           "config": "tiny-dcnv2", "traffic": "dcnv2_steady",
                           "chips": 1, "why": "a test cell"}]
    for m in bench["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.mark.parametrize("traced", [False, True])
def test_harness_serves_a_dcnv2_cell_correctly(dcnv2_root, traced):
    res = run.run_cell("tiny-dcnv2.dcnv2_steady", 2**31 + 99, 0.5, traced,
                       root=dcnv2_root, bench_dir=dcnv2_root / "bench",
                       require_tpu=False)
    assert res["correct"], res["checks"]
    assert res["checks"]["rows_mismatched"]["value"] == 0
    assert res["checks"]["logit_err"]["limit"] == LOGIT_TOL
    assert res["attempted"] > 0 and res["failed"] == 0
    m = res["metrics"]
    if traced:
        assert m["serve.h2d_mb_per_batch"]["value"] > 0
        assert m["store.miss_rows_per_query"]["value"] > 0
    else:
        assert {"qps", "setup_s"} <= set(m)
