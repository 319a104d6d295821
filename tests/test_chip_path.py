"""The path ``auto`` takes on a TPU, trained end to end on the CPU.

On a v5e ``auto`` resolves to the planes work layout, the Pallas partition
and the Pallas planes histogram (``tests/test_aot_tpu.py`` compiles that
program; ``test_auto_resolution`` below holds the decision itself). Here
the same path runs under the Pallas interpreter through ``lgb.train`` and
is held to the rows / XLA path (row-major, no Pallas) as oracle:

- with the XLA histogram on both sides the model strings are equal byte
  for byte: partition and layout move bytes, they add nothing;
- with ``tpu_hist_kernel=pallas`` the trees have the same structure and
  their gains and leaf values agree to ``HIST_RTOL``: the kernel sums the
  same exact products in another order (PR 29), and
  ``tests/test_histogram.py`` holds a histogram cell to 2e-6 of its sum of
  |terms| against the XLA loop. The data have no near-ties, so that order
  picks no other split.

Interpreter parity is a statement about control flow and arithmetic, not
about Mosaic.
"""
import collections
import json

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import obs, runtime
from lightgbm_tpu.config import Config
from lightgbm_tpu.dataset import construct_dataset
from lightgbm_tpu.learner import SerialTreeLearner
from lightgbm_tpu.ops import partition as P
from lightgbm_tpu.utils.log import (Log, LightGBMError, set_thread_log_level,
                                    set_thread_log_sink)

sp = pytest.importorskip("scipy.sparse")

CH = 256
BASE = {"objective": "binary", "num_leaves": 8, "max_bin": 31,
        "tree_builder": "partition", "verbosity": -1, "min_data_in_leaf": 2,
        "tpu_hist_chunk": CH, "tpu_iter_block": 2}
CHIP = {"tpu_work_layout": "planes", "tpu_partition_kernel": "pallas",
        "tpu_part_chunk": CH}
ORACLE = {"tpu_work_layout": "rows", "tpu_partition_kernel": "xla",
          "tpu_hist_kernel": "xla"}
# a gain is a few hundred f32 operations on histogram cells that agree to
# 2e-6 of their sum of |terms|; 1e-4 leaves room for the cancellation in
# (left + right - parent) and is far under a dropped row or a lost lo half
HIST_RTOL = 1e-4

# active(build kwargs, chip booster): the case's mechanism really ran
Case = collections.namedtuple(
    "Case", "X y params dskw rounds oracle_kw active",
    defaults=({}, {}, 2, {}, lambda kw, bst: True))


def _linear(rng, n, f):
    X = rng.randn(n, f)
    y = (X @ rng.randn(f) + 0.2 * rng.randn(n) > 0).astype(np.float64)
    return X, y


# ---- the cases: one function a case, (rng, tmp_path) -> Case

def shape_deep(rng, tmp_path):
    # N no multiple of the 256-row chunks, a deep leaf-wise tree
    return Case(*_linear(rng, 1501, 20), {"num_leaves": 15}, rounds=1)


def shape_shallow(rng, tmp_path):
    return Case(*_linear(rng, 1101, 16), {"num_leaves": 7}, rounds=1)


def nan_missing(rng, tmp_path):
    # the missing-direction (default_left) logic of the scan and the router
    n = 700
    X = rng.randn(n, 6)
    X[rng.rand(n, 6) < 0.2] = np.nan
    y = (np.nan_to_num(X[:, 0]) + 0.2 * rng.randn(n) > 0).astype(np.float64)
    return Case(X, y, {"use_missing": True})


def categorical(rng, tmp_path):
    n = 700
    X = rng.randn(n, 5)
    X[:, 0] = rng.randint(0, 12, n)
    y = ((X[:, 0] % 3 == 0) ^ (X[:, 1] > 0)).astype(np.float64)
    return Case(X, y, {"min_data_per_group": 5},
                {"categorical_feature": [0]},
                active=lambda kw, bst: kw["hp"].has_categorical)


def multiclass(rng, tmp_path):
    n = 700
    X = rng.randn(n, 6)
    y = (np.abs(X[:, 0]) + X[:, 1] > 0.5).astype(np.float64) \
        + (X[:, 2] > 0.3)
    return Case(X, y, {"objective": "multiclass", "num_class": 3}, rounds=1)


def goss(rng, tmp_path):
    # GOSS masks gradients and still streams every row
    return Case(*_linear(rng, 700, 6),
                {"data_sample_strategy": "goss", "top_rate": 0.3,
                 "other_rate": 0.2})


def efb_onehot(rng, tmp_path):
    # expo.train's mechanism: one-hot CSR blocks in a few bundled device
    # columns, W = 32 planes, the bundle view before every scan and the
    # routing table's translation before every partition
    n, blocks, w = 900, [], []
    for card in (12, 7, 20):
        ids = rng.randint(0, card, n)
        blocks.append(sp.csr_matrix((np.ones(n), (np.arange(n), ids)),
                                    shape=(n, card)))
        w.append(rng.randn(card))
    X = sp.hstack(blocks).tocsr()
    y = (np.asarray(X @ np.concatenate(w)).ravel()
         + 0.2 * rng.randn(n) > 0).astype(np.float64)
    return Case(X, y, {"max_bin": 255}, active=lambda kw, bst: (
        kw["bundle"] is not None
        and P.work_spec(bst.inner.train_set.num_groups, False, "pallas",
                        CH, CH, layout="planes")[1] == 32))


def lambdarank_two_buckets(rng, tmp_path):
    # mslr.train's mechanism: queries of 5-8 and of 20-24 documents fall on
    # two rungs of the query-length ladder, each a shape of its own
    sizes = np.concatenate([rng.randint(5, 9, 40), rng.randint(20, 25, 20)])
    n = int(sizes.sum())
    X = rng.randn(n, 8)
    y = np.clip(np.round(X[:, 0] + 0.5 * X[:, 1] + 0.3 * rng.randn(n) + 1),
                0, 4)
    return Case(X, y, {"objective": "lambdarank"}, {"group": sizes},
                active=lambda kw, bst: len(
                    bst.inner.objective.bucket_shapes) == 2)


def _monotone(method):
    def make(rng, tmp_path):
        return Case(*_linear(rng, 700, 6),
                    {"monotone_constraints": [1, -1, 0, 0, 0, 0],
                     "monotone_constraints_method": method},
                    active=lambda kw, bst: kw["hp"].has_monotone and (
                        kw["hp"].mono_intermediate,
                        kw["hp"].mono_advanced) == (
                        method != "basic", method == "advanced"))
    make.__name__ = "monotone_" + method
    return make


# basic bounds both children by the split midpoint; intermediate refreshes
# the neighbours' bounds after every split; advanced carries per-threshold
# bounds into the pair scan
monotone_basic, monotone_intermediate, monotone_advanced = (
    _monotone(m) for m in ("basic", "intermediate", "advanced"))


def forced_splits(rng, tmp_path):
    path = tmp_path / "forced.json"
    path.write_text(json.dumps(
        {"feature": 2, "threshold": 0.1,
         "left": {"feature": 4, "threshold": -0.3}}))
    return Case(*_linear(rng, 700, 6), {"forcedsplits_filename": str(path)},
                active=lambda kw, bst: (
                    bst.inner.models[0].split_feature[:2].tolist() == [2, 4]))


def bynode_extra_trees(rng, tmp_path):
    return Case(*_linear(rng, 700, 8),
                {"feature_fraction_bynode": 0.6, "extra_trees": True},
                active=lambda kw, bst: kw["extra_trees"])


def cegb(rng, tmp_path):
    return Case(*_linear(rng, 700, 6),
                {"cegb_penalty_split": 1e-4,
                 "cegb_penalty_feature_coupled": [0.5] * 6},
                active=lambda kw, bst: kw["hp"].use_cegb)


def interaction_constraints(rng, tmp_path):
    return Case(*_linear(rng, 700, 6),
                {"interaction_constraints": "[0,1,2],[3,4,5]"},
                active=lambda kw, bst: kw["constraint_sets"] is not None)


def hist_bf16(rng, tmp_path):
    # three channel rows a lo digit instead of five, on both sides
    return Case(*_linear(rng, 700, 6), {"tpu_hist_precision": "bf16"},
                active=lambda kw, bst: kw["hist_mode"] == "bf16")


def fused_block_vs_eager(rng, tmp_path):
    # the chip path in fused blocks of 3 against the oracle in the eager
    # per-iteration loop (any callback takes engine.train off the block)
    return Case(*_linear(rng, 700, 6), {"tpu_iter_block": 3}, rounds=6,
                oracle_kw={"callbacks": [lambda env: None]},
                active=lambda kw, bst: obs.telemetry.records(
                    "job_start")[-1]["path"] == "fused")


def _wide(f):
    def make(rng, tmp_path):
        # epsilon.train's mechanism at a W the suite meets nowhere else
        # (320 and 640 planes): a few strong columns, so no two candidates
        # tie, and few bins, so the interpreter stays quick
        n = 2600
        X = rng.randn(n, f)
        w = np.zeros(f)
        w[rng.choice(f, 10, replace=False)] = rng.randn(10) * 1.5
        y = (X @ w + 0.3 * rng.randn(n) > 0).astype(np.float64)
        return Case(X, y, {"max_bin": 15, "num_leaves": 6}, rounds=1,
                    active=lambda kw, bst: (
                        bst.inner.learner.bins.shape[1] == f))
    make.__name__ = "wide_f%d" % f
    return make


wide_f300, wide_f600 = _wide(300), _wide(600)


# (case, histogram kernel on the chip side); the three cells' mechanisms
# run the kernel the chip runs there
CASES = [
    (shape_deep, "pallas"), (shape_shallow, "xla"), (nan_missing, "xla"),
    (categorical, "pallas"), (multiclass, "xla"), (goss, "pallas"),
    (efb_onehot, "pallas"), (lambdarank_two_buckets, "pallas"),
    (monotone_basic, "xla"), (monotone_intermediate, "xla"),
    (monotone_advanced, "xla"), (forced_splits, "xla"),
    (bynode_extra_trees, "xla"), (cegb, "xla"),
    (interaction_constraints, "xla"), (hist_bf16, "pallas"),
    (fused_block_vs_eager, "pallas"), (wide_f300, "pallas"),
    (wide_f600, "pallas"),
]


def _train(case, side, **train_kw):
    params = dict(BASE, **case.params, **side)
    ds = lgb.Dataset(case.X, label=case.y, params=dict(params), **case.dskw)
    return lgb.train(dict(params), ds, num_boost_round=case.rounds,
                     **train_kw)


def _assert_same_trees(chip, oracle):
    assert len(chip.inner.models) == len(oracle.inner.models)
    for a, b in zip(chip.inner.models, oracle.inner.models):
        assert a.num_leaves == b.num_leaves
        for fld in ("split_feature", "split_bin", "threshold",
                    "decision_type", "left_child", "right_child",
                    "leaf_count"):
            np.testing.assert_array_equal(getattr(a, fld), getattr(b, fld),
                                          err_msg=fld)
        assert {k: v.tolist() for k, v in a.cat_threshold.items()} == \
            {k: v.tolist() for k, v in b.cat_threshold.items()}
        for fld in ("split_gain", "leaf_value"):
            np.testing.assert_allclose(getattr(a, fld), getattr(b, fld),
                                       rtol=HIST_RTOL, err_msg=fld)


@pytest.mark.parametrize("make,hist", CASES,
                         ids=[c.__name__ + "-" + h for c, h in CASES])
def test_chip_path_matches_oracle(make, hist, rng, tmp_path, monkeypatch):
    monkeypatch.setattr(P, "_INTERPRET", True)
    case = make(rng, tmp_path)
    chip = _train(case, dict(CHIP, tpu_hist_kernel=hist))
    kw = chip.inner.learner.build_kwargs()
    assert (kw["work_layout"], kw["part_kernel"], kw["hist_kernel"]) == \
        ("planes", "pallas", hist)
    assert case.active(kw, chip)
    oracle = _train(case, ORACLE, **case.oracle_kw)
    if make is fused_block_vs_eager:
        assert obs.telemetry.records("job_start")[-1]["path"] == "eager"
    assert any(t.num_leaves > 1 for t in oracle.inner.models)
    if hist == "xla":
        assert chip.model_to_string() == oracle.model_to_string()
    else:
        _assert_same_trees(chip, oracle)


def test_second_identical_train_compiles_nothing(rng, monkeypatch):
    """test_retrace.py's discipline on the chip's path: a second train at
    identical shapes and config hits every jit cache."""
    monkeypatch.setattr(P, "_INTERPRET", True)
    X, y = _linear(rng, 520, 7)      # a shape no other test module has
    params = dict(BASE, **CHIP, tpu_hist_kernel="pallas")
    ds = lgb.Dataset(X, label=y, params=dict(params))
    lgb.train(dict(params), ds, num_boost_round=2)   # warm every cache
    obs.telemetry.reset()
    bst = lgb.train(dict(params), ds, num_boost_round=2)
    jc = bst.telemetry()["jit_compiles"]
    assert jc["total"] == 0, jc
    assert jc["backend_compiles"] == 0, jc


# ---- what auto resolves to, without a compile

@pytest.fixture
def warnings_log():
    """The warnings of this thread, whatever level an earlier test left."""
    msgs = []
    set_thread_log_level(Log.WARNING)
    set_thread_log_sink(msgs.append)
    yield msgs
    set_thread_log_level(None)
    set_thread_log_sink(None, clear=True)


def _learner(f, params, mesh_devices=None, n=300):
    rng = np.random.RandomState(0)
    X = rng.randn(n, f)
    y = (X[:, 0] > 0).astype(np.float64)
    cfg = Config.from_params(dict({"objective": "binary", "num_leaves": 4,
                                   "max_bin": 15, "verbosity": -1}, **params))
    ds = construct_dataset(X, cfg, label=y)
    if mesh_devices is None:
        return SerialTreeLearner(cfg, ds)
    from jax.sharding import Mesh
    from lightgbm_tpu.parallel.mesh import DataParallelTreeLearner
    return DataParallelTreeLearner(
        cfg, ds, Mesh(np.asarray(mesh_devices), ("data",)))


# name, on a TPU?, F, params, (layout, partition, histogram, partition
# chunk, histogram chunk), warning expected. The router's side (ROUTER below)
# follows the backend and the width alone.
RESOLUTION = [
    # the three cells' device widths: packed rows of 40, 149 and 22 B
    ("higgs_f28", True, 28, {}, ("planes", "pallas", "pallas", 1024, 8192),
     None),
    ("mslr_f137", True, 137, {}, ("planes", "pallas", "pallas", 1024, 4096),
     None),
    ("expo_f10", True, 10, {}, ("planes", "pallas", "pallas", 1024, 8192),
     None),
    # past the two width gates PR 33 took out: the chunks follow W
    ("row_over_256B", True, 250, {}, ("planes", "pallas", "pallas", 1024, 4096),
     None),
    ("row_over_512B", True, 510, {}, ("planes", "pallas", "pallas", 512, 4096),
     None),
    # epsilon.train's width: W = 2,016 planes, past the router's VMEM
    ("epsilon_f2000", True, 2000, {}, ("planes", "pallas", "pallas", 256, 1024),
     None),
    # past what the planes histogram's accumulator leaves of VMEM
    ("wider_than_vmem", True, 9000, {}, ("rows", "xla", "xla", 2048, 128),
     None),
    ("explicit_planes_wider_than_vmem", True, 9000,
     {"tpu_work_layout": "planes"}, ("planes", "xla", "xla", 2048, 128), None),
    ("quantized_grad", True, 28, {"use_quantized_grad": True},
     ("rows", "pallas", "xla", 1024, 4096), None),
    ("mesh_axis", True, 28, {"tree_learner": "data"},
     ("planes", "pallas", "xla", 1024, 4096), None),
    # the oracle path of every histogram measurement: the XLA loop keeps
    # its own chunk (it spills VMEM at F > 64)
    ("tpu_explicit_xla_hist", True, 137, {"tpu_hist_kernel": "xla"},
     ("planes", "pallas", "xla", 1024, 1024), None),
    ("explicit_pallas_partition_over_512B", True, 510,
     {"tpu_partition_kernel": "pallas"},
     ("planes", "pallas", "pallas", 512, 4096), None),
    # the ROWS Pallas partition keeps its 512 B window: int8 and an
    # explicit rows layout are what still reach it
    ("quantized_over_512B", True, 510, {"use_quantized_grad": True},
     ("rows", "xla", "xla", 2048, 1024), None),
    ("explicit_rows_pallas_over_512B", True, 510,
     {"tpu_work_layout": "rows", "tpu_partition_kernel": "pallas"},
     ("rows", "xla", "xla", 2048, 1024),
     "tpu_partition_kernel=pallas needs packed rows <= 512 bytes"),
    # expo_cat.train's width: six of its columns categorical. Until PR 35 a
    # categorical column sent every tree of the job to the XLA router
    ("categorical_f8", True, 8, {"categorical_feature": "0,1,2,3,4,5"},
     ("planes", "pallas", "pallas", 1024, 8192), None),
    ("cpu", False, 28, {}, ("rows", "xla", "xla", 2048, 4096), None),
    ("cpu_explicit_planes", False, 28, {"tpu_work_layout": "planes"},
     ("planes", "xla", "xla", 2048, 4096), None),
    ("pallas_hist_over_xla_partition", False, 28,
     {"tpu_hist_kernel": "pallas"}, ("rows", "xla", "xla", 2048, 4096),
     "tpu_hist_kernel=pallas needs the pallas partition"),
    ("planes_int8", True, 28,
     {"tpu_work_layout": "planes", "tpu_hist_precision": "int8"},
     ("rows", "pallas", "xla", 1024, 4096),
     "tpu_work_layout=planes does not support int8"),
    # fatal: the planes kernel's lane DMAs are whole 128-lane tiles
    ("planes_part_chunk_96", True, 28, {"tpu_part_chunk": 96}, None,
     "multiple of 128"),
]


# the router's form where it is not the streaming kernel of a TPU / the XLA
# loop of every other backend
ROUTER = dict.fromkeys(
    ("row_over_512B", "epsilon_f2000", "wider_than_vmem",
     "explicit_planes_wider_than_vmem",
     "explicit_pallas_partition_over_512B", "quantized_over_512B",
     "explicit_rows_pallas_over_512B"), "pallas_wide")


@pytest.mark.parametrize("name,tpu,f,params,expect,warning", RESOLUTION,
                         ids=[r[0] for r in RESOLUTION])
def test_auto_resolution(name, tpu, f, params, expect, warning, monkeypatch,
                         warnings_log, request):
    """What ``build_kwargs`` decides from backend, packed row width, mode
    and comm axis: the decisions every chip measurement since PR 27 rests
    on. ``runtime.on_tpu`` is replaced as in tests/test_aot_tpu.py; nothing
    is compiled."""
    monkeypatch.setattr(runtime, "on_tpu", lambda: tpu)
    mesh = request.getfixturevalue("cpu_mesh_devices")[:4] \
        if name == "mesh_axis" else None
    if expect is None:
        with pytest.raises(LightGBMError, match=warning):
            _learner(f, params)
        return
    obs.telemetry.reset()
    lrn = _learner(f, params, mesh)
    kw = lrn.build_kwargs()
    assert (kw["work_layout"], kw["part_kernel"], kw["hist_kernel"],
            kw["part_chunk"], kw["hist_chunk"]) == expect
    # the job's record says what was decided, and what the two largest
    # device buffers hold: it cannot drift from the decision
    (rec,) = obs.telemetry.records("learner_path")
    assert (rec["work_layout"], rec["part_kernel"], rec["hist_kernel"],
            rec["part_chunk"], rec["hist_chunk"]) == expect
    assert rec["route_kernel"] == ROUTER.get(
        name, "pallas_stream" if tpu else "xla")
    assert lrn.hp.has_categorical == (name == "categorical_f8")
    assert rec["packed_row_bytes"] == f + (
        P.GH_BYTES_Q if kw["hist_mode"] == "int8" else P.GH_BYTES)
    # the pool's rows are channel-major (3, F, bins padded to 128 lanes)
    from lightgbm_tpu.ops.histogram import hist_bins
    assert rec["hist_pool_gb"] == pytest.approx(
        4 * f * hist_bins(lrn.num_bin_hist) * 12 / 1e9)
    assert rec["work_buffer_gb"] == pytest.approx(
        np.prod(lrn.work_buf_spec()[0], dtype=np.float64) / 1e9)
    assert kw["hist_mode"] == (
        "int8" if name in ("quantized_grad", "planes_int8",
                           "quantized_over_512B") else "hilo")
    hits = [m for m in warnings_log if warning and warning in m]
    assert bool(hits) == bool(warning), warnings_log


@pytest.mark.parametrize("f,kernel_chunk,root_chunk,part", [
    # the three older cells: today's numbers, which PR 29 and PR 27 measured
    (28, 8192, 8192, 1024), (137, 4096, 4096, 1024), (10, 8192, 8192, 1024),
    # the band the chip timed at the narrow cells' values (PR 33)
    (300, 4096, 1024, 1024), (500, 4096, 1024, 1024),
    # epsilon.train: the chip chose at W = 2,016 (PR 33)
    (2000, 1024, 256, 256),
    # the widest table whose accumulator and output planes (PR 36) leave
    # VMEM a chunk, and past it
    (6896, 128, 128, 256), (6897, 0, 128, 256),
])
def test_chunks_follow_the_width(f, kernel_chunk, root_chunk, part):
    """The three static rules of the planes path: the histogram kernel's
    chunk, the pack's (its root histogram is the XLA einsum, so it follows
    the einsum's operand bytes) and the partition kernel's."""
    from lightgbm_tpu.ops import histogram as H
    assert H.planes_kernel_chunk(f) == kernel_chunk
    assert H.root_einsum_chunk(
        f, kernel_chunk or H.einsum_chunk(f)) == root_chunk
    w = P.work_spec(f, False, "pallas", 0, 0, layout="planes")[1]
    assert P.planes_part_chunk(w) == P.planes_part_chunk(f + 12) == part
    if kernel_chunk:
        guard, _ = P.work_spec(f, False, "pallas", part, kernel_chunk,
                               layout="planes")
        assert guard == max(part, kernel_chunk) + 2 * P.PLANE_ALIGN


@pytest.mark.parametrize("knob", ["tpu_split_kernel", "tpu_forest_kernel",
                                  "tpu_hist_mxu"])
def test_removed_knob_warns_and_trains(knob, rng, warnings_log):
    """The three knobs PR 30 removed: a params dict that still carries one
    gets Config's unknown-parameter warning and trains on the only path."""
    X, y = _linear(rng, 300, 4)
    params = dict(BASE, **{knob: "on"})
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=2)
    assert any("Unknown parameter: " + knob in m for m in warnings_log)
    assert not hasattr(bst.inner.config, knob)
    assert bst.inner.models[0].num_leaves > 1
