"""The bundled path against the benchmark's plain EFB reference
(``benchmark/reference/efb.py``: numpy + scipy, imports nothing from the
program), at a small size on the CPU, on rows of the ``expo.train`` cell's own
generator. The reference is imported by path, as the benchmark's checks do.
"""
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.learner import bundle_feature_view
from lightgbm_tpu.ops.histogram import hist_fb3, hist_planes
from lightgbm_tpu.obs import telemetry

pytest.importorskip("scipy.sparse")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
ROWS, SEED, SAMPLE = 20_000, 2_147_483_659, 5_000
PARAMS = {"objective": "binary", "num_leaves": 31, "max_bin": 255,
          "learning_rate": 0.1, "min_data_in_leaf": 0,
          "min_sum_hessian_in_leaf": 100, "verbosity": -1}


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        "bench_%s_%s" % (kind, name), os.path.join(BENCH, kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def efb():
    sys.path.insert(0, BENCH)          # the reference's own sibling imports
    try:
        from reference import efb, model_text
    finally:
        sys.path.remove(BENCH)
    efb.model_text = model_text
    return efb


@pytest.fixture(scope="module")
def table():
    """20K rows of the cell's generator under the cell's configuration."""
    with open(os.path.join(BENCH, "configs", "expo-binary-255.json")) as f:
        cfg = json.load(f)
    shape = dict(cfg["shape"], rows=ROWS)
    return _load("datagen", cfg["datagen"]["kind"]).make(
        shape, cfg["datagen"]["args"], SEED)


def _construct(X, label, **params):
    telemetry.reset()
    ds = lgb.Dataset(X, label=label, params=dict(PARAMS, **params))
    return ds, ds.construct()


def _sampled_rows(n, sample_cnt, seed=1):
    """The rows ``construct_dataset`` samples (``data_random_seed`` = 1)."""
    idx = np.random.RandomState(seed).choice(n, size=sample_cnt, replace=False)
    idx.sort()
    return idx


@pytest.fixture(scope="module")
def clean(table):
    """Every row sampled (20K < bin_construct_sample_cnt): no conflict."""
    ds, binned = _construct(table["X"], table["label"])
    return ds, binned


@pytest.fixture(scope="module")
def clashing(table):
    """The same table with a second origin airport set on 40 rows that the
    bundling sample (5,000 rows) does not hold: the bundles are chosen
    without seeing them, so they conflict on the full table only."""
    import scipy.sparse as sp
    X = table["X"].tolil(copy=True)
    sampled = set(_sampled_rows(ROWS, SAMPLE).tolist())
    rng = np.random.RandomState(7)
    rows = [r for r in rng.permutation(ROWS) if r not in sampled][:40]
    origin0 = 12 + 31 + 7 + 22
    planted = []
    for r in sorted(rows):
        own = [c for c in X.rows[r] if origin0 <= c < origin0 + 313][0]
        # another of the 20 hottest origins: the same bundle as most rows' own
        extra = origin0 + (own - origin0 + 1 + rng.randint(19)) % 20
        X[r, extra] = 1.0
        planted.append((r, own, extra))
    X = sp.csr_matrix(X, dtype=np.float32)
    X.sort_indices()
    ds, binned = _construct(X, table["label"], bin_construct_sample_cnt=SAMPLE)
    return X, ds, binned, np.array(planted)


def test_binned_matrix_is_the_references_byte_for_byte(efb, table, clean):
    _, binned = clean
    gm = efb.group_map_of(binned)
    bundled, conflict_rows = efb.bundle(efb.csc_of(table["X"]), gm)
    assert binned.has_bundles and binned.binned.dtype == np.uint8
    assert len(binned.used_feature_indices) == 700
    assert binned.num_groups <= 12
    assert bundled.shape == binned.binned.shape
    assert np.array_equal(bundled, binned.binned)          # exact: bytes
    assert len(conflict_rows) == 0 == binned.efb_conflict_rows
    rec = telemetry.records("dataset_construct")[-1]
    assert rec["groups"] == binned.num_groups
    assert rec["bundled_features"] == 698 and rec["conflict_rows"] == 0
    assert rec["sample_conflicts"] == 0 and rec["bundle_s"] > 0
    snap = telemetry.snapshot()
    assert snap["gauges"]["efb/groups"] == binned.num_groups
    assert snap["gauges"]["efb/features"] == 698


def test_groups_are_conflict_free_on_the_sampled_rows(efb, clashing):
    X, _, binned, _ = clashing
    gm = efb.group_map_of(binned)
    sample = X[_sampled_rows(ROWS, SAMPLE)].tocsc()
    sample.sort_indices()
    _, conflict_rows = efb.bundle(sample, gm)
    assert len(conflict_rows) == 0 == binned.efb_sample_conflicts


def test_feature_view_equals_histograms_of_the_raw_columns(efb, table, clean):
    _, binned = clean
    gm = efb.group_map_of(binned)
    rng = np.random.RandomState(11)
    ghc = np.stack([rng.normal(size=ROWS), rng.uniform(0.1, 1.0, ROWS),
                    np.ones(ROWS)], axis=1).astype(np.float32).astype(np.float64)
    G, Bm = binned.num_groups, int(binned.group_num_bins().max())
    hist = np.zeros((G, Bm, 3))
    for g in range(G):
        for c in range(3):
            hist[g, :, c] = np.bincount(binned.binned[:, g], weights=ghc[:, c],
                                        minlength=Bm)
    total = ghc.sum(axis=0)
    maps = {k: jnp.asarray(v) for k, v in binned.bundle_maps().items()}
    # the loop's channel-major (3, G, Bp) in, (3, F, B) out
    view = np.asarray(hist_fb3(bundle_feature_view(
        hist_planes(jnp.asarray(hist, jnp.float32)),
        jnp.asarray(total, jnp.float32), maps, Bm, binned.bundle_view()),
        maps["put"].shape[1]))
    raw = efb.raw_feature_histograms(efb.csc_of(table["X"]), gm, ghc)
    fixed = efb.feature_histograms(hist, total, gm)
    # the reference's two routes agree to float64 rounding
    np.testing.assert_allclose(fixed, raw, rtol=0, atol=1e-9 * np.abs(total).max())
    # counts: whole numbers under 2**24, exact in float32
    assert np.array_equal(view[:, :, 2], raw[:, :, 2])
    # sums: the view holds float32 roundings of float64 bin sums, and a
    # default bin is a float32 difference total - own slots, taken in another
    # order than the raw column's own sum: 1e-6 of the largest magnitude that
    # entered the difference (the node's total or the bin itself)
    scale = np.maximum(np.abs(raw[:, :, :2]), np.abs(total[:2])[None, None, :])
    assert np.all(np.abs(view[:, :, :2] - raw[:, :, :2]) <= 1e-6 * scale)


def test_feature_view_of_the_cells_table_places_runs_bit_for_bit(
        table, clean, monkeypatch):
    """The cell's own layout (698 one-hot columns in eight bundles, two
    numeric columns alone) takes the ``runs`` form, and on histograms of the
    table's own rows the view is the (feature, bin) gather's, every float32
    of it: a one-hot column owns one slot, so its default bin is a
    difference of two numbers whatever the order of a sum."""
    from lightgbm_tpu import dataset as D
    from test_hist_planes import _gather_view
    _, binned = clean
    view = binned.bundle_view()
    assert (view.form, view.alone, view.bundled, view.width, view.num_bin,
            len(view.slices)) == ("runs", 2, 698, 2, 240, 2)
    maps = {k: jnp.asarray(v) for k, v in binned.bundle_maps().items()}
    monkeypatch.setattr(D, "VIEW_SEL_MAX_BYTES", 0)
    ref_maps = {k: jnp.asarray(v) for k, v in binned.bundle_maps().items()}
    monkeypatch.undo()
    rng = np.random.RandomState(5)
    ghc = np.stack([rng.normal(size=ROWS), rng.uniform(0.1, 1.0, ROWS),
                    np.ones(ROWS)], axis=1).astype(np.float32)
    G, Bm = binned.num_groups, int(binned.group_num_bins().max())
    hist = np.zeros((G, Bm, 3), np.float32)
    for g in range(G):
        for c in range(3):
            hist[g, :, c] = np.bincount(binned.binned[:, g], weights=ghc[:, c],
                                        minlength=Bm)
    hg = hist_planes(jnp.asarray(hist))
    total = jnp.asarray(ghc.sum(axis=0, dtype=np.float64), jnp.float32)
    got = np.asarray(bundle_feature_view(hg, total, maps, Bm, view))
    want = np.asarray(_gather_view(hg, total, ref_maps, Bm))
    assert got.shape == (3, 700, 240) and np.array_equal(got, want)
    assert np.count_nonzero(got[2]) > 700


def test_first_root_split_is_the_references(efb, table, clean):
    ds, binned = clean
    bst = lgb.train(dict(PARAMS), ds, num_boost_round=1)
    _, trees = efb.model_text.parse(bst.model_to_string())
    gm = efb.group_map_of(binned)
    j, b, gain = efb.root_split(efb.csc_of(table["X"]), table["label"], gm,
                                min_data_in_leaf=0, min_sum_hessian=100)
    t = trees[0]
    pj = int(np.flatnonzero(gm.column == int(t["split_feature"][0]))[0])
    pb = int(np.searchsorted(gm.bounds[pj][:-1], float(t["threshold"][0]), side="left"))
    assert (pj, pb) == (j, b)
    # float32 histograms and gain arithmetic against float64: the cell's own
    # tolerance (gain_rtol of root_split_binary_sparse)
    assert abs(float(t["split_gain"][0]) - gain) <= 1e-4 * abs(gain)


def test_walks_over_csc_and_bundle_agree_with_the_dense_walk(efb, table, clean):
    ds, binned = clean
    bst = lgb.train(dict(PARAMS), ds, num_boost_round=3)
    header, trees = efb.model_text.parse(bst.model_to_string())
    gm = efb.group_map_of(binned)
    Xc = efb.csc_of(table["X"])
    dense = np.ascontiguousarray(table["X"].toarray(), dtype=np.float32)
    for t in trees:
        leaf = efb.model_text.route(t, dense)
        assert np.array_equal(efb.walk_raw(t, Xc), leaf)              # exact
        assert np.array_equal(efb.walk_bundled(t, binned.binned, gm), leaf)
        assert np.array_equal(efb.leaf_counts(leaf, t), t["leaf_count"])
    # the same leaves, the same float64 sums in the same order
    assert np.array_equal(efb.raw_score(header, trees, Xc),
                          efb.model_text.raw_score(header, trees, dense))
    # program predict on CSR: float32 sums of three leaf values
    np.testing.assert_allclose(bst.predict(table["X"], raw_score=True),
                               efb.raw_score(header, trees, Xc), rtol=0, atol=1e-6)


def test_conflicts_outside_the_sample_are_counted_and_kept_by_rule(efb, clashing):
    X, ds, binned, planted = clashing
    gm = efb.group_map_of(binned)
    bundled, conflict_rows = efb.bundle(efb.csc_of(X), gm)
    # counted: the program's O(nnz) count is the reference's. They are the
    # planted rows whose two origins share a bundle (two of the 20 hottest,
    # nearly always) and the rows on which a 5,000-row sample was too small
    # to see two rare airports meet; none of them is a sampled row
    column_group = dict(zip(gm.column.tolist(), gm.group.tolist()))
    same_bundle = [r for r, own, extra in planted
                   if column_group[own] == column_group[extra]]
    assert len(same_bundle) >= 30 and set(same_bundle) <= set(conflict_rows)
    assert not set(conflict_rows) & set(_sampled_rows(ROWS, SAMPLE))
    assert binned.efb_conflict_rows == len(conflict_rows)
    assert telemetry.snapshot()["counters"]["efb/conflict_rows"] == len(conflict_rows)
    assert telemetry.records("dataset_construct")[-1]["conflict_rows"] == len(conflict_rows)
    # kept by rule: the later-placed sub-feature, byte for byte
    assert np.array_equal(bundled, binned.binned)
    bst = lgb.train(dict(PARAMS), ds, num_boost_round=2)
    _, trees = efb.model_text.parse(bst.model_to_string())
    Xc = efb.csc_of(X)
    for t in trees:
        by_bundle = efb.leaf_counts(efb.walk_bundled(t, bundled, gm), t)
        by_raw = efb.leaf_counts(efb.walk_raw(t, Xc), t)
        assert np.array_equal(by_bundle, t["leaf_count"])             # exact
        # only a conflict row can land elsewhere on the raw columns
        assert np.abs(by_raw - t["leaf_count"]).max() <= len(conflict_rows)
        moved = efb.walk_bundled(t, bundled, gm) != efb.walk_raw(t, Xc)
        assert set(np.flatnonzero(moved)) <= set(conflict_rows)


def test_conflict_share_over_the_reference_budget_warns_once(efb, clashing):
    """Over 40 of 20,000 rows is over 2e-3, against the reference's 1e-4.
    Level and sink are this thread's own: the process-wide ones are whatever
    the tests before this one in the worker left."""
    from lightgbm_tpu.dataset import EFB_CONFLICT_SHARE_WARN
    from lightgbm_tpu.utils.log import Log, set_thread_log_level, set_thread_log_sink
    X, _, binned, _ = clashing
    assert binned.efb_conflict_rows > EFB_CONFLICT_SHARE_WARN * ROWS
    lines = []
    set_thread_log_level(Log.WARNING)
    set_thread_log_sink(lines.append)
    try:
        _construct(X, np.zeros(ROWS), bin_construct_sample_cnt=SAMPLE)
    finally:
        set_thread_log_sink(None, clear=True)
        set_thread_log_level(None)
    assert "".join(lines).count("set two features of one bundle") == 1


def test_forced_splits_on_bundled_columns_are_taken_and_walk_alike(efb, table, clean, tmp_path):
    """The forced-split search reads the same per-feature view as the
    ordinary one (its ops under ``lgbtpu/efb_view`` and ``split_scan``):
    forced on a numeric column, a one-hot origin and a one-hot carrier, the
    first three splits are those, and the tree walks the reference's
    bundled matrix to the recorded leaf counts exactly."""
    _, binned = clean
    forced = tmp_path / "forced.json"
    forced.write_text(json.dumps({
        "feature": 698, "threshold": 720.0,
        "left": {"feature": 72, "threshold": 0.5},
        "right": {"feature": 50, "threshold": 0.5}}))
    params = dict(PARAMS, num_leaves=15, min_sum_hessian_in_leaf=1,
                  forcedsplits_filename=str(forced))
    ds = lgb.Dataset(table["X"], label=table["label"], params=params)
    bst = lgb.train(params, ds, num_boost_round=2)
    _, trees = efb.model_text.parse(bst.model_to_string())
    gm = efb.group_map_of(binned)
    Xc = efb.csc_of(table["X"])
    for t in trees:
        assert t["split_feature"][:3].tolist() == [698, 72, 50]
        leaf = efb.walk_bundled(t, binned.binned, gm)
        assert np.array_equal(efb.leaf_counts(leaf, t), t["leaf_count"])   # exact
        assert np.array_equal(efb.walk_raw(t, Xc), leaf)
