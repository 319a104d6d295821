"""CPU tests of the harness's parts for ``m5.train``: the grid
generator's statics, which a seed must not move (rows, release days, codes,
prices, calendar, where the NaNs are), its zero share and release rule; the
plain reference of ``reference/tweedie.py`` against brute force on a tiny
grid; the new checks on a model trained here, and on one trained with
another bag or with bfloat16 histograms; the new reader; a rehearsal of
the cell. By hand, like the others:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_m5_harness.py -q
"""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

from reference import model_text  # noqa: E402
from reference import tweedie  # noqa: E402

with open(os.path.join(BENCH, "configs", "m5-tweedie-2047.json")) as _f:
    CFG = json.load(_f)
ARGS = CFG["datagen"]["args"]
SEEDS = (1, 4_100_000_123, 2_147_483_659)
ROWS = 30_000
# a grid of every department, short enough for the CPU: 40 items a department,
# 400 days
SMALL = dict(ARGS, departments=[dict(d, items=40) for d in ARGS["departments"]])
SMALL_SHAPE = dict(CFG["shape"], days=400)


def load(kind, name):
    spec = importlib.util.spec_from_file_location(
        "bench_%s_%s" % (kind, name), os.path.join(BENCH, kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


GEN = load("datagen", "m5_grid")
COL = {c: j for j, c in enumerate(GEN.COLUMNS)}


def small_rows():
    sc = GEN.series_constants(SMALL, SMALL_SHAPE["days"])
    return int((SMALL_SHAPE["days"] - sc["release_day"]).sum())


def small_table(seed):
    return GEN.make(dict(SMALL_SHAPE, rows=small_rows()), SMALL, seed)


def test_the_configuration_is_the_sources():
    p = CFG["params"]
    assert p == {
        "objective": "tweedie", "tweedie_variance_power": 1.1, "metric": "rmse",
        "num_leaves": 2047, "min_data_in_leaf": 4095, "max_bin": 100,
        "bagging_fraction": 0.5, "bagging_freq": 1, "feature_fraction": 0.5,
        "learning_rate": 0.03, "boost_from_average": False,
        "categorical_feature": "0,1,2,3,4,5,6,7", "verbosity": -1}
    assert len(GEN.COLUMNS) == CFG["shape"]["features"] == 73
    assert GEN.CATEGORICAL == tuple(range(8))
    assert sum(d["items"] for d in ARGS["departments"]) == 3049
    assert len(ARGS["stores"]) == CFG["shape"]["stores"] == 2
    # the rows are the two stores' whole grids over the source's 1,941 days
    sc = GEN.series_constants(ARGS, CFG["shape"]["days"])
    assert sc["S"] == 6098
    assert int((CFG["shape"]["days"] - sc["release_day"]).sum()) == CFG["shape"]["rows"]


@pytest.fixture(scope="module")
def tables():
    return {s: small_table(s) for s in SEEDS}


def test_the_seed_draws_the_sales_alone(tables):
    """Codes, release, prices, SNAP and calendar columns and where every
    NaN lies are the same on every seed; the sales and what follows them
    are not."""
    a, b = tables[SEEDS[0]], tables[SEEDS[1]]
    static = [COL[c] for c in GEN.CODES + ("release",) + GEN.PRICES
              + ("snap_CA", "snap_TX", "snap_WI") + GEN.CALENDAR]
    np.testing.assert_array_equal(a["X"][:, static], b["X"][:, static])
    np.testing.assert_array_equal(np.isnan(a["X"]), np.isnan(b["X"]))
    assert not np.array_equal(a["label"], b["label"])
    assert not np.array_equal(a["X"][:, COL["sales_lag_28"]], b["X"][:, COL["sales_lag_28"]])
    # a prefix of the rows is the same grid, but for the encodings over its rows
    part = GEN.make(dict(SMALL_SHAPE, rows=5000), SMALL, SEEDS[0])
    same = [j for c, j in COL.items() if not c.startswith("enc_")]
    np.testing.assert_array_equal(part["X"][:, same], a["X"][:5000, same])
    np.testing.assert_array_equal(part["label"], a["label"][:5000])


@pytest.mark.parametrize("seed", SEEDS)
def test_zero_share_and_the_release_rule(tables, seed):
    d = tables[seed]
    X, y = d["X"], d["label"]
    assert 0.55 < float(np.mean(y == 0)) < 0.8
    assert np.all(y >= 0) and np.all(y == np.round(y))
    sc = GEN.series_constants(SMALL, SMALL_SHAPE["days"])
    days = SMALL_SHAPE["days"]
    # series after series, each from the first day of its release week
    count = days - sc["release_day"]
    sid = np.repeat(np.arange(sc["S"]), count)
    day = np.concatenate([np.arange(r, days) for r in sc["release_day"]])
    assert len(sid) == len(y)
    np.testing.assert_array_equal(X[:, COL["item_id"]], sc["item"][sid])
    np.testing.assert_array_equal(X[:, COL["store_id"]], sc["store"][sid])
    np.testing.assert_array_equal(sc["release_day"] % 7, 0)
    since = day - sc["release_day"][sid]
    for k in (28, 35, 42):
        np.testing.assert_array_equal(np.isnan(X[:, COL["sales_lag_%d" % k]]), since < k)
    for w in (7, 180):
        np.testing.assert_array_equal(np.isnan(X[:, COL["rolling_mean_%d" % w]]),
                                      since < 28 + w - 1)
    np.testing.assert_array_equal(np.isnan(X[:, COL["rolling_mean_tmp_1_7"]]), since < 7)
    # a lag is the series' sales k days back, a roll the mean of its window
    s = np.flatnonzero(since >= 60)[:500]
    np.testing.assert_array_equal(X[s, COL["sales_lag_30"]], y[s - 30])
    want = np.array([y[i - 28 - 6:i - 28 + 1].mean() for i in s], np.float32)
    np.testing.assert_allclose(X[s, COL["rolling_mean_7"]], want, rtol=1e-5, atol=1e-6)
    # an event column is NaN on a day without one
    assert 0.85 < float(np.mean(np.isnan(X[:, COL["event_name_1"]]))) < 0.97


def _tiny_grid(seed=5, n=3000):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 5).astype(np.float32)
    X[:, 0] = rng.randint(0, 8, n)           # many against many
    X[:, 1] = rng.randint(0, 3, n)           # one against the rest
    X[rng.rand(n) < 0.2, 2] = np.nan
    y = (rng.poisson(np.exp(0.5 * np.nan_to_num(X[:, 2], nan=1.5) + 0.4 * (X[:, 0] % 3)))
         * (rng.rand(n) < 0.6)).astype(np.float32)
    return X, y


def test_the_reference_root_split_is_brute_force():
    """Every candidate's gain from the histograms equals the gain of the
    rows it sends left, summed row by row; the best numerical split is the
    best of every (threshold, missing side) tried by masks."""
    X, y = _tiny_grid()
    params = {"min_data_in_leaf": 20, "min_data_per_group": 10, "bagging_fraction": 0.5,
              "bagging_freq": 1, "feature_fraction": 1.0}
    cols = [{"column": 0, "kind": "categorical", "categories": np.arange(8), "num_bins": 9},
            {"column": 1, "kind": "categorical", "categories": np.arange(3), "num_bins": 4}]
    for c in (2, 3, 4):
        bounds = np.r_[np.quantile(X[~np.isnan(X[:, c]), c], np.linspace(0.1, 0.9, 9)), np.inf]
        nan = np.isnan(X[:, c]).any()
        cols.append({"column": c, "kind": "numerical", "bounds": np.r_[bounds, np.nan] if nan
                     else bounds, "num_bins": len(bounds) + int(nan),
                     "nan_bin": len(bounds) if nan else None})
    rows = np.flatnonzero(tweedie.bag(len(y), params, 0))
    g, h = tweedie.gradients(y[rows], 0.0, 1.1)
    fb = tweedie.bin_rows(X, rows, cols)
    hists = tweedie.histograms(fb, g, h, cols)
    s, best = tweedie.best_split(hists, len(rows), g.sum(), h.sum(), cols,
                                 np.ones(len(cols), bool), params)
    assert best is not None

    def by_rows(mask, extra):
        return float(s.gain(mask.sum(), g[mask].sum(), h[mask].sum(), extra))
    for f, col in enumerate(cols):
        for gain, kind, left, dl, left_rows in s.candidates(col, *hists[f]):
            if kind == tweedie.NUMERICAL:
                mask = fb[:, f] <= left
                if col["nan_bin"] is not None:
                    mask = np.where(fb[:, f] == col["nan_bin"], dl, mask)
                extra = 0.0
            else:
                mask = np.isin(fb[:, f], left)
                extra = s.cat_l2
            assert np.isclose(by_rows(mask, extra), gain, rtol=1e-9, atol=1e-9)
            assert int(mask.sum()) == left_rows
    numerical = max(
        (by_rows(np.where(fb[:, f] == (col["nan_bin"] if col["nan_bin"] is not None else -1),
                          dl, fb[:, f] <= t), 0.0), f)
        for f, col in enumerate(cols) if col["kind"] == tweedie.NUMERICAL
        for t in range(col["num_bins"] - 1) for dl in (False, True))
    found = max(c[0] for f, col in enumerate(cols) if col["kind"] == tweedie.NUMERICAL
                for c in s.candidates(col, *hists[f]))
    assert np.isclose(numerical[0], found, rtol=1e-12)
    assert best["gain"] >= found


# ---- the checks on models trained here

TRAIN = dict(CFG["params"], min_data_in_leaf=40, tpu_iter_block=1)
CHECKS = {c["kind"]: c for c in CFG["checks"]}


def _context(params, train_params, rounds=3, seed=SEEDS[1]):
    import lightgbm_tpu as lgb
    d = GEN.make(dict(CFG["shape"], rows=ROWS), ARGS, seed)
    ds = lgb.Dataset(d["X"], label=d["label"], params=dict(train_params))
    binned = ds.construct()
    bst = lgb.train(dict(train_params), ds, num_boost_round=rounds)
    header, trees = model_text.parse(bst.model_to_string())
    return {"params": params, "rows": ROWS, "X": d["X"], "label": d["label"], "group": None,
            "booster": bst, "binned": binned, "header": header, "trees": trees}


@pytest.fixture(scope="module")
def trained():
    return _context(TRAIN, TRAIN)


def _run(kind, c, **args):
    return load("checks", kind).run(dict(CHECKS.get(kind, {}), **args), c)


def _fresh(c, **kw):
    """A copy of a context with ``kw`` replaced, without the inputs that
    ``tweedie.tree_inputs`` kept in it for another model or parameters."""
    return dict({k: v for k, v in c.items() if k != "tweedie_inputs"}, **kw)


@pytest.mark.parametrize("kind", ["tree_census_bagged", "routed_counts_bagged",
                                  "root_split_tweedie", "deep_splits_tweedie", "predict"])
def test_each_check_passes_on_a_model_trained_here(trained, kind):
    ok, detail = _run(kind, trained)
    assert ok, detail


def test_the_quality_floor_reads_a_model_better_than_none(trained):
    y = np.asarray(trained["label"], np.float64)
    untrained = tweedie.rmse(y, np.zeros(len(y)))
    raw = model_text.raw_score(trained["header"], trained["trees"], trained["X"])
    share = tweedie.rmse(y, raw) / untrained
    assert share < 1.0
    ok, detail = _run("quality_floor_rmse", trained, rows=ROWS, rmse_max_share=1.0)
    assert ok, detail
    ok, detail = _run("quality_floor_rmse", trained, rows=ROWS, rmse_max_share=share * 0.99)
    assert not ok, detail


def test_the_bag_checks_fail_on_another_bag(trained):
    """The same model held to another ``bagging_seed``: its leaf counts sum
    to another bag, the walk over that bag routes other rows, and the
    reference's root holds other rows."""
    other = _fresh(trained, params=dict(TRAIN, bagging_seed=4))
    for kind in ("tree_census_bagged", "routed_counts_bagged", "root_split_tweedie",
                 "deep_splits_tweedie"):
        ok, detail = _run(kind, other)
        assert not ok, (kind, detail)


def test_the_root_split_check_fails_on_bfloat16_histograms():
    bf16 = dict(TRAIN, tpu_hist_precision="bf16")
    ok, detail = _run("root_split_tweedie", _context(TRAIN, bf16, rounds=2))
    assert not ok, detail


def test_a_census_of_small_trees_fails(trained):
    ok, detail = _run("tree_census_bagged", trained, more_than=4000)
    assert not ok, detail


def test_a_census_of_leaves_under_min_data_in_leaf_fails(trained):
    fewest = min(int(t["leaf_count"].min()) for t in trained["trees"])
    ok, detail = _run("tree_census_bagged",
                      _fresh(trained, params=dict(TRAIN, min_data_in_leaf=fewest + 1)))
    assert not ok, detail


def _split_on_another_leafs_rows(c):
    """Tree 0 with a split past the 256th, both of whose children are
    leaves, replaced by another node's split on another feature, as a leaf
    that read another leaf's histogram would be split, and its two leaves'
    counts made those of the walk of its bag, as the program records them."""
    t = c["trees"][0]
    X = c["X"][np.flatnonzero(tweedie.bag(ROWS, TRAIN, 0))]
    numerical = [k for k in range(256, t["num_leaves"] - 1) if not t["is_categorical"][k]]
    for k in numerical:
        kids = (int(t["left_child"][k]), int(t["right_child"][k]))
        if max(kids) >= 0:
            continue
        for other in numerical:
            if t["split_feature"][other] == t["split_feature"][k]:
                continue
            moved = {n: v.copy() if isinstance(v, np.ndarray) else v for n, v in t.items()}
            for n in ("split_feature", "threshold", "decision_type", "default_left",
                      "missing_type"):
                moved[n][k] = t[n][other]
            counts = model_text.leaf_counts(moved, X)
            if min(counts[~kid] for kid in kids) >= TRAIN["min_data_in_leaf"]:
                moved["leaf_count"] = counts
                return k, moved
    raise AssertionError("no split past the 256th could take another's")


def test_a_split_on_another_leafs_rows_fails_only_the_deep_check(trained):
    """The replaced split keeps every count the walk gives, so the census
    and the routed counts pass; the deep check finds that it is not the
    best of its own rows."""
    k, moved = _split_on_another_leafs_rows(trained)
    c = _fresh(trained, trees=[moved] + trained["trees"][1:])
    for kind in ("tree_census_bagged", "routed_counts_bagged"):
        ok, detail = _run(kind, c)
        assert ok, (kind, detail)
    ok, detail = _run("deep_splits_tweedie", c)
    assert not ok and ("node %d:" % k) in detail, detail


def test_counter_quotient_reads_a_plain_quotient():
    read = load("readers", "counter_quotient").read
    args = {"numerator": "tree/leaves", "denominator": "tree/trees"}
    assert read(args, {"counters": {"tree/leaves": 3300, "tree/trees": 3}}) == 1100
    assert read(args, {"counters": {"tree/trees": 3}}) is None
    assert read(args, {"counters": {"tree/leaves": 5, "tree/trees": 0}}) is None


def test_the_cell_rehearses(tmp_path):
    """The cell end to end at 30,000 rows: every check reads ok."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", "m5.train",
         "--seed", "2147483711", "--seconds", "0.01", "--trace", "0", "--rehearse",
         json.dumps({"rows": ROWS, "params": {"min_data_in_leaf": 40, "tpu_iter_block": 1}})],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 4, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1].split(" ", 1)[1])
    failed = {k: v for k, v in line["compared"].items()
              if not v["ok"] and k != "quality_floor_rmse"}
    assert not failed, failed
