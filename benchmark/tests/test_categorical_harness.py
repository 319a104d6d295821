"""CPU tests of what PR 35 adds to the harness for ``expo_cat.train``: the
coded-columns generator against the one-hot one it mirrors, the static facts
a seed must not move, the plain categorical search against a brute force, the
``splits_categorical`` check on a trained and on a doctored model, and a
rehearsal of the cell. By hand, like the others:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""
import importlib.util
import itertools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

from reference import categorical_split as ref  # noqa: E402
from reference import model_text  # noqa: E402

with open(os.path.join(BENCH, "configs", "expo-categorical-255.json")) as _f:
    CFG = json.load(_f)
with open(os.path.join(BENCH, "configs", "expo-binary-255.json")) as _f:
    ONEHOT = json.load(_f)


def load(kind, name):
    spec = importlib.util.spec_from_file_location(
        "bench_%s_%s" % (kind, name), os.path.join(BENCH, kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def coded(rows, seed):
    return load("datagen", "coded_columns").make(dict(CFG["shape"], rows=rows),
                                                 CFG["datagen"]["args"], seed)


def test_the_configuration_is_expos_but_for_the_coding():
    assert CFG["datagen"]["args"] == ONEHOT["datagen"]["args"]
    mine = dict(CFG["params"])
    assert mine.pop("categorical_feature") == "0,1,2,3,4,5"
    assert mine == ONEHOT["params"]
    assert CFG["shape"]["rows"] == ONEHOT["shape"]["rows"]
    assert not [k for k in CFG["params"] if k.startswith("tpu_") or k.startswith("cat_")
                or k.startswith("max_cat") or k == "min_data_per_group"]


def test_coded_columns_are_the_one_hot_tables_rows_and_labels():
    """Same seed, same streams: row r's code in column j is the one-hot column
    the other table sets in block j less the block's start; numeric values and
    labels are equal. Past one chunk of 1 << 18 rows, so two streams."""
    rows, seed = 300_000, 2_147_483_659
    a = coded(rows, seed)
    onehot = load("datagen", "onehot_csr")
    b = onehot.make(dict(ONEHOT["shape"], rows=rows), ONEHOT["datagen"]["args"], seed)
    assert a["X"].dtype == np.float32 and a["X"].shape == (rows, 8) and a["group"] is None
    assert np.array_equal(a["label"], b["label"])
    starts, first_numeric, _ = onehot.layout(ONEHOT["datagen"]["args"])
    idx = b["X"].indices.reshape(rows, 8)
    val = b["X"].data.reshape(rows, 8)
    for j, col in enumerate(CFG["datagen"]["args"]["columns"]):
        assert np.array_equal(a["X"][:, j], (idx[:, j] - starts[j]).astype(np.float32)), col["name"]
        assert a["X"][:, j].min() == 0 and a["X"][:, j].max() == col["categories"] - 1
        counts = np.bincount(a["X"][:, j].astype(np.int64))
        if col["zipf"] > 0:
            assert counts[0] > 2 * counts[-1]              # hot codes first
    assert np.array_equal(idx[:, 6:], np.tile(first_numeric + np.arange(2), (rows, 1)))
    assert np.array_equal(a["X"][:, 6:], val[:, 6:])


def test_what_the_program_derives_does_not_follow_the_seed():
    import lightgbm_tpu as lgb
    for seed in (1, 3_000_000_019, 2_147_483_659):
        d = coded(60_000, seed)
        binned = lgb.Dataset(d["X"], label=d["label"], params=CFG["params"]).construct()
        assert [m.num_bins for m in binned.bin_mappers] == [13, 32, 8, 23, 255, 255, 240, 200], seed
        assert [int(m.bin_type) for m in binned.bin_mappers] == [1] * 6 + [0] * 2
        assert not binned.has_bundles and binned.binned.shape == (60_000, 8)
        # 313 - 254 categories of each airport column share its last bin
        assert 0 < binned.cat_other_bin_rows < 0.2 * 60_000


# ---- the plain search against a brute force over every subset

def _gain_of_subset(s, cnt, g, subset):
    at = np.array(subset)
    return float(s.gain(cnt[at].sum(), g[at].sum(), s.cat_l2))


@pytest.mark.parametrize("seed", range(4))
def test_plain_search_finds_the_brute_forces_best_candidate(seed):
    """One categorical column of 8 categories + the shared last bin, by hand.
    The rule's candidates are prefixes of the sorted bins from either end;
    over all 254 proper subsets the best that is such a prefix is what the
    search must return. Without smoothing or l2 (where sorting by g / h is
    known to hold the optimum) it is the best subset of all."""
    rng = np.random.RandomState(seed)
    cnt = np.concatenate([rng.randint(150, 900, 8), [40]]).astype(np.float64)
    p = 0.3
    sy = rng.binomial(cnt.astype(np.int64), np.clip(p + 0.15 * rng.randn(9), 0.05, 0.9)).astype(np.float64)
    n, sum_y = cnt.sum(), sy.sum()
    col = {"categories": np.arange(8) + 100}
    for params, every in (({"min_data_in_leaf": 0, "min_sum_hessian_in_leaf": 1e-3}, False),
                          ({"min_data_in_leaf": 0, "min_sum_hessian_in_leaf": 1e-3, "cat_smooth": 0.0,
                            "cat_l2": 0.0}, True)):
        s, best = ref.best_split([(cnt, sy)], n, sum_y, p, [col], params)
        g = p * cnt - sy
        key = g[:8] / (cnt[:8] * s.hess + s.cat_smooth)
        asc = np.argsort(key, kind="stable")
        prefixes = {tuple(sorted(o[:k])) for o in (asc, asc[::-1]) for k in range(1, 8)}
        found = {}
        for k in range(1, 8):
            for subset in itertools.combinations(range(8), k):
                found[subset] = _gain_of_subset(s, cnt, g, subset)
        rule_best = max((v, sub) for sub, v in found.items() if sub in prefixes)
        assert best["kind"] == ref.MANY_VS_MANY and best["feature"] == 0
        assert best["gain"] == pytest.approx(rule_best[0], rel=1e-12)
        assert found[best["left"]] == pytest.approx(rule_best[0], rel=1e-12)
        assert best["left_rows"] == int(cnt[list(best["left"])].sum())
        assert 8 not in best["left"]                       # the shared bin is no candidate
        if every:
            assert best["gain"] == pytest.approx(max(found.values()), rel=1e-12)


def test_plain_search_knows_the_other_two_kinds():
    """A column of 3 categories goes one against the rest; a numerical column
    with a steeper step wins over both."""
    p = 0.5
    cat3 = (np.array([400.0, 300.0, 300.0, 0.0]), np.array([260.0, 140.0, 150.0, 0.0]))
    step = (np.array([500.0, 500.0]), np.array([450.0, 100.0]))
    params = {"min_data_in_leaf": 0, "min_sum_hessian_in_leaf": 1e-3}
    cols = [{"categories": np.array([7, 8, 9])}]
    s, best = ref.best_split([cat3], 1000, 550.0, p, cols, params)
    assert (best["kind"], best["left"], best["left_rows"]) == (ref.ONE_VS_REST, (0,), 400)
    cols.append({"bounds": np.array([0.5, np.inf])})
    s, best = ref.best_split([cat3, step], 1000, 550.0, p, cols, params)
    assert (best["kind"], best["feature"], best["left"], best["left_rows"]) == (ref.NUMERICAL, 1, 0, 500)
    assert s.gain_of(cols[1], *step, 0) == (pytest.approx(best["gain"]), 500)


# ---- the check on a trained model, and on one with a small lie

@pytest.fixture(scope="module")
def job():
    import lightgbm_tpu as lgb
    rows = 120_000
    d = coded(rows, 3_000_000_019)
    params = dict(CFG["params"], num_leaves=31)
    ds = lgb.Dataset(d["X"], label=d["label"], params=params)
    bst = lgb.train(params, ds, num_boost_round=3)
    text = bst.model_to_string()
    header, trees = model_text.parse(text)
    return {"params": params, "rows": rows, "X": d["X"], "label": d["label"], "group": None,
            "booster": bst, "binned": ds.construct(), "header": header, "trees": trees, "text": text}


ARGS = next(c for c in CFG["checks"] if c["kind"] == "splits_categorical")


def test_splits_categorical_passes_on_a_trained_model(job):
    ok, detail = load("checks", "splits_categorical").run(ARGS, job)
    assert ok, detail
    assert detail.count("many_vs_many") >= 2 and "node 0 (120000 rows)" in detail


def test_splits_categorical_fails_when_one_category_crosses_a_set(job):
    """The most frequent category of the root's column moved across the
    root's set: the rows sent left are no longer the recorded ones, nor the
    reference's."""
    t = job["trees"][0]
    assert t["is_categorical"][0]
    col = int(t["split_feature"][0])
    values, counts = np.unique(job["X"][:, col].astype(np.int64), return_counts=True)
    moved = int(values[np.argmax(counts)])
    lied = dict(t, cat_sets=dict(t["cat_sets"]))
    lied["cat_sets"][0] = np.array(sorted(set(t["cat_sets"][0].tolist()) ^ {moved}), np.int64)
    ok, detail = load("checks", "splits_categorical").run(ARGS, dict(job, trees=[lied] + job["trees"][1:]))
    assert not ok and "node 0" in detail, detail


def test_splits_categorical_fails_a_gain_off_by_more_than_the_limit(job):
    t = job["trees"][0]
    lied = dict(t, split_gain=t["split_gain"] * (1.0 + 3.0 * float(ARGS["gain_rtol"])))
    ok, detail = load("checks", "splits_categorical").run(ARGS, dict(job, trees=[lied] + job["trees"][1:]))
    assert not ok, detail


def test_odd_categories_are_predicted_as_the_plain_walk_routes_them(job):
    args = next(c for c in CFG["checks"] if c["kind"] == "predict_odd_categories")
    ok, detail = load("checks", "predict_odd_categories").run(args, job)
    assert ok, detail


def test_new_readers_return_nothing_where_the_program_says_nothing():
    ratio = load("readers", "counter_ratio").read
    args = {"numerator": "tree/splits_categorical", "denominator": "tree/splits"}
    assert ratio(args, {"counters": {"tree/splits": 2540}}) is None      # the parent commit
    assert ratio(args, {"counters": {}}) is None
    assert ratio(args, {"counters": {"tree/splits": 2540, "tree/splits_categorical": 1905}}) == 75.0
    roof = load("readers", "route_roofline")
    assert roof.read({"scope": "lgbtpu/route"}, {"trace": None, "trace_trees": None, "peaks": None}) is None
    tree = {"num_leaves": 2, "internal_count": np.array([1000]), "leaf_count": np.array([600, 400]),
            "left_child": np.array([-1]), "right_child": np.array([-2])}
    facts = {"trace": {"by_scope": {"lgbtpu/route": 1e-6}}, "trace_trees": [tree, tree],
             "peaks": {"hbm_bytes_per_s": 819e9}, "features": 8}
    assert roof.route_stream_bytes(1000, 8) == 12_000
    assert roof.read({"scope": "lgbtpu/route"}, facts) == pytest.approx(100.0 * 24_000 / 819e9 / 1e-6)
    assert roof.read({"scope": "lgbtpu/route"}, dict(facts, trace={"by_scope": {}})) is None


def test_expo_cat_rehearsal_walks_every_phase_on_the_cpu_and_exits_4():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", "expo_cat.train",
         "--seed", "3000000019", "--seconds", "1", "--trace", "0", "--rehearse",
         '{"rows": 30000, "params": {"num_leaves": 15}}'],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 4, p.stderr[-2000:]
    last = p.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL ")
    line = json.loads(last[len("REHEARSAL "):])
    assert line["failed"] == 0 and line["correct"] is True, p.stdout[-3000:]
    checks = [json.loads(l[6:]) for l in p.stdout.splitlines() if l.startswith("CHECK ")]
    assert [c["check"] for c in checks] == [c["kind"] for c in CFG["checks"]]
    assert set(line["compared"]) == {c["kind"] for c in CFG["checks"]} | {"no_compile_in_window"}
