"""CPU tests of the plain reference's reach over the model format: every text
the program's ``model_to_string()`` writes for a tree booster (categorical
splits, missing-value directions, K trees an iteration) parses and walks,
independent of the program, and a plain model reads as it always did. Each
table is trained by the program at a few thousand rows. By hand, like the
others:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
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

ROWS, ROUNDS = 3000, 8
PREDICT_ARGS = {"rows": 65536, "tol": 1e-5, "edge_rows_max": 64}
COUNT_ARGS = {"trees": ["first", "last"]}
TABLES = ("many_vs_many", "one_vs_rest", "other_bin", "nan_both_sides", "zero_as_missing",
          "three_classes")


def make_table(kind):
    """-> (X float32, label, params, categorical columns, what the trained
    model's ``decision_type`` values must be). Column 0 is the one a table is
    about; a label is the sign of a score plus noise."""
    rng = np.random.RandomState(TABLES.index(kind))
    X = rng.normal(size=(ROWS, 5)).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5, "verbosity": -1}
    cat, kinds = None, {0}
    if kind == "many_vs_many":          # 12 categories: sets of several categories go left
        c = rng.randint(0, 12, size=ROWS)
        score, cat, kinds = rng.normal(size=12)[c] + X[:, 1], [0], {0, 1}
    elif kind == "one_vs_rest":         # 4 categories: one category goes left
        c = rng.randint(0, 4, size=ROWS)
        score, cat, kinds = np.array([1.0, -1.0, 0.5, -0.3])[c] + X[:, 1], [0], {0, 1}
    elif kind == "other_bin":           # 60 categories, 15 bins for them: 45 share the other bin
        c = np.minimum(rng.zipf(1.3, size=ROWS), 60) - 1
        score, cat, kinds = rng.normal(size=60)[c] + X[:, 1], [0], {0, 1}
        params["max_bin"] = 16
    elif kind == "nan_both_sides":      # NaNs that belong left in one column, right in another
        score = X[:, 0] + X[:, 1] - X[:, 2]
        X[rng.rand(ROWS) < 0.2, 0] = np.nan
        X[(X[:, 1] > 0.5) & (rng.rand(ROWS) < 0.6), 1] = np.nan
        X[(X[:, 2] > 0.5) & (rng.rand(ROWS) < 0.6), 2] = np.nan
        kinds = {8, 10}
    elif kind == "zero_as_missing":
        score = X[:, 0] + X[:, 1]
        X[rng.rand(ROWS) < 0.3, 0] = 0.0
        X[rng.rand(ROWS) < 0.1, 1] = np.nan
        params["zero_as_missing"] = True
        kinds = {4, 6}
    elif kind == "three_classes":
        score = X[:, 0] + X[:, 1]
        params.update(objective="multiclass", num_class=3)
        label = np.digitize(score + 0.3 * rng.normal(size=ROWS), [-0.7, 0.7]).astype(np.float32)
        return X, label, params, cat, kinds
    if cat:
        X[:, 0] = c
    label = (score + 0.3 * rng.normal(size=ROWS) > 0).astype(np.float32)
    return X, label, params, cat, kinds


def probe_rows(X, kind):
    """Training rows with the values a deployment meets and training did not.
    A categorical column: a category no row held, a negative one, NaN, a
    fraction, one past 32 bits, infinity. A column with a missing type: NaN,
    both zeros, values inside and just outside upstream's zero band, and NaNs
    and zeros in two more columns. Plain trees get no NaN (``model_text.route``
    says why)."""
    if kind in ("many_vs_many", "one_vs_rest", "other_bin"):
        odd = [9999.0, -1.0, -3.0, np.nan, 2.5, -0.5, 1e10, np.inf]
    else:
        odd = [np.inf, 0.0, -0.0, 1e-36, -1e-36, 1e-30, -1e-30, -np.inf]
    P = np.repeat(X[:32], len(odd), axis=0)
    P[:, 0] = np.tile(np.array(odd, np.float32), 32)
    if kind in ("nan_both_sides", "zero_as_missing"):
        P[::8, 0] = np.nan
        P[1::3, 1] = np.nan
        P[2::5, 2] = 0.0
    return np.ascontiguousarray(P)


@pytest.fixture(scope="module", params=TABLES)
def job(request):
    import lightgbm_tpu as lgb
    X, label, params, cat, kinds = make_table(request.param)
    ds = lgb.Dataset(X, label=label, params=params, categorical_feature=cat or "auto")
    bst = lgb.train(params, ds, num_boost_round=ROUNDS)
    text = bst.model_to_string()
    header, trees = model_text.parse(text)
    return {"kind": request.param, "X": X, "cat": cat, "kinds": kinds, "booster": bst,
            "text": text, "header": header, "trees": trees}


def check(kind):
    spec = importlib.util.spec_from_file_location(
        "bench_checks_" + kind, os.path.join(BENCH, "checks", kind + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.run


def context(job, text=None):
    header, trees = model_text.parse(text) if text else (job["header"], job["trees"])
    return {"rows": ROWS, "X": job["X"], "header": header, "trees": trees,
            "booster": job["booster"]}


def test_the_table_trains_the_splits_it_is_about(job):
    K = 3 if job["kind"] == "three_classes" else 1
    assert model_text.trees_per_iteration(job["header"]) == K == int(job["header"]["num_class"])
    assert len(job["trees"]) == K * ROUNDS
    seen = set(np.concatenate([t["decision_type"] for t in job["trees"]]).tolist())
    assert seen == job["kinds"]
    sizes = [len(s) for t in job["trees"] for s in t["cat_sets"].values()]
    if job["kind"] == "many_vs_many":
        assert max(sizes) > 1
    if job["kind"] == "one_vs_rest":
        assert set(sizes) == {1}
    if job["kind"] == "other_bin":      # 15 categories have a bin; the rest can go nowhere but right
        assert len(set(np.concatenate([s for t in job["trees"] for s in t["cat_sets"].values()]))) <= 15
        assert len(np.unique(job["X"][:, 0])) > 30
    if job["kind"] == "nan_both_sides":
        sides = np.concatenate([t["default_left"] for t in job["trees"]])
        assert sides.any() and not sides.all()
    for t in job["trees"]:
        assert t["num_cat"] == int(t["is_categorical"].sum()) == len(t["cat_sets"])
        assert np.array_equal(t["decision_type"],
                              t["is_categorical"] + 2 * t["default_left"] + 4 * t["missing_type"])


def test_walked_leaf_counts_are_the_recorded_ones(job):
    for i, t in enumerate(job["trees"]):
        assert np.array_equal(model_text.leaf_counts(t, job["X"]), t["leaf_count"]), i
        parent, left, right = model_text.split_rows(t)
        assert parent[0] == ROWS and np.array_equal(parent, left + right), i


@pytest.mark.parametrize("rows", ["training", "probe", "probe_on_the_device_path"])
def test_raw_score_is_the_programs_predict(job, rows):
    bst = job["booster"]
    X = job["X"] if rows == "training" else probe_rows(job["X"], job["kind"])
    if rows == "probe_on_the_device_path":
        X = np.ascontiguousarray(np.tile(X, (2, 1)))
    assert (len(X) >= bst.inner.DEVICE_PREDICT_MIN_ROWS) == (rows != "probe")
    mine = model_text.raw_score(job["header"], job["trees"], X)
    theirs = np.asarray(bst.predict(X, raw_score=True), np.float64)
    assert mine.shape == theirs.shape
    assert np.abs(mine - theirs).max() < 1e-5


@pytest.mark.xfail(strict=True, reason="PROGRAM, lightgbm_tpu/ops/predict.py pack_trees: a categorical "
                   "node's set is padded with -2 'never matches', and a row whose category truncates to -2 "
                   "matches it on the device predictor (512 rows or more) wherever a set is shorter than the "
                   "model's longest: it goes left; upstream, the text, training and the host walk send it "
                   "right. PERF.md section 7; the model_config PR that brings categorical columns repairs it")
def test_a_category_of_minus_two_goes_right_on_the_device_path():
    import lightgbm_tpu as lgb
    X, label, params, cat, _ = make_table("many_vs_many")
    bst = lgb.train(params, lgb.Dataset(X, label=label, params=params, categorical_feature=cat),
                    num_boost_round=ROUNDS)
    header, trees = model_text.parse(bst.model_to_string())
    P = np.ascontiguousarray(X[:bst.inner.DEVICE_PREDICT_MIN_ROWS])
    P[:, 0] = -2.0
    mine = model_text.raw_score(header, trees, P)
    assert np.abs(mine[:64] - bst.predict(P[:64], raw_score=True)).max() < 1e-5    # the host walk agrees
    assert np.abs(mine - bst.predict(P, raw_score=True)).max() < 1e-5


def test_the_text_survives_a_round_trip(job):
    """What a tier-1 test would hold in every later PR: the program's predict
    from the loaded text is its predict from the trained model, and the text
    is written again as it was read."""
    import lightgbm_tpu as lgb
    loaded = lgb.Booster(model_str=job["text"])
    X = probe_rows(job["X"], job["kind"])
    for rows in (X, job["X"]):
        assert np.array_equal(loaded.predict(rows, raw_score=True),
                              job["booster"].predict(rows, raw_score=True))
    _, again = model_text.parse(loaded.model_to_string())
    for a, b in zip(again, job["trees"], strict=True):
        assert np.array_equal(a["decision_type"], b["decision_type"])
        assert np.array_equal(a["threshold"], b["threshold"])
        assert a["cat_sets"].keys() == b["cat_sets"].keys()
        assert all(np.array_equal(a["cat_sets"][n], b["cat_sets"][n]) for n in a["cat_sets"])


def edit_tree(text, index, key, change):
    """The text with ``change`` applied to the value of ``key`` in ``Tree=index``."""
    head, *blocks = text.split("\nTree=")
    lines = blocks[index].split("\n")
    at = next(i for i, l in enumerate(lines) if l.startswith(key + "="))
    lines[at] = key + "=" + change(lines[at].split("=", 1)[1])
    blocks[index] = "\n".join(lines)
    return "\nTree=".join([head] + blocks)


def doctor(job, kind):
    """The model's text with one small lie in its first tree, of the kind the
    table is about and the check ``kind`` can see, at the node most training
    rows pass."""
    t = job["trees"][0]
    if job["cat"]:
        # the category most rows of the table hold, moved across its node's set
        node = max(t["cat_sets"], key=lambda n: t["internal_count"][n])
        values, counts = np.unique(job["X"][:, 0].astype(np.int64), return_counts=True)
        moved = int(values[np.argmax(counts)])
        kept = sorted(set(t["cat_sets"][node].tolist()) ^ {moved})

        def change(value):
            items = dict(i.split(":") for i in value.split(";"))
            items[str(node)] = ",".join(map(str, kept))
            return ";".join(k + ":" + v for k, v in items.items())
        return edit_tree(job["text"], 0, "cat_threshold", change)
    if job["kind"] == "three_classes":
        head, first, second, *rest = job["text"].split("\nTree=")
        if kind == "predict":   # the first two trees change places: each adds to the other's class
            return "\nTree=".join([head, "0" + second[1:], "1" + first[1:]] + rest)
        counts = " ".join(map(str, job["trees"][1]["leaf_count"]))
        return edit_tree(job["text"], 0, "leaf_count", lambda _: counts)   # another class's counts
    node = 0        # the root's default side: every missing value of its column turns round

    def change(value):
        kinds = value.split()
        kinds[node] = str(int(kinds[node]) ^ 2)
        return " ".join(kinds)
    return edit_tree(job["text"], 0, "decision_type", change)


def test_accepted_checks_pass_on_the_model_and_fail_on_a_doctored_one(job):
    for kind, args in (("predict", PREDICT_ARGS), ("routed_counts", COUNT_ARGS)):
        ok, detail = check(kind)(args, context(job))
        assert ok, detail
        ok, detail = check(kind)(args, context(job, doctor(job, kind)))
        assert not ok, detail


def test_upstreams_bitset_form_parses_to_the_same_sets_as_its_twin():
    """One tree by hand, three categorical nodes and a numerical one, written
    the program's way and upstream's (``cat_boundaries`` + bitset words, the
    node's ``threshold`` its index there). Node 2's set needs two words."""
    common = """tree
version=v3
num_class=1
num_tree_per_iteration=1
init_score=0.25

Tree=0
num_leaves=5
num_cat=3
split_feature=0 1 0 0
split_gain=4 3 2 1
decision_type=1 8 1 1
left_child=1 -1 -3 -4
right_child=2 -2 3 -5
leaf_value=0.1 0.2 0.3 0.4 0.5
leaf_count=1 1 1 1 1
internal_count=5 2 3 2
"""
    ours = common + "threshold=1 0.5 2 3\ncat_threshold=0:0,3,31;2:1,32,40;3:\n\nend of trees\n"
    words = [(1 << 0) | (1 << 3) | (1 << 31), (1 << 1), (1 << 0) | (1 << 8), 0]
    upstream = common + ("threshold=0 0.5 1 2\ncat_boundaries=0 1 3 4\ncat_threshold=%s\n"
                         "is_linear=0\nshrinkage=0.1\n\nend of trees\n" % " ".join(map(str, words)))
    (_, (a,)), (_, (b,)) = model_text.parse(ours), model_text.parse(upstream)
    want = {0: [0, 3, 31], 2: [1, 32, 40], 3: []}
    for t in (a, b):
        assert {n: s.tolist() for n, s in t["cat_sets"].items()} == want
        assert all(s.dtype == np.int64 for s in t["cat_sets"].values())
        assert t["is_categorical"].tolist() == [True, False, True, True]
        assert t["missing_type"].tolist() == [0, 2, 0, 0] and not t["default_left"].any()
    #            root set -> node 1;  x1 <= 0.5 | NaN -> right;  else node 2 -> its set | node 3 (empty set)
    X = np.array([[3, 0.0], [31, np.nan], [0, 0.5], [32, 9], [40.9, 9], [2, 9], [-1, 9], [np.nan, 9],
                  [64, 9], [33, 9]], np.float32)
    for t in (a, b):
        assert model_text.route(t, X).tolist() == [0, 1, 0, 2, 2, 4, 4, 4, 4, 4]
    header = model_text.parse(ours)[0]
    assert model_text.raw_score(header, [a], X)[:2].tolist() == [0.25 + 0.1, 0.25 + 0.2]


def parse_before_this_pr(text):
    """``model_text.parse`` as PR 24 accepted it, less its refusal."""
    head, *blocks = text.split("\nTree=")
    header = dict(l.split("=", 1) for l in head.splitlines() if "=" in l)
    trees = []
    for b in blocks:
        kv = dict(l.split("=", 1) for l in b.split("\n\n")[0].splitlines()[1:] if "=" in l)
        t = {"num_leaves": int(kv["num_leaves"])}
        for k in ("split_feature", "left_child", "right_child", "leaf_count",
                  "internal_count", "decision_type"):
            t[k] = np.array(kv.get(k, "").split(), dtype=np.int64)
        for k in ("leaf_value", "threshold", "split_gain"):
            t[k] = np.array(kv.get(k, "").split(), dtype=np.float64)
        trees.append(t)
    return header, trees


def test_a_plain_model_parses_to_the_keys_and_arrays_it_always_did(monkeypatch):
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(7)
    X = rng.normal(size=(ROWS, 4)).astype(np.float32)
    label = (X[:, 0] - X[:, 1] + 0.3 * rng.normal(size=ROWS) > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1}
    bst = lgb.train(params, lgb.Dataset(X, label=label, params=params), num_boost_round=3)
    text = bst.model_to_string()
    (header, trees), (old_header, old_trees) = model_text.parse(text), parse_before_this_pr(text)
    assert header == old_header and all(isinstance(v, str) for v in header.values())
    added = {"num_cat", "is_categorical", "default_left", "missing_type", "cat_sets"}
    for t, old in zip(trees, old_trees, strict=True):
        assert set(t) == set(old) | added
        assert t["num_leaves"] == old["num_leaves"]
        for k in set(old) - {"num_leaves"}:
            assert t[k].dtype == old[k].dtype and np.array_equal(t[k], old[k]), k
        assert not t["decision_type"].any() and t["num_cat"] == 0 and t["cat_sets"] == {}
    # an all-zero decision_type is walked by the comparison alone, as before
    monkeypatch.setattr(model_text, "_route_rows_decided", None)
    assert model_text.raw_score(header, trees, X).shape == (ROWS,)
    assert np.array_equal(model_text.leaf_counts(trees[0], X), trees[0]["leaf_count"])


def test_both_walks_agree_on_a_plain_tree_but_for_nan():
    """The decisions on a plain tree (an all-zero ``decision_type``: no missing
    type, no default side) are the comparison alone, on every row but a NaN:
    the comparison sends it right (``NaN <= t`` is false), upstream's decision
    under the missing type none walks it as 0."""
    tree = {"num_leaves": 3, "split_feature": np.array([1, 0]), "threshold": np.array([0.1, -1.0]),
            "left_child": np.array([1, -1]), "right_child": np.array([-3, -2]),
            "decision_type": np.zeros(2, np.int64), "is_categorical": np.zeros(2, bool),
            "default_left": np.zeros(2, bool), "missing_type": np.zeros(2, np.int64),
            "cat_sets": {}}
    X = np.random.RandomState(0).normal(size=(500, 2)).astype(np.float32)
    X[0] = -5.0, np.float32(0.1)        # above 0.1: right exactly, left by the nearest float32
    X[1] = -5.0, np.nan                 # as 0: left, then left
    for nearest32 in (False, True):
        plain = model_text._route_rows(tree, X, nearest32)
        decided = model_text._route_rows_decided(tree, X, nearest32)
        assert np.array_equal(decided[[0] + list(range(2, 500))], plain[[0] + list(range(2, 500))])
        assert plain[0] == (0 if nearest32 else 2)
        assert (plain[1], decided[1]) == (2, 0)


def test_a_linear_tree_is_refused_by_name():
    text = "tree\nnum_tree_per_iteration=1\n\nTree=0\nnum_leaves=1\nleaf_value=0\nis_linear=1\n\nend of trees\n"
    with pytest.raises(ValueError, match="leaf_coeff"):
        model_text.parse(text)


def test_a_categorical_node_without_its_set_is_refused():
    text = ("tree\n\nTree=0\nnum_leaves=2\nnum_cat=1\nsplit_feature=0\nthreshold=1\ndecision_type=1\n"
            "left_child=-1\nright_child=-2\nleaf_value=0 1\nleaf_count=1 1\ninternal_count=2\n"
            "cat_threshold=1:4\n\nend of trees\n")
    with pytest.raises(ValueError, match="categorical"):
        model_text.parse(text)


@pytest.mark.parametrize("K", [1, 3])
def test_the_windows_trees_are_taken_k_an_iteration(K):
    """run.py's slice of the model: iterations [first, first + n) are trees
    [K first, K (first + n)), and an iteration failed if one of its K trees is
    not there or split nothing. With K = 1 it is the slice and the count
    run.py had."""
    header = {"num_tree_per_iteration": str(K)}
    leaves = [255] * (K * 7)
    leaves[K * 3 + K - 1] = 1                       # iteration 3's last tree is a stump
    trees = [{"num_leaves": n, "at": i} for i, n in enumerate(leaves)]
    mine, failed = model_text.window(header, trees, 2, 4)
    assert [t["at"] for t in mine] == list(range(2 * K, 6 * K)) and failed == 1
    mine, failed = model_text.window(header, trees, 5, 4)   # the model ends two iterations early
    assert [t["at"] for t in mine] == list(range(5 * K, 7 * K)) and failed == 2
    if K == 1:
        old = trees[2:2 + 4]
        assert model_text.window({}, trees, 2, 4) == (old, 4 - sum(t["num_leaves"] > 1 for t in old))
    else:
        del trees[-1]                               # the last iteration lost one of its trees
        assert model_text.window(header, trees, 5, 2)[1] == 1


def test_a_run_ends_with_what_it_compared_on_both_streams():
    """The contract's last lines: every check's verdict, with the numbers it
    compared beside their limits, under the result line's own key and as the
    last lines of standard error (what the driver keeps of a run that is not
    correct)."""
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", "higgs.train",
         "--seed", "3000000023", "--seconds", "1", "--trace", "0", "--rehearse",
         '{"rows": 20000, "params": {"num_leaves": 15}}'],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True,
        timeout=900)
    assert p.returncode == 4, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1][len("REHEARSAL "):])
    with open(os.path.join(BENCH, "configs", "higgs-binary-255.json")) as f:
        kinds = [c["kind"] for c in json.load(f)["checks"]] + ["no_compile_in_window"]
    assert list(line["compared"]) == sorted(kinds)          # a rehearsal's line is printed sorted
    assert all(v["ok"] for v in line["compared"].values()) and line["correct"] is True
    assert "(tol 1e-05)" in line["compared"]["predict"]["detail"]
    last = p.stderr.strip().splitlines()[-len(kinds):]
    assert [l.split()[:2] for l in last] == [["COMPARED", k] for k in kinds]
    assert all(" ok=True: " in l for l in last)
