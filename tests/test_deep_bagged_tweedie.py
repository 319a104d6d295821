"""What ``m5.train`` runs, at 20,000 rows on the CPU: trees past 256 leaves
(``num_leaves`` 2,047, ``min_data_in_leaf`` small), a bag of half the rows
and half the columns drawn inside the fused block, a tweedie target that is
mostly zeros with ``boost_from_average`` false, a categorical column of more
categories than ``max_bin`` and lag columns with NaNs.

- the fused block and the eager loop grow the same trees, byte for byte,
  with the objective's gradients computed in numpy through a callback in
  both loops: XLA:CPU emits ``jnp.exp`` as other code inside the block's
  fusions than in the eager step's (a vectorised loop or not; an
  ``optimization_barrier`` does not survive its pipeline), so the two
  loops' tweedie gradients differ in their last bits there, and a near tie
  of a 600-leaf tree then flips. What the comparison holds is everything
  after the gradients: the bag, the column draw, the deep split loop, the
  partition, the router and the score. The program's tweedie gradients are
  held to the plain reference's formula on their own;
- the Pallas router (interpreter), the XLA ``assign_leaves`` loop over
  int32 leaf ids and a numpy walk of the model text send every row to the
  same leaf of a tree of more than 256 leaves;
- ``benchmark/reference/tweedie.py``'s bag and column subset are the
  program's masks, and every tree's leaf counts sum to its bag;
- ``tree/inbag_rows`` and ``tree/rows`` grow once a tree in both loops.

The references are imported by path, as the benchmark's checks do."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu import fused, objective, obs
from lightgbm_tpu.config import Config
from lightgbm_tpu.learner import assign_leaves
from lightgbm_tpu.ops import partition as P
from lightgbm_tpu.ops.predict import tree_to_bin_log
from lightgbm_tpu.ops.route import ROUTE_BLOCK_ROWS, build_route_table, route_rows

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark")
ROWS, ROUNDS = 20_000, 2
PARAMS = {"objective": "tweedie", "tweedie_variance_power": 1.1,
          "num_leaves": 2047, "min_data_in_leaf": 12, "max_bin": 100,
          "bagging_fraction": 0.5, "bagging_freq": 1, "feature_fraction": 0.5,
          "learning_rate": 0.03, "boost_from_average": False,
          "categorical_feature": [0, 1], "min_data_per_group": 10, "verbosity": -1,
          "tpu_iter_block": ROUNDS}
COUNTERS = ("tree/trees", "tree/leaves", "tree/inbag_rows", "tree/rows")


def _table(n=ROWS, seed=11):
    """Column 0: 300 item codes (more than ``max_bin``); 1: 7 codes; 2-5:
    lags, NaN on a row's first days; 6-11: other numbers. Daily counts,
    about two thirds zero."""
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 12).astype(np.float32)
    X[:, 0] = rng.randint(0, 300, n)
    X[:, 1] = rng.randint(0, 7, n)
    item_level = np.exp(rng.randn(300) * 1.5 - 0.5)
    lam = item_level[X[:, 0].astype(int)] * np.exp(0.3 * X[:, 6] + 0.2 * (X[:, 1] == 3))
    y = (rng.poisson(lam) * (rng.rand(n) < 0.6)).astype(np.float32)
    X[:, 2:6] = np.minimum(rng.poisson(lam[:, None] * np.ones(4)), 30)
    X[rng.rand(n) < 0.1, 2:6] = np.nan
    return X, y


@pytest.fixture(scope="module")
def refs():
    sys.path.insert(0, BENCH)          # the references' own sibling imports
    try:
        from reference import model_text, tweedie
    finally:
        sys.path.remove(BENCH)
    return model_text, tweedie


@pytest.fixture(scope="module")
def table():
    return _table()


def _numpy_tweedie_gradients(self, score):
    """``RegressionTweedie.get_gradients``' formula in numpy float32, the same
    code whichever program calls it."""
    rho = np.float32(self.config.tweedie_variance_power)

    def grads(s, label):
        s, label = np.asarray(s), np.asarray(label)
        e1 = np.exp((np.float32(1) - rho) * s)
        e2 = np.exp((np.float32(2) - rho) * s)
        return (-label * e1 + e2,
                -label * (np.float32(1) - rho) * e1 + (np.float32(2) - rho) * e2)
    shape = jax.ShapeDtypeStruct(score.shape, jnp.float32)
    return jax.pure_callback(grads, (shape, shape), score, self.label)


def _train(X, y, eager):
    obs.telemetry.reset()
    kw = {"callbacks": [lambda env: None]} if eager else {}
    bst = lgb.train(dict(PARAMS), lgb.Dataset(X, label=y, params=dict(PARAMS)),
                    num_boost_round=ROUNDS, **kw)
    path = obs.telemetry.records("job_start")[-1]["path"]
    counters = {k: obs.telemetry.snapshot()["counters"].get(k) for k in COUNTERS}
    return bst, path, counters


@pytest.fixture(scope="module")
def trained(table):
    X, y = table
    fused._BLOCK_CACHE.clear()      # a block traced with the callback stays here
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(objective.RegressionTweedie, "get_gradients", _numpy_tweedie_gradients)
        out = {"fused": _train(X, y, eager=False), "eager": _train(X, y, eager=True)}
    fused._BLOCK_CACHE.clear()
    return out


def test_the_programs_tweedie_gradients_are_the_formulas(table, refs):
    _, tweedie = refs
    _, y = table
    obj = objective.RegressionTweedie(Config.from_params(dict(PARAMS)))
    obj.init(lgb.Dataset(np.zeros((ROWS, 1)), label=y).construct().metadata)
    score = np.random.RandomState(3).randn(ROWS).astype(np.float32) * 0.5
    g, h = jax.jit(lambda s: obj.gradients(s, 0))(jnp.asarray(score))
    rg, rh = tweedie.gradients(y, score, PARAMS["tweedie_variance_power"])
    np.testing.assert_allclose(np.asarray(g), rg, rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(np.asarray(h), rh, rtol=2e-6, atol=2e-6)


def test_the_block_and_the_eager_loop_grow_the_same_deep_trees(trained):
    (fused_bst, fused_path, _), (eager_bst, eager_path, _) = trained["fused"], trained["eager"]
    assert (fused_path, eager_path) == ("fused", "eager")
    a_trees, b_trees = fused_bst.inner.models, eager_bst.inner.models
    assert len(a_trees) == len(b_trees) == ROUNDS
    for a, b in zip(a_trees, b_trees):
        assert 256 < a.num_leaves <= PARAMS["num_leaves"]
        assert a.num_leaves == b.num_leaves
    assert fused_bst.model_to_string() == eager_bst.model_to_string()
    np.testing.assert_array_equal(np.asarray(fused_bst.inner.train_score.score),
                                  np.asarray(eager_bst.inner.train_score.score))
    # the categorical column of 300 codes holds 99 bins + the shared last one
    binned = fused_bst.inner.train_set
    assert binned.bin_mappers[0].num_bins == PARAMS["max_bin"]
    assert any(t.num_cat for t in a_trees)
    assert any(t.num_default_left for t in a_trees)


def test_routers_and_the_model_text_walk_agree_past_256_leaves(trained, table, refs,
                                                               monkeypatch):
    model_text, _ = refs
    X, _ = table
    bst = trained["fused"][0]
    ds = bst.inner.train_set
    bins = jnp.asarray(ds.binned)
    _, texts = model_text.parse(bst.model_to_string())
    monkeypatch.setattr(P, "_INTERPRET", True)
    for tree, text in zip(bst.inner.models, texts):
        log = tree_to_bin_log(tree, ds)
        leaf_of_slot = tree.to_split_arrays()["leaf_of_slot"]
        assert log.split_leaf.shape[0] + 1 > 256      # int32 leaf ids in the XLA loop
        xla = np.asarray(assign_leaves(bins, log, has_categorical=True))
        npad = -(-ROWS // ROUTE_BLOCK_ROWS) * ROUTE_BLOCK_ROWS
        bins_t = jnp.pad(bins.T, ((0, 0), (0, npad - ROWS))).reshape(
            bins.shape[1], npad // 128, 128)
        table_ = build_route_table(log, None, None, categorical=True)
        pallas = np.asarray(route_rows(bins_t, table_, log.num_splits, ROWS,
                                       categorical=True))[:ROWS]
        walked = model_text.route(text, X)
        np.testing.assert_array_equal(leaf_of_slot[xla], walked)
        np.testing.assert_array_equal(leaf_of_slot[pallas], walked)
        assert len(np.unique(walked)) == tree.num_leaves


def test_the_references_bag_and_columns_are_the_programs(trained, table, refs):
    _, tweedie = refs
    X, y = table
    bst = trained["fused"][0]
    cfg = Config.from_params(dict(PARAMS))
    nf = bst.inner.train_set.num_features
    sampler = fused.make_sampler(cfg, ROWS)
    fmask = fused.make_feature_mask_fn(cfg, nf)
    ones = jnp.ones((ROWS,), jnp.float32)
    for it, tree in enumerate(bst.inner.models):
        inbag = np.asarray(sampler(None, it, ones, ones)[0]) > 0
        np.testing.assert_array_equal(tweedie.bag(ROWS, PARAMS, it), inbag)
        assert int(tree.leaf_count.sum()) == int(inbag.sum()) == int(tree.internal_count[0])
        subset = tweedie.feature_subset(nf, PARAMS, it)
        np.testing.assert_array_equal(subset, np.asarray(fmask(it)))
        assert subset.sum() == int(np.ceil(PARAMS["feature_fraction"] * nf))
        used = {int(bst.inner.train_set.inner_feature_index(int(f)))
                for f in tree.split_feature[:tree.num_internal]}
        assert used <= set(np.flatnonzero(subset).tolist())
    assert abs(tweedie.bag(ROWS, PARAMS, 0).mean() - 0.5) < 0.02
    assert not np.array_equal(tweedie.bag(ROWS, PARAMS, 0), tweedie.bag(ROWS, PARAMS, 1))


@pytest.mark.parametrize("loop", ["fused", "eager"])
def test_the_bag_and_router_counters_grow_once_a_tree(trained, loop):
    bst, _, counters = trained[loop]
    trees = bst.inner.models
    assert counters["tree/trees"] == len(trees) == ROUNDS
    assert counters["tree/leaves"] == sum(t.num_leaves for t in trees)
    assert counters["tree/inbag_rows"] == sum(int(t.internal_count[0]) for t in trees)
    assert counters["tree/rows"] == ROWS * len(trees)
    assert counters == trained["eager" if loop == "fused" else "fused"][2]


# the categorical columns of a 40-column table, in three runs: four of 300,
# 60, 3 and 7 codes, then two, then the last column
WIDE_CATS = (0, 1, 2, 3, 17, 18, 29, 39)


def _wide_table(n=12_000, seed=12):
    """40 columns, 8 of them categorical codes (two of at most
    ``max_cat_to_onehot`` categories), the rest numbers with NaN lags."""
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 40).astype(np.float32)
    for c, k in zip(WIDE_CATS, (300, 60, 3, 7, 40, 4, 25, 90)):
        X[:, c] = rng.randint(0, k, n)
    lam = np.exp(0.8 * np.sin(X[:, 0]) + 0.4 * (X[:, 1] % 5 == 2)
                 + 0.3 * X[:, 5] - 0.2 * (X[:, 17] % 3) + 0.2 * X[:, 9])
    y = (rng.poisson(lam) * (rng.rand(n) < 0.6)).astype(np.float32)
    X[rng.rand(n) < 0.1, 4:8] = np.nan
    return X, y


def _train_both_searches():
    """Train the wide job twice, searching many against many over the
    categorical columns alone and over every column (``cat_features``
    empty); run in a child process by the test below."""
    import json
    from lightgbm_tpu import learner
    objective.RegressionTweedie.get_gradients = _numpy_tweedie_gradients
    X, y = _wide_table()
    params = dict(PARAMS, categorical_feature=list(WIDE_CATS))
    real = learner.SplitHyper
    out = []
    for every in (False, True):
        if every:
            learner.SplitHyper = lambda **kw: real(**dict(kw, cat_features=()))
        fused._BLOCK_CACHE.clear()
        bst = lgb.train(dict(params), lgb.Dataset(X, label=y, params=dict(params)),
                        num_boost_round=ROUNDS)
        hp = bst.inner.learner.hp
        out.append(dict(has_categorical=hp.has_categorical,
                        cat_features=list(hp.cat_features),
                        leaves=[t.num_leaves for t in bst.inner.models],
                        num_cat=sum(t.num_cat for t in bst.inner.models),
                        text=bst.model_to_string()))
    print(json.dumps(out))


def test_the_categorical_search_over_its_columns_alone_grows_the_same_trees():
    """The learner names the table's categorical columns to the search
    (``SplitHyper.cat_features``), which runs many against many over them
    alone: the model text is byte for byte that of the same job searching
    every column (the empty tuple), bagged, half the columns drawn, past
    256 leaves. The gradients come from numpy, as in ``trained``.

    The two jobs run in a child process whose XLA:CPU emits no FMA
    (``--xla_cpu_max_isa=SSE4_2``): on a CPU that has one, XLA contracts
    ``a * b + c`` inside a fused kernel, so the same numerical gain, whose
    arithmetic neither search changes, rounds one ulp apart in the two
    programs' fusions, and a near tie past the 600th split flips. Every
    operation rounded on its own, the two programs give the same bits."""
    import json
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        os.environ.get("XLA_FLAGS", "") + " --xla_cpu_max_isa=SSE4_2").strip())
    code = ("import sys; sys.path.insert(0, %r); sys.path.insert(0, %r); "
            "import test_deep_bagged_tweedie as t; t._train_both_searches()"
            % (os.path.dirname(BENCH), os.path.dirname(os.path.abspath(__file__))))
    run = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=600)
    assert run.returncode == 0, run.stderr[-4000:]
    compact, every = json.loads(run.stdout.strip().splitlines()[-1])
    assert compact["has_categorical"] and every["has_categorical"]
    assert compact["cat_features"] == list(WIDE_CATS) and every["cat_features"] == []
    assert all(256 < n <= PARAMS["num_leaves"] for n in compact["leaves"])
    assert compact["num_cat"] > 0
    assert compact["text"] == every["text"]
