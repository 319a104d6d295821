"""The Pallas router on trees with categorical splits (PR 35).

A job with ``categorical_feature`` columns hands ``route_rows`` each round's
kind and its go-left table as eight bit-packed SMEM words, and a categorical
round reads bit ``eff`` of them where a numerical round compares. Held here,
under the interpreter, to the XLA ``fori_loop`` (the CPU path, whose
categorical round is an (N, B) one-hot select), leaf id for leaf id, on the
logs of trained trees that mix numerical, one-against-the-rest and
many-against-many rounds; with and without EFB bundles beside the
categorical columns; in both forms of the kernel. A job WITHOUT such
columns must keep the ten-column table and the kernel it had.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import learner, runtime
from lightgbm_tpu.ops import partition, route
from lightgbm_tpu.ops.split import (KIND_CAT_MVM_ASC, KIND_CAT_ONEHOT,
                                    KIND_NUMERICAL)

sp = pytest.importorskip("scipy.sparse")

PARAMS = {"objective": "binary", "num_leaves": 31, "max_bin": 255,
          "tree_builder": "partition", "verbosity": -1, "min_data_in_leaf": 5,
          "min_data_per_group": 10, "cat_smooth": 1.0}


def _dense(rng, n):
    """Two numeric columns, a column of 3 categories (one against the rest),
    one of 40 and one of 300 (many against many; 300 > 254 bins, so the
    rarest share the last bin) and a negative / NaN category here and there."""
    X = rng.randn(n, 5)
    X[:, 2] = rng.randint(0, 3, n)
    X[:, 3] = rng.randint(0, 40, n)
    X[:, 4] = np.minimum(rng.zipf(1.3, n), 300) - 1
    w3, w40, w300 = rng.randn(3), rng.randn(40), rng.randn(300)
    score = (X[:, 0] + 0.5 * np.sin(3 * X[:, 1]) + w3[X[:, 2].astype(int)]
             + w40[X[:, 3].astype(int)] + w300[X[:, 4].astype(int)])
    X[rng.rand(n) < 0.01, 3] = np.nan
    X[rng.rand(n) < 0.01, 4] = -1
    y = (score + 0.3 * rng.randn(n) > 0).astype(np.float64)
    return X, y, [2, 3, 4]


def _bundled(rng, n):
    """The dense table beside two one-hot blocks that EFB bundles: the
    categorical columns keep a device column each (``plain``), the rounds on
    bundled columns go through the bundle arithmetic in the same tree."""
    X, y, cats = _dense(rng, n)
    blocks = [sp.csr_matrix(X)]
    for card in (9, 14):
        ids = rng.randint(0, card, n)
        blocks.append(sp.csr_matrix((np.ones(n), (np.arange(n), ids)),
                                    shape=(n, card)))
        y = np.where(ids == 0, 1.0 - y, y)
    return sp.hstack(blocks).tocsr(), y, cats


def _log_of_a_tree(X, y, cats):
    n = len(y)
    ds = lgb.Dataset(X, label=y, params=dict(PARAMS), categorical_feature=cats)
    lrn = lgb.Booster(dict(PARAMS), ds).inner.learner
    assert lrn.hp.has_categorical
    grad = jnp.asarray(np.stack([0.5 - y, np.full(n, 0.25), np.ones(n)],
                                axis=1), jnp.float32)
    f = lrn.dataset.num_features
    log = lrn.make_build_fn()(
        lrn.bins, grad, lrn.meta, jnp.ones((f,), bool), jax.random.PRNGKey(0),
        jnp.zeros((f,), bool))
    return lrn, log


@pytest.mark.parametrize("kind", ["dense", "bundled"])
def test_categorical_rounds_route_as_the_xla_router(kind, rng, monkeypatch):
    monkeypatch.setattr(partition, "_INTERPRET", True)
    X, y, cats = (_dense if kind == "dense" else _bundled)(rng, 40_000)
    lrn, log = _log_of_a_tree(X, y, cats)
    assert (lrn.bundle is not None) == (kind == "bundled")
    splits = int(log.num_splits)
    kinds = np.asarray(log.kind)[:splits]
    # the mix the kernel has to take: all three kinds of round in one tree
    assert (kinds == KIND_NUMERICAL).any() and (kinds == KIND_CAT_ONEHOT).any() \
        and (kinds >= KIND_CAT_MVM_ASC).any(), kinds
    if kind == "bundled":
        bundled = np.asarray(lrn.bundle["has_rest"])[np.asarray(log.feature)[:splits]]
        assert bundled.any(), "no round split a bundled column"
    want = np.asarray(learner._route_rows(lrn.bins, log, True, lrn.bundle,
                                          None))
    assert np.array_equal(want, np.asarray(log.row_leaf))
    assert len(np.unique(want)) == splits + 1
    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    for budget, form in ((route.ROUTE_VMEM_BUDGET, "stream"), (0, "wide")):
        monkeypatch.setattr(route, "ROUTE_VMEM_BUDGET", budget)
        assert route.route_form(lrn.bins.shape[1]) == form
        got = learner._route_rows(lrn.bins, log, True, lrn.bundle, None)
        assert np.array_equal(np.asarray(got), want), form
    # a tree that stopped early: the rounds past its last split change nothing
    short = log._replace(num_splits=jnp.int32(4))
    got = np.asarray(learner._route_rows(lrn.bins, short, True, lrn.bundle,
                                         None))
    monkeypatch.setattr(runtime, "on_tpu", lambda: False)
    want = np.asarray(learner._route_rows(lrn.bins, short, True, lrn.bundle,
                                          None))
    assert np.array_equal(got, want) and want.max() == 4


def test_table_of_a_numerical_job_is_the_ten_columns_it_was(rng):
    """``categorical`` is static: without it the table holds no kind and no
    bit word, with it each round gains 1 + TABLE_WORDS scalars whose bits
    are the round's go-left table."""
    X, y, cats = _dense(rng, 6000)
    _, log = _log_of_a_tree(X, y, cats)
    rounds = log.split_leaf.shape[0]
    plain = route.build_route_table(log, None, None)
    assert plain.shape == (rounds * route.TBL_W,) and route.TBL_W == 10
    assert route.table_width(False) == 10
    wide = np.asarray(route.build_route_table(log, None, None, True)) \
        .reshape(rounds, route.table_width(True))
    assert route.table_width(True) == 10 + 1 + partition.TABLE_WORDS
    assert np.array_equal(wide[:, :10], np.asarray(plain).reshape(rounds, 10))
    assert np.array_equal(wide[:, 10], (np.asarray(log.kind) > 0).astype(np.int32))
    words = wide[:, 11:].astype(np.uint32)
    bits = (words[:, :, None] >> np.arange(32, dtype=np.uint32)) & 1
    go_left = np.asarray(log.go_left)
    assert np.array_equal(bits.reshape(rounds, -1)[:, :go_left.shape[1]],
                          go_left.astype(np.uint32))


def test_categorical_columns_of_more_bins_than_the_words_hold_keep_xla():
    assert route.pallas_routes(False, 1024)
    assert route.pallas_routes(True, 256) and route.pallas_routes(True, 255)
    assert not route.pallas_routes(True, 257)


@pytest.mark.parametrize("f,form", [(28, "stream"), (600, "wide")])
def test_a_numerical_jobs_kernel_holds_no_bit_word_and_no_branch(f, form):
    """``categorical`` is static: the kernel of a job without categorical
    columns is traced without the round's kind, its eight words, the shift
    and the branch (``higgs.train`` spends 41 ms an iteration in it; the
    jaxpr of both forms was compared with the parent commit's, PR 35)."""
    assert route.route_form(f) == form
    bt = jnp.zeros((f, 256, 128), jnp.uint8)
    texts = {}
    for cat in (False, True):
        table = jnp.zeros((254 * route.table_width(cat),), jnp.int32)
        texts[cat] = str(jax.make_jaxpr(
            lambda b, t, s: route.route_rows(b, t, s, 32768, categorical=cat))(
                bt, table, jnp.int32(5)))
    # the wide form's own ``pl.when(r == 0)`` is its one branch
    assert texts[False].count(" cond[") == (form == "wide")
    assert texts[True].count(" cond[") == 1 + (form == "wide")
    assert "shift_right_logical" not in texts[False]
    assert "shift_right_logical" in texts[True]
