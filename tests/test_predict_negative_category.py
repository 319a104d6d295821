"""A value no category can be goes RIGHT at a categorical node, on the device
predictor as on the host walk (upstream's ``Tree::CategoricalDecision``: NaN
right; truncated toward zero; negative right; not in the set right).

Until PR 35 the device predictor (``ops/predict.py``, taken from
``DEVICE_PREDICT_MIN_ROWS`` rows on) padded a node's set with -2 and matched
a value that truncates to -2 against the padding: such a row went LEFT
wherever a set was shorter than the model's longest, while training, the
model text and the host walk sent it right."""
import numpy as np
import pytest

import lightgbm_tpu as lgb

ROWS = 3000
ODD = [-1.0, -2.0, -2.7, -3.0, np.nan, 9999.0, -0.5 - 2.0, np.inf, -np.inf]


@pytest.fixture(scope="module")
def model():
    rng = np.random.RandomState(0)
    X = rng.normal(size=(ROWS, 4)).astype(np.float32)
    c = rng.randint(0, 12, size=ROWS)
    X[:, 0] = c
    y = (rng.normal(size=12)[c] + X[:, 1] + 0.3 * rng.normal(size=ROWS) > 0)
    params = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
              "verbosity": -1}
    bst = lgb.train(params, lgb.Dataset(X, label=y.astype(np.float32),
                                        params=params,
                                        categorical_feature=[0]),
                    num_boost_round=8)
    sizes = {len(v) for t in bst.inner.models for v in t.cat_threshold.values()}
    assert len(sizes) > 1, "sets of one length hold no padding"
    return bst, X


@pytest.mark.parametrize("value", ODD, ids=[repr(v) for v in ODD])
def test_an_impossible_category_goes_right_on_both_paths(model, value):
    bst, X = model
    n = 2 * bst.inner.DEVICE_PREDICT_MIN_ROWS
    assert n >= 512
    P = X[:n].copy()
    P[:, 0] = value
    unseen = P.copy()
    unseen[:, 0] = 777.0            # no training row held it: right everywhere
    want = bst.predict(unseen[:64], raw_score=True)         # the host walk
    assert np.array_equal(bst.predict(P[:64], raw_score=True), want)
    got = bst.predict(P, raw_score=True)                    # the device path
    assert np.abs(got[:64] - want).max() < 1e-6
    assert np.abs(got - bst.predict(unseen, raw_score=True)).max() < 1e-6
    # the column matters: a category of a left set scores otherwise
    inset = P.copy()
    inset[:, 0] = float(next(iter(
        bst.inner.models[0].cat_threshold.values()))[0])
    assert np.abs(bst.predict(inset, raw_score=True) - got).max() > 1e-3
