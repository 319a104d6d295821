"""Forest-at-once serving kernel (ISSUE 16 tentpole).

``ops/forest.py`` packs the ensemble into BIN-space split-major tables
and evaluates the WHOLE forest per row tile in one pallas launch;
``serve/session.PredictSession`` routes to it behind the
``tpu_forest_kernel`` knob with the per-depth-gather ``_predict_bucket``
retained verbatim as the oracle. The contract these tests pin (the PR-12
discipline): under the CPU interpreter the kernel is BIT-IDENTICAL to
the oracle — ``a.tobytes() == b.tobytes()``, not allclose — for every
model class (plain binary, NaN-missing routing, categorical splits,
multiclass, linear leaves, linear + NaN), across chunked multi-tile
dispatches, and the knob's auto default resolves to "off" until
``scripts/forest_bisect.py`` validates the Mosaic lowering on hardware.

Feature grids are quantized to 1/64 (f32-exact, including the 1/128 bin
midpoints) so BIN-space routing vs raw-threshold routing cannot split a
row on representation error.
"""
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu import obs  # noqa: E402
from lightgbm_tpu.ops import partition  # noqa: E402
from lightgbm_tpu.serve import PredictSession  # noqa: E402
from lightgbm_tpu.utils.log import LightGBMError  # noqa: E402


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    """The kernel runs under the Pallas interpreter only because this says
    so; nothing infers it from the backend."""
    monkeypatch.setattr(partition, "_INTERPRET", True)


def _grid(rng, n, f):
    # 1/64 grid: every value and every bin-boundary midpoint is f32-exact
    return np.round(rng.randn(n, f) * 16) / 64.0


def _model(params, cat=False, nan=False, classes=0, rounds=10,
           n=800, f=10, n_query=300, seed=7):
    rng = np.random.RandomState(seed)
    X = _grid(rng, n, f)
    if cat:
        X[:, 0] = rng.randint(0, 6, size=n)
    if classes:
        y = np.digitize(X[:, 1], [-0.5, 0.5]).astype(np.float64)
    else:
        y = (X[:, 1] + 0.25 * _grid(rng, n, 1)[:, 0] > 0) \
            .astype(np.float64)
    if nan:
        m = rng.rand(n, f) < 0.15
        if cat:
            m[:, 0] = False
        X[m] = np.nan
    p = dict(params)
    p.setdefault("verbosity", -1)
    p.setdefault("num_leaves", 15)
    ds = lgb.Dataset(X, label=y, categorical_feature=[0] if cat else [])
    bst = lgb.train(p, ds, num_boost_round=rounds)
    Xq = _grid(rng, n_query, f)
    if cat:
        Xq[:, 0] = rng.randint(0, 6, size=n_query)
    if nan:
        mq = rng.rand(n_query, f) < 0.15
        if cat:
            mq[:, 0] = False
        Xq[mq] = np.nan
    return bst, Xq


CLASSES = {
    "binary": dict(params={"objective": "binary"}),
    "nan_missing": dict(params={"objective": "binary"}, nan=True),
    "categorical": dict(params={"objective": "binary"}, cat=True),
    "multiclass": dict(params={"objective": "multiclass", "num_class": 3},
                       classes=3),
    "linear": dict(params={"objective": "regression",
                           "linear_tree": True}),
    "linear_nan": dict(params={"objective": "regression",
                               "linear_tree": True}, nan=True),
}


# --------------------------------------------------------------- bit parity

@pytest.mark.parametrize("name", sorted(CLASSES))
def test_forest_kernel_bit_parity(name):
    """Kernel raw scores are byte-identical to the per-depth-gather
    oracle's for every model class (interpret-mode contract)."""
    bst, Xq = _model(**CLASSES[name])
    a = PredictSession(bst, buckets=(256,), forest="off").raw_scores(Xq)
    b = PredictSession(bst, buckets=(256,), forest="on").raw_scores(Xq)
    assert a.shape == b.shape
    assert a.tobytes() == b.tobytes(), \
        "%s: max |diff| = %g over %d/%d rows" \
        % (name, np.abs(a - b).max(), (np.abs(a - b) > 0).sum(), a.size)


def test_forest_kernel_multi_tile_chunked_dispatch():
    """A request past the top rung chunks into several dispatches, each
    padded to its covering bucket and spanning multiple kernel tiles —
    parity must survive the seams."""
    bst, _ = _model(**CLASSES["binary"])
    rng = np.random.RandomState(11)
    Xq = _grid(rng, 700, 10)       # 3 chunks at bucket 256, last padded
    a = PredictSession(bst, buckets=(256,), forest="off").raw_scores(Xq)
    b = PredictSession(bst, buckets=(256,), forest="on").raw_scores(Xq)
    assert a.tobytes() == b.tobytes()


def test_forest_kernel_final_predictions_match():
    """The full predict path (init score + output transform + squeeze)
    rides the same parity: final probabilities byte-match the oracle's."""
    bst, Xq = _model(**CLASSES["binary"])
    a = PredictSession(bst, buckets=(256,), forest="off").predict(Xq)
    b = PredictSession(bst, buckets=(256,), forest="on").predict(Xq)
    assert a.tobytes() == b.tobytes()


# ------------------------------------------------------------- eligibility

def test_forest_ineligible_falls_back_to_oracle():
    """A booster without its training Dataset (model round-tripped
    through a string) has no bin mappers to pack BIN tables from: a
    forest="on" session must warn once, fall back to the oracle, and
    still answer correctly."""
    bst, Xq = _model(**CLASSES["binary"])
    ref = PredictSession(bst, buckets=(256,)).predict(Xq)
    loaded = lgb.Booster(model_str=bst.model_to_string())
    obs.telemetry.reset()
    sess = PredictSession(loaded, buckets=(256,), forest="on")
    out = sess.predict(Xq)
    np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-7)
    assert obs.telemetry.counter("serve/forest_dispatches") == 0
    recs = obs.telemetry.snapshot()["records"]
    assert "forest_ineligible" in recs, recs.keys()


# ------------------------------------------------------------ knob plumbing

def test_forest_knob_auto_resolves_off():
    """The PR-12 discipline: parity is proven under interpret only, so
    auto stays off until forest_bisect.py validates hardware — and the
    resolution record names the script."""
    bst, _ = _model(**CLASSES["binary"])
    assert bst.inner._forest_knob() == "off"
    recs = {r["knob"]: r
            for r in bst.telemetry()["records"]["auto_resolution"]}
    rec = recs["tpu_forest_kernel"]
    assert rec["value"] == "off"
    assert "forest_bisect" in rec["reason"]


def test_forest_knob_explicit_on_reaches_session():
    params = dict(CLASSES["binary"]["params"], tpu_forest_kernel="on")
    bst, Xq = _model(params=params)
    assert bst.inner._forest_knob() == "on"
    obs.telemetry.reset()
    sess = PredictSession(bst, buckets=(256,))   # no override: follow knob
    sess.predict(Xq)
    assert obs.telemetry.counter("serve/forest_dispatches") >= 1


def test_forest_session_override_validated():
    bst, _ = _model(**CLASSES["binary"])
    with pytest.raises(LightGBMError):
        PredictSession(bst, forest="sideways")


def test_forest_config_value_validated():
    bst_params = {"objective": "binary", "verbosity": -1,
                  "tpu_forest_kernel": "sideways"}
    rng = np.random.RandomState(0)
    X = _grid(rng, 200, 5)
    y = (X[:, 0] > 0).astype(np.float64)
    with pytest.raises(LightGBMError):
        lgb.train(bst_params, lgb.Dataset(X, label=y), num_boost_round=2)


# --------------------------------------------------------- compile budgets

def test_forest_second_same_bucket_predict_zero_compiles():
    """The serving contract extends to the forest path: once a rung is
    warm, repeat forest predicts pay ZERO tracked compiles, ZERO backend
    compiles, and ZERO table rebuilds."""
    bst, Xq = _model(**CLASSES["binary"])
    sess = PredictSession(bst, buckets=(256,), forest="on")
    sess.predict(Xq[:200])            # warm: table build + compile
    obs.telemetry.reset()
    sess.predict(Xq[:200])            # same bucket, same N
    sess.predict(Xq[:256])            # same bucket, different N
    jc = obs.telemetry.snapshot()["jit_compiles"]
    assert jc["total"] == 0, jc
    assert jc["backend_compiles"] == 0, jc
    assert obs.telemetry.counter("serve/forest_build") == 0
    assert obs.telemetry.counter("serve/forest_dispatches") == 2
