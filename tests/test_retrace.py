"""Runtime retrace / compile-budget detector (ISSUE 4 tentpole).

obs.track_jit wraps every training-path jit entry point, turning
compiled-cache growth into ``jit/compiles/<name>`` telemetry counters.
These tests pin the contract the round-5 "dispatch soup" regression
violated: a first train pays a bounded number of compilations, and a
second train at identical shapes/config pays ZERO — every jit entry must
hit its cache (fused path: the cross-Booster _BLOCK_CACHE).
"""
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu import obs  # noqa: E402

#: first-train ceiling for TRACKED entry-point compiles. The fused path
#: compiles run_block once; the eager path adds learner/build, grads,
#: score_add and assign_leaves. Anything near double this is a retrace
#: leak, not workload growth.
PER_TRAIN_COMPILE_BUDGET = 8


def _data(n=600, f=8, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.1 * rng.randn(n) > 0).astype(np.float64)
    return X, y


PARAMS = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
          "tpu_iter_block": 5}


# ------------------------------------------------------------ track_jit unit

def test_track_jit_counts_traces():
    obs.telemetry.reset()
    calls = []

    @jax.jit
    def f(x):
        calls.append(None)
        return x * 2

    g = obs.track_jit("test/f", f)
    g(jnp.ones((4,)))
    assert obs.jit_compiles().get("test/f") == 1
    g(jnp.ones((4,)))                      # cache hit: no growth
    assert obs.jit_compiles().get("test/f") == 1
    g(jnp.ones((8,)))                      # new shape: retrace
    assert obs.jit_compiles().get("test/f") == 2


def test_track_jit_delegates_attributes():
    @jax.jit
    def f(x):
        return x + 1

    g = obs.track_jit("test/delegate", f)
    lowered = g.lower(jnp.ones((2,)))      # PjitFunction API passes through
    assert lowered is not None
    # re-wrapping re-labels instead of stacking wrappers
    h = obs.track_jit("test/relabel", g)
    assert h._fn is f


def test_snapshot_exposes_jit_compiles():
    obs.telemetry.reset()

    @jax.jit
    def f(x):
        return x - 1

    obs.track_jit("test/snap", f)(jnp.ones((2,)))
    snap = obs.telemetry.snapshot()
    jc = snap["jit_compiles"]
    assert jc["per_function"] == {"test/snap": 1}
    assert jc["total"] == 1
    assert jc["backend_compiles"] >= 1     # global listener saw the compile


# ------------------------------------------------------------ train budgets

def test_first_train_within_compile_budget():
    X, y = _data()
    ds = lgb.Dataset(X, label=y)
    obs.telemetry.reset()
    bst = lgb.train(dict(PARAMS), ds, num_boost_round=5)
    jc = bst.telemetry()["jit_compiles"]
    assert jc["total"] >= 1, "no tracked jit entry point ran"
    assert jc["total"] <= PER_TRAIN_COMPILE_BUDGET, jc
    assert "fused/run_block" in jc["per_function"], jc


def test_second_identical_train_compiles_nothing():
    X, y = _data()
    ds = lgb.Dataset(X, label=y)
    lgb.train(dict(PARAMS), ds, num_boost_round=5)       # warm every cache
    obs.telemetry.reset()
    bst = lgb.train(dict(PARAMS), ds, num_boost_round=5)
    jc = bst.telemetry()["jit_compiles"]
    assert jc["total"] == 0, jc
    assert jc["backend_compiles"] == 0, jc


# ---------------------------------------------------------- serving budgets

def test_second_same_bucket_predict_zero_compiles():
    """The serving contract: once a bucket is warm, repeat predicts in that
    bucket pay ZERO tracked compiles, ZERO backend compiles, and ZERO host
    re-packs — regardless of the exact row count within the bucket."""
    from lightgbm_tpu.serve import PredictSession
    X, y = _data(n=1000)
    ds = lgb.Dataset(X, label=y)
    bst = lgb.train(dict(PARAMS), ds, num_boost_round=5)
    sess = PredictSession(bst, buckets=(1024,))
    sess.predict(X[:600], raw_score=True)    # warm: pack upload + compile
    obs.telemetry.reset()
    sess.predict(X[:600], raw_score=True)    # same bucket, same N
    sess.predict(X[:600], raw_score=True)
    sess.predict(X[:1000], raw_score=True)   # same bucket, different N
    jc = obs.telemetry.snapshot()["jit_compiles"]
    assert jc["total"] == 0, jc
    assert jc["backend_compiles"] == 0, jc
    assert obs.telemetry.counter("serve/pack_build") == 0
    assert obs.telemetry.counter("serve/bucket_hit") == 3


def test_second_same_shape_linear_predict_zero_compiles():
    """Linear models ride the same bucket contract: the coefficient-table
    gather + dot adds no per-call retrace, so a second same-shape predict
    on a linear model pays ZERO compiles and ZERO re-packs."""
    from lightgbm_tpu.serve import PredictSession
    rng = np.random.RandomState(3)
    X = rng.randn(1000, 5)
    y = 0.3 * X[:, 0] - 0.1 * X[:, 1] + 0.02 * rng.randn(1000)
    p = {"objective": "regression", "num_leaves": 8, "verbosity": -1,
         "linear_tree": True, "linear_lambda": 0.01}
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=dict(p)),
                    num_boost_round=4)
    assert any(t.is_linear for t in bst.inner.models)
    sess = PredictSession(bst, buckets=(1024,))
    sess.predict(X[:600])                    # warm: pack upload + compile
    obs.telemetry.reset()
    sess.predict(X[:600])                    # same bucket, same N
    sess.predict(X[:1000])                   # same bucket, different N
    jc = obs.telemetry.snapshot()["jit_compiles"]
    assert jc["total"] == 0, jc
    assert jc["backend_compiles"] == 0, jc
    assert obs.telemetry.counter("serve/pack_build") == 0
    assert obs.telemetry.counter("serve/bucket_hit") == 2


def test_warmup_ladder_compile_budget():
    """warmup() pre-compiles the ladder: at most one predict compile per
    rung, and a second warmup compiles nothing new."""
    from lightgbm_tpu.serve import PredictSession
    X, y = _data()
    ds = lgb.Dataset(X, label=y)
    bst = lgb.train(dict(PARAMS), ds, num_boost_round=5)
    rungs = (96, 192, 384)
    sess = PredictSession(bst, buckets=rungs)
    obs.telemetry.reset()
    sess.warmup()
    jc = obs.telemetry.snapshot()["jit_compiles"]["per_function"]
    assert jc.get("serve/predict_bucket", 0) <= len(rungs), jc
    obs.telemetry.reset()
    sess.warmup()
    jc = obs.telemetry.snapshot()["jit_compiles"]
    assert jc["total"] == 0, jc


def test_bench_json_carries_jit_compiles():
    """bench.py embeds telemetry.snapshot(); the jit_compiles section must
    be json-serializable and present."""
    import json
    X, y = _data(300, 6)
    ds = lgb.Dataset(X, label=y)
    obs.telemetry.reset()
    bst = lgb.train(dict(PARAMS), ds, num_boost_round=3)
    snap = json.loads(json.dumps(bst.telemetry()))
    assert "jit_compiles" in snap
    assert snap["jit_compiles"]["total"] >= 0
