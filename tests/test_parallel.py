"""Distributed tree learners on the 8-device CPU mesh.

The reference tests multi-node behavior with in-process Dask workers over
localhost sockets (reference: tests/python_package_test/test_dask.py:26);
here the analog is an 8-virtual-CPU-device ``jax.sharding.Mesh``.
"""
import numpy as np
import pytest


def _mesh_ready():
    import jax
    return jax.default_backend() == "cpu" and len(jax.devices()) >= 8


needs_mesh = pytest.mark.skipif(
    "not config.getoption('collectonly', False) and not _mesh_ready()",
    reason="needs 8 CPU devices")


def _problem(rng, n=4000, f=10):
    X = rng.randn(n, f)
    w = rng.randn(f)
    y = (X @ w + 0.3 * rng.randn(n) > 0).astype(np.float64)
    return X, y


def _train(X, y, **overrides):
    import lightgbm_tpu as lgb
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "min_data_in_leaf": 5, "metric": ["auc"],
              "tpu_part_chunk": 256, "tpu_hist_chunk": 256}
    params.update(overrides)
    return lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=6)


@needs_mesh
@pytest.mark.parametrize("kind", ["data", "feature", "voting"])
def test_parallel_matches_serial(rng, kind):
    """Each distributed learner must produce a parity-quality model
    (reference analog: test_dask.py accuracy-vs-local assertions)."""
    from lightgbm_tpu.parallel import mesh as pm

    X, y = _problem(rng)
    serial = _train(X, y)
    (_, _, auc_s, _), = serial.eval_train()
    dist = _train(X, y, tree_learner=kind)
    cls = {"data": pm.DataParallelTreeLearner,
           "feature": pm.FeatureParallelTreeLearner,
           "voting": pm.VotingParallelTreeLearner}[kind]
    assert isinstance(dist.inner.learner, cls)
    (_, _, auc_d, _), = dist.eval_train()
    assert auc_d > 0.9
    # data-parallel computes the same global histograms -> same trees up
    # to f32 reduction order; feature/voting may differ on near-ties
    tol = 0.005 if kind == "data" else 0.03
    assert abs(auc_d - auc_s) < tol
    ps = serial.predict(X[:500])
    pd = dist.predict(X[:500])
    assert np.corrcoef(ps, pd)[0, 1] > 0.97


@needs_mesh
def test_data_parallel_uneven_rows(rng):
    """Row counts that don't divide the mesh force padding rows, which must
    never leak into histograms or predictions."""
    X, y = _problem(rng, n=4001)
    bst = _train(X, y, tree_learner="data")
    pred = bst.predict(X)
    assert pred.shape == (4001,)
    assert np.isfinite(pred).all()
    (_, _, auc, _), = bst.eval_train()
    assert auc > 0.9


@needs_mesh
def test_data_parallel_goss(rng):
    """GOSS sampling composes with the sharded learner (reference:
    goss.hpp under tree_learner=data)."""
    X, y = _problem(rng, n=4800)
    bst = _train(X, y, tree_learner="data", data_sample_strategy="goss",
                 top_rate=0.3, other_rate=0.2, learning_rate=0.3)
    (_, _, auc, _), = bst.eval_train()
    assert auc > 0.85


@needs_mesh
def test_sharded_valid_eval(rng):
    """Valid-set scoring during sharded training matches raw predictions."""
    import lightgbm_tpu as lgb

    X, y = _problem(rng, n=4000)
    Xv, yv = X[3000:], y[3000:]
    dtr = lgb.Dataset(X[:3000], label=y[:3000])
    dva = lgb.Dataset(Xv, label=yv, reference=dtr)
    res = {}
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "verbosity": -1, "tree_learner": "data",
                     "metric": ["binary_logloss"], "tpu_part_chunk": 256,
                     "tpu_hist_chunk": 256},
                    dtr, num_boost_round=6, valid_sets=[dva],
                    valid_names=["va"], callbacks=[lgb.record_evaluation(res)])
    pred = bst.predict(Xv)
    eps = 1e-7
    ll = -np.mean(yv * np.log(pred + eps) + (1 - yv) * np.log(1 - pred + eps))
    assert abs(ll - res["va"]["binary_logloss"][-1]) < 1e-3


@needs_mesh
def test_voting_wide_features(rng):
    """Voting must stay accurate when F >> 2*top_k (its comm stays
    O(top_k*B) while data-parallel's grows with F)."""
    n, f = 3000, 60
    X = rng.randn(n, f)
    w = np.zeros(f)
    w[:5] = rng.randn(5) * 3
    y = (X @ w + 0.3 * rng.randn(n) > 0).astype(np.float64)
    bst = _train(X, y, tree_learner="voting", top_k=8)
    (_, _, auc, _), = bst.eval_train()
    assert auc > 0.9
