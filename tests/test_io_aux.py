"""Auxiliary IO/subsystem surface: binary dataset cache, snapshots, forced
bins, pandas inputs, plotting, timers.

Reference analogs: Dataset::SaveBinaryFile/LoadFromBinFile, gbdt.cpp:277
snapshot_freq, dataset_loader.cpp GetForcedBins, basic.py _data_from_pandas,
plotting.py, common.h:931 global_timer (the telemetry registry's timers).
"""
import json
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb


def _xy(rng, n=1200, f=6):
    X = rng.randn(n, f)
    y = (X[:, 0] > 0).astype(np.float64)
    return X, y


def test_binary_dataset_roundtrip(tmp_path, rng):
    X, y = _xy(rng)
    d = lgb.Dataset(X, label=y, weight=np.abs(rng.randn(len(y))) + 0.5)
    p = str(tmp_path / "train.bin.npz")
    d.save_binary(p)
    d2 = lgb.Dataset(p)
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1}
    b1 = lgb.train(dict(params), d, num_boost_round=3)
    b2 = lgb.train(dict(params), d2, num_boost_round=3)
    np.testing.assert_allclose(b1.predict(X[:100]), b2.predict(X[:100]))


def test_snapshot_freq_resume(tmp_path, rng):
    X, y = _xy(rng)
    out = str(tmp_path / "m.txt")
    lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1,
               "snapshot_freq": 2, "output_model": out},
              lgb.Dataset(X, label=y), num_boost_round=4,
              valid_sets=[lgb.Dataset(X[:100], label=y[:100])])
    snap = out + ".snapshot_iter_2"
    assert os.path.exists(snap)
    resumed = lgb.train({"objective": "binary", "num_leaves": 7,
                         "verbosity": -1}, lgb.Dataset(X, label=y),
                        num_boost_round=2, init_model=snap)
    assert resumed.inner.num_trees() == 4


def test_forced_bins(tmp_path, rng):
    X, y = _xy(rng)
    fb = str(tmp_path / "forced.json")
    with open(fb, "w") as f:
        json.dump([{"feature": 0, "bin_upper_bound": [-0.5, 0.5]}], f)
    ds = lgb.Dataset(X, label=y,
                     params={"forcedbins_filename": fb}).construct()
    ub = ds.bin_mappers[0].upper_bounds
    assert -0.5 in ub and 0.5 in ub


def test_pandas_dataframe_with_categoricals(rng):
    pd = pytest.importorskip("pandas")
    n = 900
    df = pd.DataFrame({
        "num": rng.randn(n),
        "cat": pd.Categorical(rng.choice(["x", "y", "z"], n)),
    })
    y = ((df["num"] > 0) & (df["cat"] == "x")).astype(float).values
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbosity": -1, "min_data_in_leaf": 5},
                    lgb.Dataset(df, label=y), num_boost_round=6)
    ds = lgb.Dataset(df, label=y).construct()
    from lightgbm_tpu.ops.binning import BIN_CATEGORICAL
    inner = ds.inner_feature_index(1)
    assert ds.bin_mappers[inner].bin_type == BIN_CATEGORICAL
    pred = bst.predict(lgb.basic._to_2d(df))
    assert ((pred > 0.5) == y).mean() > 0.95


def test_plotting_smoke(rng):
    mpl = pytest.importorskip("matplotlib")
    mpl.use("Agg")
    X, y = _xy(rng)
    res = {}
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbosity": -1, "metric": ["auc"]},
                    lgb.Dataset(X, label=y), num_boost_round=3,
                    valid_sets=[lgb.Dataset(X[:200], label=y[:200])],
                    callbacks=[lgb.record_evaluation(res)])
    assert lgb.plot_importance(bst) is not None
    assert lgb.plot_metric(res) is not None


def test_phase_timers(rng, monkeypatch):
    """The phase timers live in the telemetry registry, and the
    LIGHTGBM_TPU_TIMETAG report at the end of a job prints them."""
    from lightgbm_tpu.obs import telemetry
    from lightgbm_tpu.utils.log import Log
    X, y = _xy(rng, n=600)
    lines = []
    monkeypatch.setenv("LIGHTGBM_TPU_TIMETAG", "1")
    monkeypatch.setattr(Log, "info",
                        staticmethod(lambda m, *a: lines.append(m % a if a else m)))
    bst = lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1},
                    lgb.Dataset(X, label=y), num_boost_round=2,
                    valid_sets=[lgb.Dataset(X[:100], label=y[:100])])
    timers = bst.telemetry()["timers"]
    assert timers["train/iter"] > 0 and timers["train/booster_init"] > 0
    rep = "\n".join(lines)
    assert "train/iter" in rep and "train/booster_init" in rep


def test_native_parser_matches_python(tmp_path, rng):
    """native/parser.cpp via ctypes vs numpy (reference: src/io/parser.cpp
    + fast_double_parser). Skips when no compiler is available."""
    from lightgbm_tpu.io_native import parse_file

    X = rng.randn(500, 7)
    p = str(tmp_path / "t.tsv")
    np.savetxt(p, X, delimiter="\t", fmt="%.6g")
    out = parse_file(p)
    if out is None:
        pytest.skip("native parser unavailable (no g++)")
    M, fmt = out
    assert fmt == "tsv"
    np.testing.assert_allclose(M, np.genfromtxt(p, delimiter="\t"))


@pytest.mark.slow  # two full trainings; accuracy comparison, not a parity pin
def test_quantized_gradients_accuracy(rng):
    """int8 quantized-gradient histograms (LightGBM 4.x quantized training
    analog) must track the exact path's accuracy."""
    n = 20000
    X = rng.randn(n, 10)
    y = (X @ rng.randn(10) + 0.3 * rng.randn(n) > 0).astype(np.float64)
    base = {"objective": "binary", "num_leaves": 31, "verbosity": -1,
            "metric": ["auc"]}
    exact = lgb.train(dict(base), lgb.Dataset(X, label=y), num_boost_round=10)
    quant = lgb.train(dict(base, use_quantized_grad=True),
                      lgb.Dataset(X, label=y), num_boost_round=10)
    (_, _, auc_e, _), = exact.eval_train()
    (_, _, auc_q, _), = quant.eval_train()
    assert auc_q > auc_e - 0.01
