"""Exclusive Feature Bundling, wired end to end.

Reference: Dataset::FindGroups / FastFeatureBundling
(src/io/dataset.cpp:100,239) + FeatureGroup offsets
(include/LightGBM/feature_group.h:25) + FixHistogram (dataset.h:503).
"""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.dataset import construct_dataset

sp = pytest.importorskip("scipy.sparse")


def _onehot_blocks(rng, n, n_vars=6, card=12):
    blocks, w = [], []
    for _ in range(n_vars):
        ids = rng.randint(0, card, n)
        blocks.append(sp.csr_matrix((np.ones(n), (np.arange(n), ids)),
                                    shape=(n, card)))
        w.append(rng.randn(card))
    X = sp.hstack(blocks).tocsr()
    y = (np.asarray(X @ np.concatenate(w)).ravel()
         + 0.2 * rng.randn(n) > 0).astype(np.float64)
    return X, y


def test_bundles_shrink_columns(rng):
    X, y = _onehot_blocks(rng, 3000)
    cfg = Config.from_params({"objective": "binary", "verbosity": -1})
    ds = construct_dataset(X, cfg, label=y)
    assert ds.num_features == 72
    # mutually exclusive one-hot groups collapse to ~n_vars columns
    assert ds.num_groups <= 10
    assert ds.binned.shape == (3000, ds.num_groups)
    # every row of a one-hot block hits exactly one non-default slot
    maps = ds.bundle_maps()
    assert maps["put"].shape[0] == ds.num_features


@pytest.mark.slow
def test_bundled_training_matches_unbundled(rng):
    X, y = _onehot_blocks(rng, 4000)
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "metric": ["auc"], "min_data_in_leaf": 5}
    b1 = lgb.train(dict(params), lgb.Dataset(X, label=y), num_boost_round=8)
    p2 = dict(params)
    p2["enable_bundle"] = False
    b2 = lgb.train(p2, lgb.Dataset(np.asarray(X.todense()), label=y),
                   num_boost_round=8)
    (_, _, auc1, _), = b1.eval_train()
    (_, _, auc2, _), = b2.eval_train()
    assert auc1 > 0.8
    # same splits are available either way; allow tiny numeric divergence
    assert abs(auc1 - auc2) < 0.02
    Xd = np.asarray(X.todense())
    pr1, pr2 = b1.predict(Xd[:300]), b2.predict(Xd[:300])
    assert np.corrcoef(pr1, pr2)[0, 1] > 0.98


def test_sparse_input_binning_matches_dense(rng):
    X, y = _onehot_blocks(rng, 2000)
    cfg = Config.from_params({"objective": "binary", "verbosity": -1})
    ds_sp = construct_dataset(X, cfg, label=y)
    ds_dn = construct_dataset(np.asarray(X.todense()), cfg, label=y)
    assert ds_sp.num_groups == ds_dn.num_groups
    np.testing.assert_array_equal(ds_sp.binned, ds_dn.binned)


@pytest.mark.slow
def test_valid_set_shares_bundling(rng):
    X, y = _onehot_blocks(rng, 3000)
    Xtr, ytr = X[:2000], y[:2000]
    Xva, yva = X[2000:], y[2000:]
    dtr = lgb.Dataset(Xtr, label=ytr)
    dva = lgb.Dataset(Xva, label=yva, reference=dtr)
    res = {}
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "verbosity": -1, "metric": ["binary_logloss"]},
                    dtr, num_boost_round=8, valid_sets=[dva],
                    valid_names=["va"],
                    callbacks=[lgb.record_evaluation(res)])
    # valid-set score tracking ran on the bundled matrix and is consistent
    # with raw-value prediction
    final_ll = res["va"]["binary_logloss"][-1]
    pred = bst.predict(np.asarray(Xva.todense()))
    eps = 1e-7
    ll = -np.mean(yva * np.log(pred + eps) + (1 - yva) * np.log(1 - pred + eps))
    assert abs(ll - final_ll) < 1e-3


# ------------------------------------- the per-feature view's two forms (PR 38)

def _mixed_table(rng, n=3000):
    """One-hot blocks, a block of exclusive sparse columns of several
    levels each (bundled members of more than one own slot) and two dense
    numeric columns that stay alone in theirs."""
    X, y = _onehot_blocks(rng, n, n_vars=4, card=9)
    ids = rng.randint(0, 6, n)
    levels = sp.csr_matrix((rng.randint(1, 8, n).astype(np.float64),
                            (np.arange(n), ids)), shape=(n, 6))
    dense = rng.randn(n, 2)
    X = sp.hstack([X, levels, sp.csr_matrix(dense)]).tocsr()
    y = ((y > 0) ^ (dense[:, 0] > 1.0)).astype(np.float64)
    return X, y


@pytest.mark.parametrize("case", ["serial", "forced_splits", "data", "voting"])
def test_both_forms_of_the_view_train_the_same_model(rng, case, tmp_path,
                                                     monkeypatch):
    """A bundled CSR job trains the SAME model text whether its per-feature
    view places runs or gathers every (feature, bin): the form follows
    ``dataset.VIEW_SEL_MAX_BYTES`` alone, which the test moves, and every
    caller of the view (root and child pairs, the forced-split search, the
    mesh learners' global and local totals) takes whichever was chosen."""
    import json
    import jax
    from lightgbm_tpu import dataset as D
    from lightgbm_tpu.obs import telemetry
    X, y = _mixed_table(rng)
    params = {"objective": "binary", "num_leaves": 31, "verbosity": -1,
              "min_data_in_leaf": 5, "tpu_part_chunk": 256,
              "tpu_hist_chunk": 256}
    if case == "forced_splits":
        forced = tmp_path / "forced.json"
        forced.write_text(json.dumps({
            "feature": 43, "threshold": 0.0,
            "left": {"feature": 3, "threshold": 0.5},
            "right": {"feature": 38, "threshold": 2.5}}))
        params["forcedsplits_filename"] = str(forced)
    elif case in ("data", "voting"):
        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device CPU mesh")
        params.update(tree_learner=case, top_k=8)
    texts = {}
    for form, limit in (("runs", D.VIEW_SEL_MAX_BYTES), ("gather", 0)):
        monkeypatch.setattr(D, "VIEW_SEL_MAX_BYTES", limit)
        telemetry.reset()
        bst = lgb.train(dict(params), lgb.Dataset(X, label=y),
                        num_boost_round=4)
        lrn = bst.inner.learner
        assert lrn.bundle_view.form == form
        assert lrn.bundle_view.alone == 2 and lrn.bundle_view.width == 8
        assert ("sel" in lrn.bundle, "proj" in lrn.bundle) == \
            (form == "runs", form == "gather")
        assert telemetry.records("learner_path")[-1]["efb_view"] == form
        text = bst.model_to_string()
        texts[form] = text[text.index("Tree=0"):]
    assert texts["runs"] == texts["gather"]
    assert texts["runs"].count("Tree=") == 4
    if case == "forced_splits":
        assert "split_feature=43 3 38" in texts["runs"]


def test_learner_path_says_which_form_the_view_took(rng, monkeypatch):
    """One ``learner_path`` record a job names the view's form, the features
    alone in their column and those that share one, and what the selection
    matrix and its product hold; a table over the size limit keeps the
    gather and says so; a dense table says nothing of a view."""
    from lightgbm_tpu import dataset as D
    from lightgbm_tpu.obs import telemetry
    X, y = _mixed_table(rng, n=1500)
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1}

    def record(data, limit):
        monkeypatch.setattr(D, "VIEW_SEL_MAX_BYTES", limit)
        telemetry.reset()
        bst = lgb.train(dict(params), lgb.Dataset(data, label=y),
                        num_boost_round=1)
        (rec,) = telemetry.records("learner_path")
        return bst.inner.learner, rec

    lrn, rec = record(X, D.VIEW_SEL_MAX_BYTES)
    f, g = lrn.dataset.num_features, lrn.dataset.num_groups
    slots = int(lrn.dataset.group_num_bins().max())
    assert (rec["efb_view"], rec["efb_alone"], rec["efb_bundled"]) == \
        ("runs", 2, f - 2)
    # the bf16 matrix (slots x F x widest bundled feature's 8 bins) and the
    # f32 product of three terms x three channels x G columns with it
    assert rec["efb_sel_bytes"] == 2 * slots * f * 8 + 4 * 9 * g * f * 8
    assert lrn.bundle["sel"].shape == (slots, f * 8)
    assert rec["efb_sel_bytes"] <= D.VIEW_SEL_MAX_BYTES
    _, rec = record(X, rec["efb_sel_bytes"] - 1)
    assert (rec["efb_view"], rec["efb_alone"], rec["efb_bundled"],
            rec["efb_sel_bytes"]) == ("gather", 2, f - 2, 0)
    lrn, rec = record(rng.randn(1500, 5), D.VIEW_SEL_MAX_BYTES)
    assert lrn.bundle is None and lrn.bundle_view is None
    assert not [k for k in rec if k.startswith("efb_")]
