"""CPU tests of what the ``epsilon.train`` cell added to the harness: the plain
reference for the root's two children and the check that holds the program's
first tree to it. By hand, like the others:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""
import copy
import importlib.util
import json
import os
import sys
import types

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

from datagen import linear_decay  # noqa: E402
from reference import binary_children  # noqa: E402

ARGS = {"kind": "child_splits_binary", "gain_rtol": 1e-4, "min_children": 2}
PARAMS = {"min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 1e-3}


def _check():
    spec = importlib.util.spec_from_file_location(
        "bench_checks_child", os.path.join(BENCH, "checks", "child_splits_binary.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.run


def _table():
    """400 rows x 3 columns of 0 / 1, a bound at 0.5 each. Column 0 halves the
    rows (the root). In the left half the label IS column 1, and column 2
    copies column 1 (an exact tie); in the right half the label is column 2
    and column 1 alternates. Every split is known by hand: p = 0.5, hessian
    0.25 a row, a child of 100 + 100 rows split cleanly gains
    50^2 / 25 + 50^2 / 25 = 200."""
    x0 = np.repeat([0, 1], 200)
    half = np.tile(np.repeat([0, 1], 100), 2)           # 100 zeros, 100 ones, twice
    x1 = np.where(x0 == 0, half, np.tile([0, 1], 200))
    x2 = half
    X = np.stack([x0, x1, x2], axis=1).astype(np.float32)
    y = half.astype(np.float32)
    bounds = [np.array([0.5, np.inf])] * 3
    return X, y, bounds


def _context(tree):
    X, y, bounds = _table()
    binned = types.SimpleNamespace(
        bin_mappers=[types.SimpleNamespace(upper_bounds=b) for b in bounds])
    return {"params": PARAMS, "rows": len(y), "X": X, "label": y, "binned": binned,
            "trees": [tree]}


def _tree(left_feature=1):
    """The first tree as the model text gives it: node 0 the root, node 1 its
    left child, node 2 its right child, four leaves of 100 rows."""
    return {"num_leaves": 4,
            "split_feature": np.array([0, left_feature, 2]),
            "threshold": np.array([0.5, 0.5, 0.5]),
            "split_gain": np.array([0.0, 200.0, 200.0]),
            "left_child": np.array([1, -1, -3]), "right_child": np.array([2, -2, -4]),
            "internal_count": np.array([400, 200, 200]),
            "leaf_count": np.array([100, 100, 100, 100])}


def test_reference_finds_the_hand_made_children():
    X, y, bounds = _table()
    kids, probed = binary_children.child_splits(
        X, y, bounds, 0, 0, min_data_in_leaf=1, min_sum_hessian=1e-3,
        probe=[(0, 2, 0), (1, 1, 0)])
    # of two exact ties the lower column wins, as in binary_root
    assert kids[0] == {"rows": 200, "feature": 1, "bin": 0, "gain": 200.0, "left_rows": 100}
    assert kids[1] == {"rows": 200, "feature": 2, "bin": 0, "gain": 200.0, "left_rows": 100}
    assert probed[(0, 2, 0)] == (200.0, 100)             # the left child's tie
    assert probed[(1, 1, 0)] == (0.0, 100)               # column 1 says nothing on the right
    # the leaf minimums are the caller's: no split may leave under 150 rows
    kids, _ = binary_children.child_splits(X, y, bounds, 0, 0, min_data_in_leaf=150)
    assert kids[0]["feature"] is None and kids[1]["gain"] is None


def test_check_passes_the_true_tree_and_names_its_numbers():
    ok, detail = _check()(ARGS, _context(_tree()))
    assert ok, detail
    assert "left: 200 rows, feature 1 bin 0, gain 200 against numpy 200" in detail
    assert "left rows 100 against 100" in detail and "tie" not in detail


@pytest.mark.parametrize("field,node,value,says", [
    ("split_gain", 1, 200.0 * (1 + 5e-4), "off 0.0005"),     # over gain_rtol
    ("split_gain", 2, 200.0 * (1 - 5e-4), "off 0.0005"),
    ("leaf_count", 0, 99, "left rows 99 against 100"),       # one row gone left
    ("internal_count", 2, 199, "right: 199 rows, numpy 200"),
    ("split_feature", 2, 1, "its gain for the program's split 0"),  # no tie: gain 0 there
], ids=["gain_high", "gain_low", "left_rows", "child_rows", "other_feature"])
def test_check_fails_a_tampered_tree(field, node, value, says):
    tree = copy.deepcopy(_tree())
    tree[field] = tree[field].astype(np.float64 if field == "split_gain" else np.int64)
    tree[field][node] = value
    ok, detail = _check()(ARGS, _context(tree))
    assert not ok and says in detail, detail


def test_a_tie_inside_the_tolerance_passes_and_says_so():
    # column 2 copies column 1 in the left child: the program may take either
    ok, detail = _check()(ARGS, _context(_tree(left_feature=2)))
    assert ok, detail
    assert "tie: numpy's best is feature 1 bin 0, gain 200 against 200 here" in detail
    # a gain inside the tolerance of the tie's is still held to it
    tree = _tree(left_feature=2)
    tree["split_gain"] = np.array([0.0, 200.2, 200.0])
    ok, detail = _check()(ARGS, _context(tree))
    assert not ok and "tie" in detail


def test_a_child_left_a_leaf_is_counted():
    tree = _tree()
    tree.update(num_leaves=3, left_child=np.array([1, -1, 0]), right_child=np.array([-3, -2, 0]),
                split_feature=np.array([0, 1]), threshold=np.array([0.5, 0.5]),
                split_gain=np.array([0.0, 200.0]), internal_count=np.array([400, 200]),
                leaf_count=np.array([100, 100, 200]))
    for k in ("left_child", "right_child"):
        tree[k] = tree[k][:2]
    ok, detail = _check()(ARGS, _context(tree))
    assert not ok and "right: 200 rows, left a leaf" in detail
    assert "1 children of the root were split, want 2" in detail
    ok, detail = _check()(dict(ARGS, min_children=1), _context(tree))
    assert ok, detail


def test_decaying_weights_are_the_files_and_the_rows_the_seeds():
    cfg = json.load(open(os.path.join(BENCH, "configs", "epsilon-binary-255.json")))
    assert cfg["datagen"]["kind"] == "linear_decay"
    args = cfg["datagen"]["args"]
    w = linear_decay.weights(2000, args)
    assert w.dtype == np.float32 and np.sum(w.astype(np.float64) ** 2) == pytest.approx(1.0, abs=1e-6)
    share = np.sort(w.astype(np.float64) ** 2)[::-1]
    # power 0.5: the k-th strongest column holds 1 / (k H_2000) of the score
    assert share[0] == pytest.approx(0.1222, abs=1e-3) and share[:100].sum() == pytest.approx(0.634, abs=2e-3)
    assert 0.45 < np.mean(w > 0) < 0.55 and np.argmax(np.abs(w)) != 0      # signs and order drawn
    assert np.array_equal(w, linear_decay.weights(2000, args))               # from the file alone
    shape = {"rows": 70000, "features": 40}
    a, b, c = (linear_decay.make(shape, args, s) for s in (7, 7, 3300000011))
    assert a["X"].dtype == np.float32 and a["X"].shape == (70000, 40) and a["group"] is None
    assert np.array_equal(a["X"], b["X"]) and np.array_equal(a["label"], b["label"])
    assert not np.array_equal(a["X"], c["X"]) and set(np.unique(a["label"])) == {0.0, 1.0}
    assert abs(a["label"].mean() - 0.5) < 0.01
    # the label is the sign of the file's score but for the noise
    agree = np.mean((a["X"] @ linear_decay.weights(40, args) > 0) == (a["label"] > 0))
    assert 0.85 < agree < 0.95
    with pytest.raises(ValueError):
        linear_decay.make(shape, dict(args, label="graded"), 7)


def test_epsilon_configuration_lists_the_new_check():
    cfg = json.load(open(os.path.join(BENCH, "configs", "epsilon-binary-255.json")))
    kinds = [c["kind"] for c in cfg["checks"]]
    assert kinds == ["tree_census", "routed_counts", "predict", "root_split_binary",
                     "child_splits_binary", "quality_floor"]
    assert cfg["shape"] == {"rows": 400000, "features": 2000, "source_rows": 400000}
    assert cfg["params"]["min_data_in_leaf"] == 1 and cfg["reduced"] == ["iterations"]
    assert not any(k.startswith("tpu_") for k in cfg["params"])
