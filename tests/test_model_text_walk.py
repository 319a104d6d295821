"""Tier-1 twin of ``benchmark/tests/test_model_text_format.py`` (owed since
PR 34): on each of its six tables (categorical sets of many and of one, the
shared other bin, NaNs that go both ways, zero as missing, three classes) the
program's ``predict`` from the trained model == its ``predict`` from the text
loaded again == the benchmark's plain walk of that text
(``benchmark/reference/model_text.py``), on the training rows, on rows with
values training never saw, and on those rows often enough to take the device
predictor. A seventh case holds a category of -2 (the device predictor's
padding value until PR 35) to the same three: the passing twin of that file's
``xfail(strict=True)``. The tables are the benchmark's own (``make_table``,
imported by path as ``tests/test_efb_reference.py`` imports the EFB
reference)."""
import importlib.util
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")


def _format_tests():
    spec = importlib.util.spec_from_file_location(
        "bench_tests_model_text_format",
        os.path.join(BENCH, "tests", "test_model_text_format.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)      # puts benchmark/ on sys.path itself
    return mod


fmt = _format_tests()
CASES = fmt.TABLES + ("category_minus_two",)


@pytest.mark.parametrize("case", CASES)
def test_predict_is_the_round_trips_and_the_plain_walks(case):
    kind = "many_vs_many" if case == "category_minus_two" else case
    X, label, params, cat, kinds = fmt.make_table(kind)
    ds = lgb.Dataset(X, label=label, params=params,
                     categorical_feature=cat or "auto")
    bst = lgb.train(params, ds, num_boost_round=fmt.ROUNDS)
    text = bst.model_to_string()
    header, trees = fmt.model_text.parse(text)
    assert {int(d) for t in trees for d in t["decision_type"]} == kinds
    loaded = lgb.Booster(model_str=text)
    device_rows = bst.inner.DEVICE_PREDICT_MIN_ROWS
    if case == "category_minus_two":
        probe = X[:device_rows].copy()
        probe[:, 0] = -2.0
    else:
        probe = fmt.probe_rows(X, kind)
    often = np.ascontiguousarray(np.tile(probe, (-(-device_rows // len(probe)), 1)))
    assert len(probe[:64]) < device_rows <= len(often) and len(X) >= device_rows
    for rows in (X, probe[:64], often):
        theirs = np.asarray(bst.predict(rows, raw_score=True), np.float64)
        again = np.asarray(loaded.predict(rows, raw_score=True), np.float64)
        mine = fmt.model_text.raw_score(header, trees, rows)
        assert np.array_equal(theirs, again)
        assert mine.shape == theirs.shape
        assert np.abs(mine - theirs).max() < 1e-5, len(rows)
    for t in trees:
        assert np.array_equal(fmt.model_text.leaf_counts(t, X), t["leaf_count"])
