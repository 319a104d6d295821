"""The job's timeline on the program's clock: the ``package_import`` and
``runtime_start`` records a process writes once, one ``fused_block`` record
a finalized block with the work of its trees, the ``tree/*`` counters of
both loops, and what none of it may cost: a transfer, a sync, a bit of a
model.
"""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import fused, obs, runtime
from lightgbm_tpu.obs import Telemetry, telemetry
from lightgbm_tpu.tree import Tree

PARAMS = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
          "verbosity": -1}


def _data(n=400, f=6, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f)
    return X, (X[:, 0] + X[:, 1] > 1).astype(np.float64)


def work_of(trees):
    """(row visits, histogram rows) of some trees by a plain loop over
    their nodes: the reference ``Tree.work`` is held to."""
    visits = hist = 0
    for t in trees:
        if t.num_leaves < 2:
            continue

        def rows(child):
            return int(t.internal_count[child] if child >= 0
                       else t.leaf_count[~child])
        for r in range(t.num_leaves - 1):
            visits += int(t.internal_count[r])
            hist += min(rows(int(t.left_child[r])), rows(int(t.right_child[r])))
    return visits, hist


# ------------------------------------------------------------ the registry

def test_a_kept_list_holds_its_first_record_and_the_newest():
    t = Telemetry()
    for i in range(10):
        t.record("blocks", keep=4, index=i)
    assert [r["index"] for r in t.records("blocks")] == [0, 7, 8, 9]
    t.clear_records("blocks")
    assert t.records("blocks") == []
    t.clear_records("never_written")        # nothing to clear is no error


def test_reset_keeps_the_records_of_the_process():
    t = Telemetry()
    t.record("package_import", import_s=1.0)
    t.record("runtime_start", runtime_start_s=2.0)
    t.record("job_start", path="fused")
    t.count("c")
    t.reset()
    snap = t.snapshot()
    assert set(snap["records"]) == set(obs.PROCESS_RECORDS)
    assert snap["counters"] == {}


# ------------------------------------------------------------- the startup

_GROUPS = ("core_s", "serve_online_s", "plotting_s", "sklearn_s")


def test_package_import_is_written_once_and_its_parts_sum():
    telemetry.reset()                   # whatever ran before: it is kept
    rec, = telemetry.records("package_import")
    assert sum(rec[g] for g in _GROUPS) == pytest.approx(rec["import_s"],
                                                         abs=1e-9)
    assert all(rec[g] >= 0.0 for g in _GROUPS) and rec["import_s"] > 0
    assert rec["jax_preimported"] in (True, False)
    # ops/split.py asks runtime.start() while the package imports, ahead of
    # the device scalars of its default arguments: the backend's coming up
    # is the runtime_start record's and is taken off the import's
    started, = telemetry.records("runtime_start")
    assert rec["entry_s"] <= started["asked_s"] <= rec["entry_s"] + rec["elapsed_s"]
    assert rec["runtime_start_s"] == started["runtime_start_s"]
    assert rec["elapsed_s"] == pytest.approx(
        rec["import_s"] + rec["runtime_start_s"], abs=1e-9)
    assert started["backend_was_up"] in (True, False)
    assert rec["process_age_s"] is None or rec["process_age_s"] >= 0.0


def test_package_import_parts_from_marks(monkeypatch):
    reg = Telemetry()
    monkeypatch.setattr(obs, "telemetry", reg)
    marks = [("entry", 10.0), ("core", 12.0), ("serve_online", 12.5),
             ("plotting", 12.75), ("core", 13.0), ("sklearn", 16.0)]
    # the backend came up inside the first core group, for one second
    reg.record("runtime_start", asked_s=10.5, runtime_start_s=1.0)
    obs.record_package_import(marks, jax_preimported=False)
    rec, = reg.records("package_import")
    assert (rec["core_s"], rec["serve_online_s"], rec["plotting_s"],
            rec["sklearn_s"]) == (1.25, 0.5, 0.25, 3.0)
    assert (rec["import_s"], rec["runtime_start_s"], rec["elapsed_s"]) == \
        (5.0, 1.0, 6.0)
    assert rec["entry_s"] == 10.0 and rec["jax_preimported"] is False
    assert "import/total" not in reg.snapshot()["timers"]
    # a backend asked before the package's entry, and one never asked
    obs.record_package_import([("entry", 11.0), ("core", 12.0)], True)
    reg.clear_records("runtime_start")
    obs.record_package_import([("entry", 10.0), ("core", 12.0)], True)
    assert [(r["runtime_start_s"], r["import_s"], r["core_s"])
            for r in reg.records("package_import")[1:]] \
        == [(0.0, 1.0, 1.0), (0.0, 2.0, 2.0)]


@pytest.mark.parametrize("first", ["start", "on_tpu", "device_identity"])
def test_runtime_start_is_written_once_whichever_asks_first(first, monkeypatch):
    reg = Telemetry()
    monkeypatch.setattr(runtime, "telemetry", reg)
    monkeypatch.setattr(runtime, "_started", False)
    second = "device_identity" if first == "on_tpu" else "on_tpu"
    getattr(runtime, first)()
    rec, = reg.records("runtime_start")
    # the process's backend has been up since the package's import
    assert rec["backend_was_up"] is True
    ident = runtime.device_identity()
    assert (rec["platform"], rec["device_kind"], rec["device_count"]) == \
        (ident["platform"], ident["kind"], ident["count"])
    assert rec["runtime_start_s"] >= 0.0
    getattr(runtime, second)(), getattr(runtime, first)(), runtime.start()
    assert len(reg.records("runtime_start")) == 1
    assert runtime.on_tpu() is False            # the tests run on the CPU
    assert obs.PHASES["lgbtpu/runtime_start"] == ("host", "runtime",
                                                  "runtime/start")


# --------------------------------------------------------------- the loop

def _rank_data(n=400, f=6, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f)
    return X, np.floor(3.99 * X[:, 0]), [20] * (n // 20)


def _coded(X):
    rng = np.random.RandomState(4)
    X = X.copy()
    X[:, 0], X[:, 1] = rng.randint(0, 3, len(X)), rng.randint(0, 12, len(X))
    return X


def _block_cases():
    X, y = _data(n=600)
    Xr, yr, group = _rank_data()
    return {
        "binary": ({"objective": "binary"}, X, {"label": y}, 7),
        "lambdarank": ({"objective": "lambdarank"}, Xr,
                       {"label": yr, "group": group}, 7),
        "categorical": ({"objective": "binary", "min_data_per_group": 5},
                        _coded(X), {"label": y, "categorical_feature": [0, 1]},
                        7),
        "three_classes": ({"objective": "multiclass", "num_class": 3}, X,
                          {"label": np.floor(X[:, 0] * 3)}, 7),
        # a constant label: the first block's trees split nothing, the job
        # stops there and the block in flight behind it is dropped
        "all_constant_stop": ({"objective": "regression"}, X,
                              {"label": np.ones(len(X))}, 9),
    }


@pytest.mark.parametrize("case", sorted(_block_cases()))
def test_one_fused_block_record_a_finalized_block(case):
    params, X, kw, rounds = _block_cases()[case]
    telemetry.reset()
    bst = lgb.train(dict(PARAMS, tpu_iter_block=3, **params),
                    lgb.Dataset(X, **kw), num_boost_round=rounds)
    recs = telemetry.records("fused_block")
    g = bst.inner
    K = g.num_tree_per_iteration
    assert [r["index"] for r in recs] == list(range(len(recs))) and recs
    assert sum(r["iters"] for r in recs) == g.iter_
    # every dispatched block is finalized but the one a stop drops
    assert len(recs) == telemetry.counter("fused/blocks_dispatched") - \
        (case == "all_constant_stop")
    at = 0
    for r in recs:
        assert r["first_iter"] == at and r["rows"] == len(X)
        assert r["dispatched_s"] <= r["wait_end_s"] <= r["finalized_s"]
        trees = g.models[K * at:K * (at + r["iters"])]
        assert (r["row_visits"], r["hist_rows"]) == work_of(trees)
        assert r["row_visits"] == sum(
            int(t.internal_count[:t.num_leaves - 1].sum()) for t in trees
            if t.num_leaves > 1)
        assert r["splits"] == sum(t.num_leaves - 1 for t in trees)
        assert r["leaves"] == sum(t.num_leaves for t in trees)
        assert r["splits_categorical"] == sum(t.num_cat for t in trees)
        at += r["iters"]
    # blocks are dispatched in order, each before the one before it is waited for
    assert all(a["dispatched_s"] <= b["dispatched_s"] and
               a["wait_end_s"] <= b["wait_end_s"]
               for a, b in zip(recs, recs[1:]))
    c = telemetry.snapshot()["counters"]
    assert c["tree/splits"] == sum(r["splits"] for r in recs)
    assert c["tree/leaves"] == sum(r["leaves"] for r in recs)
    if case == "all_constant_stop":
        assert len(recs) == 1 and recs[0]["splits"] == 0 == recs[0]["row_visits"]
    elif case == "categorical":
        assert sum(r["splits_categorical"] for r in recs) > 0
    else:
        assert all(r["row_visits"] >= K * r["iters"] * len(X) for r in recs)


def test_the_eager_loop_counts_the_same_growth():
    """One function counts ``tree/*`` for both loops; the work of the trees
    is the fused loop's block records' alone (nothing reads a counter of
    it), so the eager loop never asks ``Tree.work``."""
    X, y = _data(n=500, seed=3)
    ds = lgb.Dataset(X, label=y)
    telemetry.reset()
    fused_bst = lgb.train(dict(PARAMS), ds, num_boost_round=4)
    fused_counts = dict(telemetry.snapshot()["counters"])
    recs = telemetry.records("fused_block")
    assert (sum(r["row_visits"] for r in recs),
            sum(r["hist_rows"] for r in recs)) == work_of(fused_bst.inner.models)
    telemetry.reset()
    eager_bst = lgb.train(dict(PARAMS), ds, num_boost_round=4, valid_sets=[ds])
    eager_counts = telemetry.snapshot()["counters"]
    assert telemetry.records("fused_block") == []
    assert work_of(eager_bst.inner.models) == work_of(fused_bst.inner.models)
    tree_counters = {k: v for k, v in fused_counts.items()
                     if k.startswith("tree/")}
    assert tree_counters == {k: v for k, v in eager_counts.items()
                             if k.startswith("tree/")}
    assert set(tree_counters) == {"tree/" + n for n in (
        "trees", "splits", "splits_categorical", "leaves")}


def test_a_job_keeps_its_first_block_and_its_newest(monkeypatch):
    X, y = _data(seed=2)
    ds = lgb.Dataset(X, label=y)
    monkeypatch.setattr(fused, "_BLOCK_RECORDS", 4)
    telemetry.reset()
    lgb.train(dict(PARAMS, tpu_iter_block=1), ds, num_boost_round=9)
    recs = telemetry.records("fused_block")
    assert [r["index"] for r in recs] == [0, 6, 7, 8]
    assert [r["first_iter"] for r in recs] == [0, 6, 7, 8]
    # a new lgb.train call starts a new list
    lgb.train(dict(PARAMS, tpu_iter_block=2), ds, num_boost_round=4)
    assert [(r["index"], r["iters"]) for r in telemetry.records("fused_block")] \
        == [(0, 2), (1, 2)]
    assert fused._BLOCK_RECORDS == 4 and fused.FusedTrainer is not None


def test_a_block_costs_the_transfers_and_syncs_it_did(monkeypatch):
    """One forced read and one fetch of the logs a finalized block, as
    before the timeline: the records come from what the host already holds."""
    import jax
    X, y = _data(seed=5)
    ds = lgb.Dataset(X, label=y)
    ds.construct(dict(PARAMS))
    lgb.train(dict(PARAMS, tpu_iter_block=2), ds, num_boost_round=2)  # compiled
    calls = {"sync": 0, "device_get": 0}
    real_sync, real_get = fused.sync, jax.device_get

    def counting_sync(x):
        calls["sync"] += 1
        return real_sync(x)

    def counting_get(x):
        calls["device_get"] += 1
        return real_get(x)
    monkeypatch.setattr(fused, "sync", counting_sync)
    telemetry.reset()
    monkeypatch.setattr(jax, "device_get", counting_get)
    lgb.train(dict(PARAMS, tpu_iter_block=2), ds, num_boost_round=6)
    monkeypatch.undo()
    blocks = len(telemetry.records("fused_block"))
    assert blocks == 3
    # the forced read is itself one device_get of one element
    assert calls == {"sync": blocks, "device_get": 2 * blocks}


def test_the_timeline_leaves_every_model_bit_as_it_was(monkeypatch):
    """The same job with the block records and the work counters taken out
    gives the same model text."""
    X, y = _data(n=300, seed=4)
    params = dict(PARAMS, tpu_iter_block=2)
    with_it = lgb.train(dict(params), lgb.Dataset(X, label=y), num_boost_round=5)
    monkeypatch.setattr(fused, "count_trees", lambda trees: {})
    monkeypatch.setattr(fused.FusedTrainer, "_record_block",
                        lambda self, *a: None)
    without = lgb.train(dict(params), lgb.Dataset(X, label=y), num_boost_round=5)
    assert with_it.model_to_string() == without.model_to_string()
    np.testing.assert_array_equal(with_it.predict(X), without.predict(X))


def test_the_unnamed_stretches_of_a_block_have_their_phases():
    X, y = _data(seed=6)
    telemetry.reset()
    lgb.train(dict(PARAMS, tpu_iter_block=2), lgb.Dataset(X, label=y),
              num_boost_round=6)
    snap = telemetry.snapshot()
    blocks = snap["counters"]["fused/blocks_dispatched"]
    for name in ("lgbtpu/fused_args", "lgbtpu/fused_after_call"):
        kind, layer, timer = obs.PHASES[name]
        assert (kind, layer) == ("host", "booster")
        assert snap["timer_calls"][timer] == blocks and snap["timers"][timer] > 0
    assert not [k for k in list(snap["counters"]) + list(snap["gauges"])
                if k.startswith(("learner/", "traffic/"))]


def test_a_wrap_of_train_block_holds_the_programs_phases_inside(monkeypatch):
    """The benchmark wraps ``GBDT.train_block`` in an annotation of its own
    and reads an idle gap by the innermost annotation around it: the
    program's ``lgbtpu/train_block`` opens INSIDE the method, so that it and
    every ``lgbtpu/fused_*`` phase of the call lie inside such a wrap, and a
    moment between two of them is still under a name of the program's."""
    from lightgbm_tpu.boosting import GBDT
    from lightgbm_tpu.obs_trace import tracer
    tracer.clear()
    wraps, inner = [], GBDT.train_block

    def wrapped(gbdt, k):
        t0 = obs.monotonic()
        stop = inner(gbdt, k)
        wraps.append((t0, obs.monotonic()))
        return stop
    monkeypatch.setattr(GBDT, "train_block", wrapped)
    X, y = _data(seed=7)
    telemetry.reset()
    lgb.train(dict(PARAMS, trace_spans="on", tpu_iter_block=2),
              lgb.Dataset(X, label=y), num_boost_round=6)
    spans = tracer.events()
    tracer.configure("off")         # process-global, like verbosity
    tracer.clear()
    blocks = [sp for sp in spans if sp.name == "lgbtpu/train_block"]
    assert len(blocks) == len(wraps) == 3
    for (t0, t1), b in zip(wraps, blocks):
        assert t0 <= b.t0 and b.t0 + b.dur <= t1
        inside = [sp for sp in spans if sp.name.startswith("lgbtpu/fused_")
                  and t0 <= sp.t0 <= t1]
        assert {"lgbtpu/fused_block_fn", "lgbtpu/fused_args",
                "lgbtpu/fused_dispatch", "lgbtpu/fused_after_call"} \
            <= {sp.name for sp in inside}
        assert all(b.t0 <= sp.t0 and sp.t0 + sp.dur <= b.t0 + b.dur
                   for sp in inside)
    assert telemetry.snapshot()["timer_calls"]["train/block"] == 3


# ------------------------------------------------------------------ a tree

def test_tree_work_against_the_plain_loop():
    assert Tree(1).work() == {"row_visits": 0, "hist_rows": 0}
    X, y = _data(n=3000, f=8, seed=9)
    for leaves in (2, 3, 31, 127):
        bst = lgb.train(dict(PARAMS, num_leaves=leaves, min_data_in_leaf=3),
                        lgb.Dataset(X, label=y), num_boost_round=3)
        for t in bst.inner.models:
            w = t.work()
            assert (w["row_visits"], w["hist_rows"]) == work_of([t])
    # a chain, the deepest a tree of that many leaves can be: node r's right
    # child is node r + 1
    n = 40
    t = Tree(n + 1)
    t.left_child[:n] = ~np.arange(n)
    t.right_child[:n] = np.arange(1, n + 1)
    t.right_child[n - 1] = ~n
    t.internal_count[:n] = np.arange(n + 1, 1, -1)
    t.leaf_count[:] = 1
    assert t.work() == {"row_visits": int(t.internal_count.sum()),
                        "hist_rows": n}
