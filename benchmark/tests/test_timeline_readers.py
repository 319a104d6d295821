"""CPU tests of the readers PR 37 adds over the program's timeline records
(``package_import``, ``runtime_start``, ``fused_block``): each new metric on
hand-made facts and records, and a rehearsal-sized job on the CPU in which
the work the program recorded for a block equals what
``readers/traced_block.py`` walks out of the same trees of the model text.
By hand, like the others:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

from reference import model_text  # noqa: E402

NEW = ("import_s", "import_optional_s", "runtime_start_s", "first_blocks_s",
       "setup_unnamed_s", "row_visits_per_row", "partition_ns_per_row_visit",
       "hist_ns_per_row_feature")


def load(kind, name):
    spec = importlib.util.spec_from_file_location(
        "bench_%s_%s" % (kind, name), os.path.join(BENCH, kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def spec(name):
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        return json.load(f)


def read(name, facts):
    s = spec(name)
    return load("readers", s["reader"]).read(s["args"], facts)


def tree(parent, left, right):
    """A two-leaf tree as ``model_text.parse`` gives it."""
    return {"num_leaves": 2, "internal_count": np.array([parent]),
            "leaf_count": np.array([left, right]),
            "left_child": np.array([-1]), "right_child": np.array([-2])}


STUMP = {"num_leaves": 1, "internal_count": np.array([], dtype=np.int64),
         "leaf_count": np.array([1000]), "left_child": np.array([], dtype=np.int64),
         "right_child": np.array([], dtype=np.int64)}


@pytest.fixture
def registry():
    """The program's registry with a job's timeline in it. The process's own
    ``package_import`` and ``runtime_start`` records are set aside: reset()
    keeps them, and the readers take the first of a name."""
    from lightgbm_tpu.obs import telemetry
    telemetry.reset()
    kept = {k: telemetry._records.pop(k, None) for k in ("package_import", "runtime_start")}
    telemetry.record("package_import", import_s=9.5, core_s=5.0, sklearn_s=3.0,
                     serve_online_s=1.0, plotting_s=0.5, jax_preimported=True,
                     elapsed_s=16.75, runtime_start_s=7.25)
    telemetry.record("runtime_start", runtime_start_s=7.25, platform="tpu")
    telemetry.record("dataset_construct", total_s=8.0)
    telemetry.record("job_start", entry_to_first_dispatch_s=6.0, path="fused")
    for i, (visits, hist) in enumerate(((5000, 900), (5200, 1000), (5400, 1100))):
        telemetry.record("fused_block", index=i, first_iter=2 * i, iters=2, rows=1000,
                         dispatched_s=100.0 + 4 * i, wait_end_s=107.5 + 4 * i,
                         finalized_s=107.6 + 4 * i, row_visits=visits, hist_rows=hist,
                         splits=4)
    yield telemetry
    telemetry.reset()
    for k, v in kept.items():
        telemetry._records.pop(k, None)
        if v:
            telemetry._records[k] = v


def traced_facts(trees, iters=2, **more):
    return dict({"values": {"setup_s": 47.0}, "features": 28, "trace_iters": iters,
                 "trace_trees": trees, "peaks": {"hbm_bytes_per_s": 819e9},
                 "trace": {"by_scope": {"lgbtpu/partition": 5.2e-3,
                                        "lgbtpu/histogram": 2.8e-3}}}, **more)


# two trees grown on 1000 rows each: 1000 -> (800 -> 500 + 300) + 200, and
# 1000 -> 600 + 400: 2800 row visits, 200 + 300 + 400 smaller-child rows
BLOCK1 = [{"num_leaves": 3, "internal_count": np.array([1000, 800]),
           "leaf_count": np.array([500, 200, 300]),
           "left_child": np.array([1, -1]), "right_child": np.array([-2, -3])},
          tree(1000, 600, 400)]


def test_every_new_metric_has_its_file_and_its_entry():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-len(NEW):] == list(NEW)
    ends = {m["name"] for m in bench["end_to_end"]}
    for name in NEW:
        s = spec(name)
        assert {k: s[k] for k in ("name", "unit", "better", "source", "layer", "moves")} \
            == listed[name]
        assert "workloads" not in listed[name] and s["moves"] in ends
        assert os.path.exists(os.path.join(BENCH, "readers", s["reader"] + ".py"))


@pytest.mark.parametrize("name,want", [
    ("import_s", 9.5),
    ("import_optional_s", 4.5),
    ("runtime_start_s", 7.25),
    ("first_blocks_s", 7.5),                        # the FIRST block's wait
    ("setup_unnamed_s", 47.0 - 9.5 - 7.25 - 8.0 - 6.0 - 7.5),
    ("row_visits_per_row", 2800 / 2000),
    ("partition_ns_per_row_visit", 5.2e-3 * 1e9 / 2800),
    ("hist_ns_per_row_feature", 2.8e-3 * 1e9 / (900 * 28)),
])
def test_new_metrics_on_hand_made_records(registry, name, want):
    assert read(name, traced_facts(BLOCK1)) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("name", NEW[:5])
def test_record_metrics_are_left_out_on_a_commit_without_the_records(name):
    from lightgbm_tpu.obs import telemetry
    telemetry.reset()
    kept = {k: telemetry._records.pop(k, None) for k in ("package_import", "runtime_start")}
    try:
        assert read(name, traced_facts(BLOCK1)) is None
    finally:
        telemetry._records.update({k: v for k, v in kept.items() if v})


@pytest.mark.parametrize("name", NEW[-3:])
def test_block_metrics_read_the_traced_trees_alone(name):
    """No record of the program is asked: the counts are the model's own."""
    facts = traced_facts(BLOCK1)
    assert read(name, facts) is not None
    assert read(name, dict(facts, trace_trees=None, trace_iters=0)) is None    # no trace
    assert read(name, traced_facts([STUMP])) is None          # nothing was split
    # a tree that split nothing adds nothing to the walk
    assert read(name, traced_facts(BLOCK1 + [STUMP])) == read(name, facts)
    assert read(name, traced_facts(BLOCK1 + BLOCK1, iters=4)) == \
        pytest.approx(read(name, facts) * (1 if name == "row_visits_per_row" else 0.5))


def test_scope_metrics_need_the_scopes_seconds():
    facts = traced_facts(BLOCK1, trace={"by_scope": {}})
    assert read("partition_ns_per_row_visit", facts) is None
    assert read("hist_ns_per_row_feature", facts) is None
    assert read("row_visits_per_row", facts) == 1.4


def test_setup_unnamed_needs_every_part(registry):
    facts = traced_facts(BLOCK1)
    assert read("setup_unnamed_s", dict(facts, values={})) is None
    registry.clear_records("job_start")
    assert read("setup_unnamed_s", facts) is None


def test_partition_ns_and_partition_roofline_are_one_reading(registry):
    """2 x (F + 12) B / 819 GB/s over the ns a row visit is the roofline
    share: both count the same parents' rows over the same scope's seconds."""
    facts = traced_facts(BLOCK1)
    ns = read("partition_ns_per_row_visit", facts)
    share = read("partition_roofline", facts)
    assert share == pytest.approx(100.0 * 2 * (28 + 12) / 819e9 / (ns * 1e-9), rel=1e-12)
    hist = read("hist_ns_per_row_feature", facts)
    bytes_read = 900 * (28 + 8)
    written = 3 * 2 * 28 * 256 * 12             # two histograms a split
    assert read("hist_roofline", facts) == pytest.approx(
        100.0 * (bytes_read + written) / 819e9 / (hist * 1e-9 * 900 * 28), rel=1e-12)


# ------------------------------------------------------------ a rehearsal

@pytest.mark.parametrize("cell,rows,params", [
    ("higgs.train", 20000, {"num_leaves": 31}),
    ("expo_cat.train", 20000, {"num_leaves": 15}),
])
def test_the_programs_block_work_is_what_the_model_text_holds(cell, rows, params):
    """The job kind of the harness on a rehearsal-sized table: every
    fused_block record's row_visits and hist_rows equal, exactly, what
    ``readers/traced_block.work`` sums over the same trees of the model text,
    so the block metrics read what the program counted."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import telemetry
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == next(
        w["config"] for w in bench["workloads"] if w["name"] == cell))
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    cfg["shape"]["rows"] = rows
    cfg["params"].update(params, tpu_iter_block=3)
    data = load("datagen", cfg["datagen"]["kind"]).make(
        cfg["shape"], cfg["datagen"]["args"], 3_700_000_019)
    dataset = lgb.Dataset(data["X"], label=data["label"], group=data["group"],
                          params=cfg["params"])
    dataset.construct()
    telemetry.reset()
    job = load("jobs", "train").run({
        "params": cfg["params"], "mix": {"job": "train", "num_boost_round": 12},
        "dataset": dataset, "seconds": 1e9, "trace": False, "trace_dir": ""})
    header, trees = model_text.parse(job["booster"].model_to_string())
    records = telemetry.records("fused_block")
    assert [r["iters"] for r in records] == [3, 3, 3, 3] and len(trees) == 12
    for r in records:
        mine = trees[r["first_iter"]:r["first_iter"] + r["iters"]]
        walked = load("readers", "traced_block").work(mine)
        parent, smaller = walked["row_visits"], walked["hist_rows"]
        assert (r["row_visits"], r["hist_rows"]) == (parent, smaller)
        assert walked["root_rows"] == r["iters"] * r["rows"] == 3 * rows
        facts = {"trace_trees": mine, "trace_iters": 3, "features": cfg["shape"]["features"],
                 "trace": {"by_scope": {"lgbtpu/partition": 1.0, "lgbtpu/histogram": 1.0}}}
        assert read("row_visits_per_row", facts) == parent / (3 * rows)
        assert read("partition_ns_per_row_visit", facts) == 1e9 / parent
        assert read("hist_ns_per_row_feature", facts) == \
            1e9 / (smaller * cfg["shape"]["features"])
    assert read("first_blocks_s", {}) == pytest.approx(
        records[0]["wait_end_s"] - records[0]["dispatched_s"])
    assert read("import_s", {}) > 0 and read("runtime_start_s", {}) >= 0
