"""CPU tests of the readers that read what the program records about itself
(``program_record``) and the idle gaps of the traced call
(``idle_gap_share``), on synthetic facts and records.

Run by hand (the repo's tier-1 command collects tests/ only):

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""
import importlib.util
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]


def reader(kind):
    path = os.path.join(BENCH, "readers", kind + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + kind, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_args(name):
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        return json.load(f)["args"]


@pytest.fixture
def records():
    """The program's registry with two jobs and two constructions in it."""
    from lightgbm_tpu.obs import telemetry
    telemetry.reset()
    for whole, compile_s, init in ((9.0, 3.0, 4.0), (5.5, 1.25, 2.5)):
        telemetry.record("job_start", path="fused",
                         entry_to_first_dispatch_s=whole, booster_init_s=init,
                         trace_s=1.0, lower_s=0.5, compile_or_load_s=compile_s)
    for copy_s in (3.75, 0.5):
        telemetry.record("dataset_construct", total_s=8.0, copy_s=copy_s)
    yield telemetry
    telemetry.reset()


@pytest.mark.parametrize("name,want", [
    ("job_start_direct_s", 5.5 - 1.25),     # the LAST job, less compile
    ("job_init_s", 2.5),
    ("job_trace_lower_s", 1.5),
    ("construct_copy_s", 3.75),             # the FIRST construction
])
def test_program_record_metrics(records, name, want):
    assert reader("program_record").read(metric_args(name), {}) == \
        pytest.approx(want)


@pytest.mark.parametrize("args", [
    {"record": "no_such_record", "add": ["x"]},                 # none written
    {"record": "job_start", "which": "last", "add": ["no_such_field"]},
    {"record": "job_start", "add": ["path"]},                   # not a number
])
def test_program_record_is_none_where_there_is_nothing_to_read(records, args):
    assert reader("program_record").read(args, {}) is None


def test_program_record_is_none_on_an_empty_registry():
    from lightgbm_tpu.obs import telemetry
    telemetry.reset()
    read = reader("program_record").read
    assert read(metric_args("job_start_direct_s"), {}) is None
    assert read(metric_args("construct_copy_s"), {}) is None


GAP_ARGS = {"unnamed_prefixes": ["bench/"], "unnamed": ["(no lgbtpu annotation)"],
            "left_out": ["(gaps too short to attribute)"]}


@pytest.mark.parametrize("gaps,want", [
    # the ledger's higgs.train line of PR 24: three quarters unnamed
    ({"bench/train_block": 15.6e-3, "lgbtpu/fused_dispatch": 3.2e-3,
      "lgbtpu/fused_device_wait": 1.8e-3,
      "(gaps too short to attribute)": 23.8e-3}, 100 * 15.6 / 20.6),
    # every gap under a program span
    ({"lgbtpu/fused_host_trees": 9e-3, "lgbtpu/fused_commit": 1e-3}, 0.0),
    # under no annotation at all counts as unnamed too
    ({"(no lgbtpu annotation)": 1e-3, "lgbtpu/fused_flush": 3e-3}, 25.0),
])
def test_idle_gap_share(gaps, want):
    facts = {"trace": {"idle_gaps": gaps}}
    assert reader("idle_gap_share").read(GAP_ARGS, facts) == pytest.approx(want)
    assert reader("idle_gap_share").read(
        metric_args("idle_unnamed_share"), facts) == pytest.approx(want)


@pytest.mark.parametrize("facts", [
    {"trace": None},                                        # --trace 0
    {},
    {"trace": {"idle_gaps": {}}},                           # no gap at all
    {"trace": {"idle_gaps": {"(gaps too short to attribute)": 0.02}}},
])
def test_idle_gap_share_is_none_where_there_is_nothing_to_read(facts):
    assert reader("idle_gap_share").read(GAP_ARGS, facts) is None


def test_every_new_metric_file_agrees_with_benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in ("objective_ms_per_iter", "route_ms_per_iter",
                 "rank_move_ms_per_iter", "rank_pairs_ms_per_iter",
                 "job_start_direct_s", "job_init_s", "job_trace_lower_s",
                 "construct_copy_s", "idle_unnamed_share"):
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            spec = json.load(f)
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert spec[key] == listed[name][key], (name, key)
        assert os.path.exists(os.path.join(BENCH, "readers", spec["reader"] + ".py"))


def test_scope_metrics_name_only_phases_the_program_has():
    from lightgbm_tpu.obs import PHASES
    for name in ("objective_ms_per_iter", "route_ms_per_iter",
                 "rank_move_ms_per_iter", "rank_pairs_ms_per_iter"):
        for scope in metric_args(name)["scopes"]:
            assert PHASES[scope][0] == "device", (name, scope)
