"""CPU tests of the harness's own arithmetic and of run.py's control flow.

Run by hand (the repo's tier-1 command collects tests/ only):

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

import arith  # noqa: E402
import trace_reduce  # noqa: E402
from reference import binary_root, model_text, quality  # noqa: E402


def test_ms_per_iter_is_the_whole_window_over_all_its_iterations():
    # (host seconds, iterations dispatched by then)
    returns = [(10.0, 10), (12.0, 20), (18.5, 30), (25.1, 40), (31.5, 50), (40.0, 55)]
    assert arith.closing_return(returns, 1, 13.0) == 3
    assert arith.closing_return(returns, 1, 13.2) == 4
    assert arith.closing_return(returns, 1, 99.0) is None
    assert arith.window_ms_per_iter(returns, 1, 4) == pytest.approx(650.0)
    assert arith.block_periods_ms(returns, 1, 4) == pytest.approx([650.0, 660.0, 640.0])
    # a stall in one block moves the number, where a median of blocks hid it
    stalled = [(t + (3.0 if i >= 3 else 0.0), d) for i, (t, d) in enumerate(returns)]
    assert arith.window_ms_per_iter(stalled, 1, 4) == pytest.approx(750.0)
    # a short block counts with its own iterations
    assert arith.window_ms_per_iter(returns, 1, 5) == pytest.approx(800.0)


def test_job_start_takes_out_boosting_and_compile():
    assert arith.job_start_s(100.0, 126.5, 10, 650.0, 8.0) == pytest.approx(12.0)


def test_bytes_functions():
    assert arith.partition_bytes([100, 60], features=28) == 2 * 160 * 40
    assert arith.histogram_bytes([70, 10], [30, 50], features=28, bins=256) == \
        (30 + 10) * 36 + 2 * 2 * 28 * 256 * 12
    assert arith.roofline_pct(819e9, 2.0, 819e9) == pytest.approx(50.0)


def test_peaks_unknown_device_is_an_error():
    assert arith.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        arith.peaks("cpu")


HLO = '''
%body (p: f32[8]) -> f32[8] {
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(run_block)/jit(main)/while/body/lgbtpu/partition/lgbtpu/ops/partition_segment_planes/mul" source_file="x.py" source_line=3}
  ROOT %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fd, metadata={op_name="jit(run_block)/jit(main)/while/body/lgbtpu/histogram/add"}
}
ENTRY %main (a: f32[8]) -> f32[8] {
  %while.3 = f32[8]{0} while(%a), condition=%cond, body=%body, metadata={op_name="jit(run_block)/jit(main)/while"}
  %copy.4 = f32[8]{0} copy(%while.3)
}
'''


def test_scope_map_takes_the_outermost_lgbtpu_scope():
    m = trace_reduce.scope_map(HLO)
    assert m == {"fusion.1": "lgbtpu/partition", "fusion.2": "lgbtpu/histogram",
                 "while.3": ""}
    assert trace_reduce.instruction_name(
        "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)") == "fusion.1"


def test_leaf_events_only_and_idle_gaps():
    # a while event encloses its body's events; only leaves are summed
    vocab = ["while.3", "fusion.1", "fusion.2", "copy.4"]
    #         while   f1   f2   f1   copy
    codes = [0, 1, 2, 1, 3]
    start = [0, 0, 50, 90, 120]
    dur = [100, 40, 40, 10, 10]
    spans = [("lgbtpu/train_block", 0, 200), ("lgbtpu/fused_device_wait", 95, 125)]
    r = trace_reduce.reduce_events(codes, vocab, start, dur,
                                   trace_reduce.scope_map(HLO), spans)
    assert r["leaf_events"] == 4 and r["events"] == 5
    assert r["by_scope"] == pytest.approx({"lgbtpu/partition": 50e-9,
                                           "lgbtpu/histogram": 40e-9, "": 10e-9})
    assert r["by_op"]["lgbtpu/partition/fusion.1"] == pytest.approx(50e-9)
    assert r["busy_s"] == pytest.approx(100e-9)
    assert r["window_s"] == pytest.approx(130e-9)
    # gaps 40-50 (inside train_block only) and 100-120 (innermost: device_wait)
    assert r["idle_gaps"] == pytest.approx({"lgbtpu/train_block": 10e-9,
                                            "lgbtpu/fused_device_wait": 20e-9})
    # a window on the trace's clock: its idle ends count, events outside do not
    spans.append(("bench/train_block", 45, 160))
    r = trace_reduce.reduce_events(codes, vocab, start, dur,
                                   trace_reduce.scope_map(HLO), spans, (45, 160))
    assert r["events"] == 5 and r["events_in_window"] == 3
    assert r["busy_s"] == pytest.approx(60e-9) and r["window_s"] == pytest.approx(115e-9)
    assert r["idle_gaps"] == pytest.approx({"bench/train_block": 35e-9,
                                            "lgbtpu/fused_device_wait": 20e-9})


def test_quality_metrics_on_cases_worked_by_hand():
    assert quality.auc(np.array([0, 0, 1, 1.]), np.array([.1, .4, .35, .8])) == pytest.approx(0.75)
    assert quality.auc(np.array([0, 1, 0, 1.]), np.array([.5, .5, .5, .5])) == pytest.approx(0.5)
    label = np.array([3, 2, 1, 0, 0, 0.])
    perfect = quality.ndcg_at(label, -np.arange(6.), [4, 2], 10)
    assert perfect == pytest.approx(1.0)      # second query has no relevant doc: counts 1
    swapped = quality.ndcg_at(label, np.array([0, 1, 2, 3, 0, 0.]), [4, 2], 2)
    dcg = 0.0 / np.log2(2) + (2 ** 1 - 1) / np.log2(3)
    ideal = (2 ** 3 - 1) / np.log2(2) + (2 ** 2 - 1) / np.log2(3)
    assert swapped == pytest.approx((dcg / ideal + 1.0) / 2)


@pytest.fixture(scope="module")
def tiny_model():
    import lightgbm_tpu as lgb
    from datagen import linear_score
    data = linear_score.make({"rows": 6000, "features": 6},
                             {"label": "binary", "weights_seed": 5, "interaction": 0.5, "noise": 0.3},
                             3000000019)
    params = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
              "learning_rate": 0.1, "verbosity": -1, "tpu_iter_block": 2}
    ds = lgb.Dataset(data["X"], label=data["label"], params=params)
    binned = ds.construct()
    bst = lgb.train(params, ds, num_boost_round=4)
    return data, binned, bst


def test_numpy_router_and_root_split_against_a_tiny_trained_model(tiny_model):
    data, binned, bst = tiny_model
    header, trees = model_text.parse(bst.model_to_string())
    assert len(trees) == 4
    for t in trees:
        assert np.array_equal(model_text.leaf_counts(t, data["X"]), t["leaf_count"])
        parent, left, right = model_text.split_rows(t)
        assert parent[0] == 6000 and np.array_equal(parent, left + right)
    mine = model_text.raw_score(header, trees, data["X"][:2000])
    theirs = bst.predict(data["X"][:2000], raw_score=True)
    assert np.abs(mine - theirs).max() < 1e-5
    bounds = [np.asarray(m.upper_bounds, np.float64) for m in binned.bin_mappers]
    f, b, gain = binary_root.root_split(data["X"], data["label"], bounds)
    assert f == trees[0]["split_feature"][0]
    assert bounds[f][b] == pytest.approx(trees[0]["threshold"][0])
    assert gain == pytest.approx(trees[0]["split_gain"][0], rel=1e-4)


def test_predict_check_counts_rows_on_a_thresholds_nearest_float32(tiny_model):
    """The program's predictor rounds thresholds to the nearest float32; a row
    whose value is that float32, above the threshold, goes left there and
    right in training. The check finds such rows, counts them, and lets
    them match either walk."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "check_predict", os.path.join(BENCH, "checks", "predict.py"))
    check = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(check)
    data, _, bst = tiny_model
    header, trees = model_text.parse(bst.model_to_string())
    rows = []
    for t in trees:      # a row on the root threshold's float32, where that lies above it
        thr, f = t["threshold"][0], t["split_feature"][0]
        if np.float64(np.float32(thr)) > thr:
            x = np.zeros(data["X"].shape[1], np.float32)
            x[f] = np.float32(thr)
            rows.append(x)
    assert rows, "no root threshold rounds up: pick another seed"
    # enough rows that predict takes the device path, as the check's 65,536 do
    X = np.ascontiguousarray(np.vstack(rows + [data["X"]]))
    assert len(X) >= bst.inner.DEVICE_PREDICT_MIN_ROWS
    exact = model_text.raw_score(header, trees, X)
    near = model_text.raw_score(header, trees, X, nearest32=True)
    assert (exact != near)[:len(rows)].all()
    theirs = bst.predict(X, raw_score=True)[:len(rows)]
    assert np.abs(theirs - near[:len(rows)]).max() < 1e-5 < np.abs(theirs - exact[:len(rows)]).min()
    c = {"rows": len(X), "X": X, "header": header, "trees": trees, "booster": bst}
    ok, detail = check.run({"rows": len(X), "tol": 1e-5, "edge_rows_max": 64}, c)
    assert ok, detail
    ok, detail = check.run({"rows": len(X), "tol": 1e-5, "edge_rows_max": 0}, c)
    assert not ok and "%d rows sit on" % int((exact != near).sum()) in detail


def test_data_is_the_seeds_and_query_sizes_are_the_configurations():
    from datagen import linear_score
    cfg = json.load(open(os.path.join(BENCH, "configs", "mslr-lambdarank-255.json")))
    args, shape = cfg["datagen"]["args"], {"rows": 20000, "features": 5}
    a, b, c = (linear_score.make(shape, args, s) for s in (1, 1, 3000000019))
    assert np.array_equal(a["X"], b["X"]) and not np.array_equal(a["X"], c["X"])
    assert np.array_equal(a["group"], c["group"]) and a["group"].sum() == 20000
    assert a["X"].dtype == np.float32 and set(np.unique(a["label"])) <= {0, 1, 2, 3, 4}


def test_benchmark_json_and_the_files_it_names_agree():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        spec = json.load(open(os.path.join(BENCH, "metrics", m["name"] + ".json")))
        assert {k: spec[k] for k in m if k != "workloads"} == \
            {k: v for k, v in m.items() if k != "workloads"}
        assert m["moves"] in e2e
        assert os.path.exists(os.path.join(BENCH, "readers", spec["reader"] + ".py"))
    for w in bench["workloads"]:
        cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
        spec = json.load(open(os.path.join(ROOT, cfg["file"])))
        assert spec["reduced"] == cfg["reduced"]
        mix = json.load(open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")))
        assert os.path.exists(os.path.join(BENCH, "jobs", mix["job"] + ".py"))
        for chk in spec["checks"]:
            assert os.path.exists(os.path.join(BENCH, "checks", chk["kind"] + ".py"))


def test_benchmark_json_keeps_to_the_contracts_limits():
    import re
    path = os.path.join(ROOT, "BENCHMARK.json")
    bench = json.load(open(path))
    assert os.path.getsize(path) <= 64 * 1024
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

    def line(text):
        return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert all(line(w) for w in bench["command"]) and len(bench["command"]) <= 32
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert name.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert all(name.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
    cells = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert name.match(w["name"]) and name.match(w["traffic"]) and line(w["why"])
        assert w["chips"] in (1, 4) and (w["config"], w["traffic"]) not in cells
        cells.add((w["config"], w["traffic"]))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert line(m["layer"]) and m["moves"] in e2e
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(name.match(m["name"]) and unit.match(m["unit"])
               and m["better"] in ("lower", "higher") for m in metrics)
    for d in bench["paths"]:
        for folder, _, files in os.walk(os.path.join(ROOT, d)):
            if "__pycache__" not in folder:
                assert all(re.match(r"^[A-Za-z0-9_.\-]+$", f) for f in files), folder


def _run(*extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", "higgs.train",
         "--seed", "3000000019", "--seconds", "1", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)


def test_no_result_without_a_tpu():
    p = _run("--trace", "0")
    assert p.returncode not in (0, 4)
    assert "platform=cpu" in p.stderr
    assert not p.stdout.strip().splitlines()[-1].startswith("{")


def test_rehearsal_walks_every_phase_on_the_cpu_and_exits_4():
    p = _run("--trace", "0", "--rehearse",
             '{"rows": 30000, "params": {"num_leaves": 31}}')
    assert p.returncode == 4, p.stderr[-2000:]
    last = p.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL ")
    line = json.loads(last[len("REHEARSAL "):])
    assert line["device"]["platform"] == "cpu" and line["correct"] is True
    assert line["attempted"] >= 10 and line["failed"] == 0
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert set(line["metrics"]) == {m["name"] for m in bench["end_to_end"]}
