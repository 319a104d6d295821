"""Tables wider than the narrow cells', trained on the CPU.

Until PR 33 ``build_kwargs`` sent a packed row of more than 256 B to the
rows layout and the XLA einsum histogram, and one of more than 512 B to the
XLA partition too; now such a table takes the planes layout and both planes
kernels with chunks that follow its width, and ``ops/route.py`` gives a
table whose columns do not fit VMEM together the router's wide form
(``epsilon.train``, 2,000 columns; ``tests/test_aot_tpu.py`` compiles its
block for a v5e). Here a 300- and a 600-column table train through
``lgb.train`` as they resolve on a TPU, ``runtime.on_tpu`` replaced as in
``tests/test_chip_path.py`` and the Pallas kernels under the interpreter,
and are held to the benchmark's plain numpy references by the cell's own
checks: the root's split, both of its children's, the routed leaf counts,
``predict``.

One thing does not follow the patch: the kernels' operands stay float32
(``histogram._mxu_dtype``), because XLA:CPU accumulates a bf16 dot in bf16.
"""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from conftest import clean_cpu_env
from test_chip_path import efb_onehot
from lightgbm_tpu import fused, learner, obs, runtime
from lightgbm_tpu.ops import histogram, partition, route

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from reference import model_text  # noqa: E402

PARAMS = {"objective": "binary", "num_leaves": 6, "max_bin": 63,
          "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 10,
          "learning_rate": 0.1, "verbosity": -1, "tpu_iter_block": 2}
CHECKS = [{"kind": "tree_census", "min_leaves": 6},
          {"kind": "routed_counts", "trees": ["first", "last"]},
          {"kind": "predict", "rows": 4096, "tol": 1e-5, "edge_rows_max": 0},
          {"kind": "root_split_binary", "gain_rtol": 1e-4},
          {"kind": "child_splits_binary", "gain_rtol": 1e-4,
           "min_children": 2}]


def _check(kind):
    spec = importlib.util.spec_from_file_location(
        "bench_checks_" + kind, os.path.join(BENCH, "checks", kind + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def as_on_tpu(monkeypatch):
    """Resolve and route as a TPU does, kernels interpreted. jit's trace
    caches do not key on the patched function: dropped on the way in and
    out, as tests/test_aot_tpu.py does."""
    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    monkeypatch.setattr(histogram, "_mxu_dtype", lambda: jnp.float32)
    monkeypatch.setattr(partition, "_INTERPRET", True)
    jax.clear_caches()
    fused._BLOCK_CACHE.clear()
    yield
    jax.clear_caches()
    fused._BLOCK_CACHE.clear()


def _table(rng, n, f):
    """Dense float32 rows and a label that a few strong columns decide, so
    that no two of the f candidates tie at a node."""
    X = rng.randn(n, f).astype(np.float32)
    w = np.zeros(f)
    w[rng.choice(f, 12, replace=False)] = rng.randn(12) * 1.5
    y = (X @ w + 0.3 * rng.randn(n) > 0).astype(np.float32)
    return X, y


def _train(X, y, params):
    ds = lgb.Dataset(X, label=y, params=dict(params))
    bst = lgb.train(dict(params), ds, num_boost_round=2)
    header, trees = model_text.parse(bst.model_to_string())
    return bst, {"params": params, "rows": len(y), "X": X, "label": y,
                 "group": None, "booster": bst, "binned": ds.construct(),
                 "header": header, "trees": trees}


# columns, (layout, partition, histogram, router) as on a TPU
SIDES = [(300, ("planes", "pallas", "pallas", "pallas_stream")),
         (600, ("planes", "pallas", "pallas", "pallas_wide"))]


@pytest.mark.parametrize("f,path", SIDES, ids=["f300", "f600"])
def test_wide_table_trains_to_the_plain_reference(f, path, rng, as_on_tpu):
    X, y = _table(rng, 1800, f)
    obs.telemetry.reset()
    bst, c = _train(X, y, PARAMS)
    rec = obs.telemetry.records("learner_path")[-1]
    assert (rec["work_layout"], rec["part_kernel"], rec["hist_kernel"],
            rec["route_kernel"]) == path
    assert rec["packed_row_bytes"] == f + 12
    assert obs.telemetry.records("job_start")[-1]["path"] == "fused"
    for chk in CHECKS:
        ok, detail = _check(chk["kind"]).run(chk, c)
        assert ok, (chk["kind"], detail)


def test_child_check_fails_a_bf16_histogram(rng, as_on_tpu):
    """The nearest precision below the configured ``hilo``: the children's
    gains leave the reference by more than the limit, the rows do not."""
    X, y = _table(rng, 1800, 300)
    chk = CHECKS[-1]
    _, c = _train(X, y, dict(PARAMS, tpu_hist_precision="bf16"))
    ok, detail = _check("child_splits_binary").run(chk, c)
    assert not ok and "numpy" in detail, detail
    ok, detail = _check("routed_counts").run(CHECKS[1], c)
    assert ok, detail


@pytest.mark.parametrize("kind", ["dense", "bundled"])
def test_wide_router_routes_as_the_xla_router(kind, rng, monkeypatch):
    """Both Pallas forms against the XLA ``fori_loop`` router, leaf id for
    leaf id, on a trained tree's own log. The limit is patched down rather
    than a table of 400 columns built."""
    monkeypatch.setattr(partition, "_INTERPRET", True)
    if kind == "dense":
        X, y = _table(rng, 40_000, 24)    # three row blocks, the last padded
    else:
        X, y = efb_onehot(rng, None)[:2]  # expo.train's mechanism, one block
    n = len(y)
    params = dict(PARAMS, num_leaves=15, max_bin=255)
    ds = lgb.Dataset(X, label=y, params=dict(params))
    g = lgb.Booster(dict(params), ds).inner
    lrn = g.learner
    assert (lrn.bundle is not None) == (kind == "bundled")
    grad = jnp.asarray(np.stack([0.5 - y, np.full(n, 0.25), np.ones(n)],
                                axis=1), jnp.float32)
    log = lrn.make_build_fn()(
        lrn.bins, grad, lrn.meta, jnp.ones((lrn.bins.shape[1]
                                            if lrn.bundle is None else
                                            lrn.dataset.num_features,), bool),
        jax.random.PRNGKey(0), jnp.zeros((lrn.dataset.num_features,), bool))
    splits = int(log.num_splits)
    assert splits >= 10
    want = np.asarray(learner._route_rows(lrn.bins, log, False, lrn.bundle,
                                          None))
    assert np.array_equal(want, np.asarray(log.row_leaf))
    assert len(np.unique(want)) == splits + 1
    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    for budget, form in ((route.ROUTE_VMEM_BUDGET, "stream"), (0, "wide")):
        monkeypatch.setattr(route, "ROUTE_VMEM_BUDGET", budget)
        assert route.route_form(lrn.bins.shape[1]) == form
        got = learner._route_rows(lrn.bins, log, False, lrn.bundle, None)
        assert np.array_equal(np.asarray(got), want), form
    # a tree that stopped early: the rounds past its last split change nothing
    short = log._replace(num_splits=jnp.int32(5))
    monkeypatch.setattr(runtime, "on_tpu", lambda: False)
    want = np.asarray(learner._route_rows(lrn.bins, short, False, lrn.bundle,
                                          None))
    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    got = learner._route_rows(lrn.bins, short, False, lrn.bundle, None)
    assert np.array_equal(np.asarray(got), want) and want.max() == 5


def test_router_form_follows_the_static_shape_alone():
    """F x 32 KB against the budget: the three older cells' widths stream,
    the v5e compiler's own limit (500 columns, PERF.md PR 32) is past the
    switch, and ``epsilon.train``'s 2,000 take the wide form."""
    assert [route.route_form(f) for f in (10, 28, 137, 384)] == ["stream"] * 4
    assert [route.route_form(f) for f in (385, 500, 2000)] == ["wide"] * 3
    assert route.route_form(200, itemsize=2) == "wide"


def test_rehearsal_walks_the_epsilon_cell():
    """``benchmark/run.py`` on the new cell at a tiny size: the published
    width, every check of the configuration, the exit code of a rehearsal."""
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "epsilon.train", "--seed", "3300000023", "--seconds", "0.01",
         "--trace", "0", "--rehearse", json.dumps(
             {"rows": 4000, "params": {"num_leaves": 6, "tpu_iter_block": 1,
                                       "min_sum_hessian_in_leaf": 10}})],
        capture_output=True, text=True, env=clean_cpu_env(1), cwd=ROOT)
    assert p.returncode == 4, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    assert lines[-1].startswith("REHEARSAL ")
    line = json.loads(lines[-1][len("REHEARSAL "):])
    checks = {}
    for ln in lines:
        if ln.startswith("CHECK "):
            d = json.loads(ln[len("CHECK "):])
            checks[d["check"]] = d
    cfg = json.load(open(os.path.join(BENCH, "configs",
                                      "epsilon-binary-255.json")))
    assert cfg["shape"]["features"] == 2000 and cfg["reduced"] == ["iterations"]
    assert list(checks) == [c["kind"] for c in cfg["checks"]]
    # the AUC floor is the real size's (50 trees on 400,000 rows)
    assert all(d["ok"] for k, d in checks.items()
               if k != "quality_floor"), checks
    assert line["workload"] == "epsilon.train" and line["failed"] == 0
    assert "left rows" in checks["child_splits_binary"]["detail"]
