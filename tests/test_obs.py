"""Telemetry subsystem: obs primitives, run counters, exposure surfaces.

Covers the registry round-trip, the dataset device-cache hit/miss/
invalidation counters over repeated trains, the auto-knob resolution
records, CallbackEnv.telemetry during log_evaluation, the bit-parity
guarantee (telemetry never perturbs trained trees), the utils.log
thread-default regression, and the "no naked time.time() walls" grep over
the migrated timing harnesses.
"""
import json
import os
import re
import threading

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.obs import Telemetry, telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _data(n=400, f=6, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f)
    y = (X[:, 0] + X[:, 1] > 1).astype(np.float64)
    return X, y


PARAMS = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
          "verbosity": -1}


def _work_of(trees):
    """(row visits, histogram rows) of the trees, from the trees
    (``Tree.work`` is held to a plain loop in tests/test_job_timeline.py)."""
    work = [t.work() for t in trees]
    return (sum(w["row_visits"] for w in work),
            sum(w["hist_rows"] for w in work))


# ---------------------------------------------------------------- registry

def test_registry_snapshot_roundtrip():
    t = Telemetry()
    t.count("a/b")
    t.count("a/b", 3)
    t.gauge("g", np.int64(7))          # numpy scalars must serialize
    t.add_time("t", 0.25)
    with t.timed("t"):
        pass
    t.record("ev", knob="k", value=np.float32(1.5))
    t.record("dd", dedupe_key=("x", 1), v=1)
    t.record("dd", dedupe_key=("x", 1), v=1)   # deduped
    t.record("dd", dedupe_key=("x", 2), v=2)
    snap = t.snapshot()
    parsed = json.loads(json.dumps(snap))      # must survive json round-trip
    assert parsed["counters"]["a/b"] == 4
    assert parsed["gauges"]["g"] == 7
    assert parsed["timers"]["t"] >= 0.25
    assert parsed["timer_calls"]["t"] == 2
    assert parsed["records"]["ev"] == [{"knob": "k", "value": 1.5}]
    assert len(parsed["records"]["dd"]) == 2
    t.reset()
    empty = t.snapshot()
    assert empty["counters"] == {} and empty["records"] == {}


def test_wall_and_sync_primitives():
    import jax.numpy as jnp
    with obs.wall("obs_test/block", record=False) as w:
        x = jnp.arange(8.0) * 2
        got = obs.sync(x)
    assert w.seconds > 0
    assert got is not None and got.shape == (1,)
    assert obs.sync({"host": 3}) is None       # no device leaves -> no-op


def test_ab_interleaved_protocol():
    import jax
    import jax.numpy as jnp

    def make(k):
        @jax.jit
        def f():
            def body(c, _):
                return c * 1.0000001 + 1.0, None   # changing carry
            out, _ = jax.lax.scan(body, jnp.float32(0), None, length=k * 50)
            return out.reshape(1)
        return f

    with pytest.raises(ValueError):
        obs.ab_interleaved([("x", make)], k=1)
    res = obs.ab_interleaved([("x", make)], reps=2, k=3)
    assert set(res) == {"x"} and np.isfinite(res["x"])


# ------------------------------------------------------------ histograms

def test_histogram_buckets_and_percentiles():
    h = obs.Histogram(bounds=(1.0, 2.0, 4.0, 8.0))
    for v in (0.5, 1.0, 3.0, 3.5, 100.0):
        h.observe(v)
    assert h.count == 5 and h.sum == 108.0
    # le-inclusive buckets: 1.0 lands in le=1, 100 overflows to +Inf
    assert h.cumulative() == [(1.0, 2), (2.0, 2), (4.0, 4), (8.0, 4),
                              ("+Inf", 5)]
    assert h.percentile(0.0) == 0.0 or h.percentile(0.0) <= 1.0
    assert 2.0 <= h.percentile(0.6) <= 4.0    # interpolated in (2, 4]
    assert h.percentile(1.0) == 8.0           # overflow clamps to top bound
    snap = h.snapshot()
    assert snap["count"] == 5
    assert snap["buckets"][-1] == ["+Inf", 5]
    assert set(snap) >= {"p50", "p90", "p99", "p999", "sum"}
    json.dumps(snap)
    # cumulative counts never decrease (Prometheus invariant)
    cums = [c for _, c in h.cumulative()]
    assert cums == sorted(cums)


def test_registry_histograms_in_snapshot():
    t = Telemetry()
    for v in (1.0, 5.0, 50.0):
        t.observe("lat_ms", v)
    snap = t.snapshot()
    assert snap["histograms"]["lat_ms"]["count"] == 3
    assert t.histogram("lat_ms")["count"] == 3
    assert t.histogram("nope") is None
    t.reset()
    assert t.snapshot()["histograms"] == {}


_PROM_SAMPLE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{le="[^"]+"\})? (-?[0-9.eE+\-]+|[0-9]+)$')
_PROM_TYPE = re.compile(
    r"^# TYPE ([a-zA-Z_:][a-zA-Z0-9_:]*) (counter|gauge|histogram)$")


def parse_prometheus(text):
    """Strict line parser for text exposition 0.0.4: returns
    {family: type} and {sample_name(+labels): float}."""
    families, samples = {}, {}
    assert text.endswith("\n")
    for line in text.rstrip("\n").split("\n"):
        m = _PROM_TYPE.match(line)
        if m:
            assert m.group(1) not in families, "duplicate family"
            families[m.group(1)] = m.group(2)
            continue
        m = _PROM_SAMPLE.match(line)
        assert m, "unparseable exposition line: %r" % line
        samples[m.group(1) + (m.group(2) or "")] = float(m.group(3))
    return families, samples


def test_prometheus_text_renders_all_kinds():
    t = Telemetry()
    t.count("serve/requests", 3)
    t.gauge("serve/queue_depth", 2)
    t.gauge("layout", "rows-major")            # non-numeric: skipped
    t.add_time("wall/serve", 0.5)
    t.observe("serve/latency_ms", 3.0)
    t.observe("serve/latency_ms", 700.0)
    text = obs.prometheus_text(t)
    families, samples = parse_prometheus(text)
    assert families["lgbtpu_serve_requests_total"] == "counter"
    assert families["lgbtpu_serve_queue_depth"] == "gauge"
    assert families["lgbtpu_wall_serve_seconds_total"] == "counter"
    assert families["lgbtpu_serve_latency_ms"] == "histogram"
    assert "lgbtpu_layout" not in families
    assert samples["lgbtpu_serve_requests_total"] == 3
    assert samples["lgbtpu_wall_serve_calls_total"] == 1
    assert samples['lgbtpu_serve_latency_ms_bucket{le="+Inf"}'] == 2
    assert samples["lgbtpu_serve_latency_ms_count"] == 2
    assert samples["lgbtpu_serve_latency_ms_sum"] == 703.0
    # cumulative bucket series is monotone in le order
    buckets = [(k, v) for k, v in samples.items()
               if k.startswith("lgbtpu_serve_latency_ms_bucket")]
    vals = [v for _, v in buckets]
    assert vals == sorted(vals) and vals[-1] == 2


def test_prometheus_name_collision_first_family_wins():
    t = Telemetry()
    t.count("a/b", 1)
    t.count("a.b", 5)          # sanitizes to the same family name
    families, samples = parse_prometheus(obs.prometheus_text(t))
    assert families["lgbtpu_a_b_total"] == "counter"
    # keys render in sorted order, so "a.b" is emitted first and wins
    assert samples["lgbtpu_a_b_total"] == 5


def test_fleet_exposition_round_trips_every_family(tmp_path):
    """ISSUE 15: after a fleet e2e run (trainer + replica + one publish
    + heartbeats) EVERY counter and histogram family in the snapshot
    round-trips through the strict exposition parser — including the
    new ``lgbtpu_fleet_*`` convergence families."""
    from lightgbm_tpu.fleet import FleetStore, ReplicaWatcher
    from lightgbm_tpu.online import OnlineTrainer

    X, y = _data(n=300)
    bst = lgb.train(PARAMS, lgb.Dataset(X, label=y), num_boost_round=4)
    telemetry.reset()
    store = FleetStore(str(tmp_path), "default")
    trainer = OnlineTrainer(bst, trigger_rows=10**9, min_rows=64,
                            shadow_rows=10**6, promote_threshold=2.0,
                            promote_patience=2, store=store,
                            holder_id="obs-trainer", start=False)
    store.publish(bst.model_to_string(), event="boot")
    replica = lgb.Booster(model_str=bst.model_to_string())
    w = ReplicaWatcher(replica, store, node_id="obs-replica", start=False)
    assert w.poll_once()
    assert trainer.maybe_heartbeat(force=True)
    assert w.maybe_heartbeat(force=True)
    trainer.close()

    snap = telemetry.snapshot()
    families, samples = parse_prometheus(obs.prometheus_text())
    # the run actually exercised the new convergence families
    for fam, kind in (("lgbtpu_fleet_replica_polls_total", "counter"),
                      ("lgbtpu_fleet_replica_swaps_total", "counter"),
                      ("lgbtpu_fleet_heartbeats_recorded_total", "counter"),
                      ("lgbtpu_fleet_publish_adopt_lag_ms", "histogram"),
                      ("lgbtpu_fleet_version_skew", "gauge"),
                      ("lgbtpu_fleet_applied_version", "gauge"),
                      ("lgbtpu_fleet_events_log_bytes", "gauge")):
        assert families.get(fam) == kind, (fam, families.get(fam))
    assert samples["lgbtpu_fleet_replica_swaps_total"] == 1
    assert samples["lgbtpu_fleet_heartbeats_recorded_total"] == 2
    assert samples["lgbtpu_fleet_publish_adopt_lag_ms_count"] == 1
    # completeness: every snapshot counter/histogram surfaced as a
    # correctly-typed family (first-family-wins may merge same-name
    # kin, but nothing may go missing or change type)
    for name in snap["counters"]:
        assert families.get(obs._prom_name(name) + "_total") == \
            "counter", name
    for name in snap["histograms"]:
        fam = obs._prom_name(name)
        assert families.get(fam) == "histogram", name
        assert samples[fam + "_count"] == \
            snap["histograms"][name]["count"], name


def test_compile_listener_install_is_idempotent():
    import jax
    import jax.numpy as jnp

    obs.install_compile_listener()
    # simulate a module re-import losing the module-global flag: the
    # sentinel on jax.monitoring must still prevent a second listener
    obs._compile_listener_installed = False
    obs.install_compile_listener()
    assert obs._compile_listener_installed
    telemetry.reset()

    @jax.jit
    def _fresh(x):
        return x * 3.0 + 1.0

    _fresh(np.arange(11.0)).block_until_ready()
    c = telemetry.snapshot()["counters"]
    # a doubled listener would count 2 per compile
    assert c.get("jit/backend_compiles", 0) == 1


# ------------------------------------------------------------- hot path

def test_train_telemetry_counters_and_auto_records():
    X, y = _data()
    ds = lgb.Dataset(X, label=y)
    telemetry.reset()
    bst = lgb.train(dict(PARAMS), ds, num_boost_round=4)
    snap = bst.telemetry()
    json.dumps(snap)                           # acceptance: serializable
    c = snap["counters"]
    # dataset device caches: first train uploads (misses), no hits yet
    assert c["dataset/device_bins/miss"] >= 1
    assert c["dataset/device_bins/upload_bytes"] > 0
    # fused pipeline dispatched and flushed at train end
    assert c["fused/blocks_dispatched"] >= 1
    assert c["fused/iters_dispatched"] == 4
    assert c["fused/flush/train_end"] == 1
    # per-tree growth + work accounting, against the trees themselves
    assert c["tree/trees"] == 4
    assert c["tree/leaves"] == c["tree/splits"] + c["tree/trees"]
    blocks = snap["records"]["fused_block"]
    visits, hist = (sum(r[f] for r in blocks)
                    for f in ("row_visits", "hist_rows"))
    assert (visits, hist) == _work_of(bst.inner.models)
    assert 0 < hist <= visits // 2
    assert sum(r["splits"] for r in blocks) == c["tree/splits"]
    # phase timers nonzero after a CPU train
    assert snap["timers"].get("fused/dispatch", 0) > 0
    assert snap["timers"].get("fused/logs_transfer", 0) > 0
    # one auto-resolution record per auto knob (ISSUE 10 added the
    # chunk knobs so the run ledger can preresolve the full set; ISSUE 17
    # the GOSS compaction knob)
    knobs = {r["knob"]: r for r in snap["records"]["auto_resolution"]}
    assert set(knobs) == {"tpu_partition_kernel", "tpu_hist_kernel",
                          "tpu_work_layout", "tpu_resident_state",
                          "tpu_part_chunk", "tpu_hist_chunk",
                          "tpu_goss_compact"}
    for r in knobs.values():
        assert r["configured"] == "auto" and r["value"] and r["reason"]


def test_second_train_hits_device_cache_and_bump_invalidates():
    X, y = _data(seed=1)
    ds = lgb.Dataset(X, label=y)
    binned = ds.construct(dict(PARAMS))
    lgb.train(dict(PARAMS), ds, num_boost_round=3)
    telemetry.reset()
    lgb.train(dict(PARAMS), ds, num_boost_round=3)
    c = telemetry.snapshot()["counters"]
    assert c.get("dataset/device_bins/hit", 0) > 0      # acceptance bar
    assert c.get("dataset/device_bins/miss", 0) == 0
    # bump_version invalidates: next train re-uploads
    binned.bump_version()
    binned.metadata.bump_version()
    telemetry.reset()
    lgb.train(dict(PARAMS), ds, num_boost_round=3)
    c = telemetry.snapshot()["counters"]
    assert c.get("dataset/device_bins/miss", 0) >= 1


def test_read_api_flush_reasons():
    X, y = _data(seed=2)
    ds = lgb.Dataset(X, label=y)
    telemetry.reset()
    bst = lgb.train(dict(PARAMS), ds, num_boost_round=3)
    bst.num_trees()
    c = telemetry.snapshot()["counters"]
    # train() itself flushed at train_end; num_trees after that finds no
    # in-flight block, so no fused/flush/num_trees is counted
    assert c["fused/flush/train_end"] == 1
    assert "fused/flush/num_trees" not in c
    # model_to_string mid-block: drive the fused trainer manually
    telemetry.reset()
    bst2 = lgb.Booster(dict(PARAMS, tpu_iter_block=8), ds)
    bst2.inner.train_block(4)                  # dispatch, leave in flight
    bst2.inner.model_to_string()
    c = telemetry.snapshot()["counters"]
    assert c.get("fused/flush/model_to_string", 0) == 1


def test_callback_env_carries_telemetry():
    X, y = _data(seed=3)
    ds = lgb.Dataset(X, label=y)
    seen = []

    def spy(env):
        seen.append(env.telemetry)

    spy.order = 20
    lgb.train(dict(PARAMS), ds, num_boost_round=3, valid_sets=[ds],
              valid_names=["train"],
              callbacks=[lgb.log_evaluation(period=1), spy])
    assert len(seen) == 3
    assert all(t is telemetry for t in seen)
    # positional 6-field construction stays valid (telemetry defaults None)
    env = lgb.callback.CallbackEnv(None, {}, 0, 0, 1, None)
    assert env.telemetry is None


def _rank_data(n=600, f=6, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f)
    y = np.floor(X[:, 0] * 4).astype(np.float64)
    return X, y, [20] * (n // 20)


@pytest.mark.parametrize("objective", ["binary", "lambdarank"])
def test_telemetry_is_bit_parity_neutral(objective, monkeypatch):
    """Counters/tracing must not perturb training: a train with every
    phase scope in place and one with ``jax.named_scope`` taken out (and
    the registry reset mid-way) give byte-identical models."""
    import contextlib
    import jax
    from lightgbm_tpu import fused
    if objective == "binary":
        X, y = _data(n=300, seed=4)
        kw = {"label": y}
    else:
        X, y, group = _rank_data(seed=4)
        kw = {"label": y, "group": group}
    params = dict(PARAMS, objective=objective)
    b1 = lgb.train(dict(params), lgb.Dataset(X, **kw), num_boost_round=5)
    telemetry.reset()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    fused._BLOCK_CACHE.clear()        # trace the block again, unscoped
    b2 = lgb.train(dict(params), lgb.Dataset(X, **kw), num_boost_round=5)
    fused._BLOCK_CACHE.clear()        # and keep the unscoped one to itself
    assert b1.model_to_string() == b2.model_to_string()
    np.testing.assert_array_equal(b1.predict(X), b2.predict(X))


# ------------------------------------------------- phases, spans, records

_OP = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = (?:\([^=]*\)|\S+) ([\w\-]+)\(')
_NOT_WORK = ("parameter", "tuple", "get-tuple-element", "constant")


def _block_text(params, ds, k=2):
    """Compiled text of fused/run_block for a tiny job."""
    import jax.numpy as jnp
    from lightgbm_tpu import fused
    g = lgb.Booster(dict(params, verbosity=-1), ds).inner
    ft = fused.FusedTrainer(g)
    args = (g.train_score.score, ft._used_dev(), g._key, jnp.int32(0),
            g.learner.bins, g.learner.meta,
            fused._obj_array_state(g.objective))
    return ft._block_fn(k).lower(*args).compile().as_text()


def _scope_cases():
    X, y = _data(n=600)
    Xr, yr, group = _rank_data()
    return {
        "binary": ({"objective": "binary"}, X, {"label": y}),
        "regression": ({"objective": "regression"}, X,
                       {"label": X[:, 0] * 2 + X[:, 1]}),
        "multiclass": ({"objective": "multiclass", "num_class": 3}, X,
                       {"label": np.floor(X[:, 0] * 3)}),
        "lambdarank": ({"objective": "lambdarank"}, Xr,
                       {"label": yr, "group": group}),
        "binary_bundled": ({"objective": "binary"}, _onehot(600), {"label": y}),
        "binary_categorical": ({"objective": "binary", "min_data_per_group": 5},
                               _coded(X), {"label": y,
                                           "categorical_feature": [0, 1]}),
    }


def _coded(X):
    """Column 0 three categories (one against the rest), column 1 twelve
    (many against many); the others stay numerical."""
    rng = np.random.RandomState(4)
    X = X.copy()
    X[:, 0], X[:, 1] = rng.randint(0, 3, len(X)), rng.randint(0, 12, len(X))
    return X


def _onehot(n, blocks=4, card=9):
    """A scipy CSR one-hot table: EFB bundles each block into one column."""
    import scipy.sparse as sp
    rng = np.random.RandomState(3)
    return sp.hstack([sp.csr_matrix(
        (np.ones(n), (np.arange(n), rng.randint(0, card, n))), shape=(n, card))
        for _ in range(blocks)]).tocsr()


@pytest.mark.parametrize("objective", ["binary", "lambdarank", "regression",
                                       "multiclass", "binary_bundled",
                                       "binary_categorical"])
def test_block_program_ops_lie_under_a_phase_of_the_table(objective):
    """Every op the program puts into the block lies under one outermost
    ``lgbtpu/<phase>`` of ``obs.PHASES``, read from the compiled module's
    op_name as the benchmark reads it (``benchmark/trace_reduce.py``).

    What is left without one, and cannot be reached by a scope: what the
    compiler makes itself (no op_name: layout copies, rewritten cumsums),
    constants it hoists out of the tree build (their op_name ends at
    ``closed_call``) and the scan's own stacking of its outputs. On the CPU
    backend that is 9 % of the instructions that carry an op_name and 22 %
    of all; before the phases of PR 26 it was most of them."""
    import sys
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import trace_reduce
    params, X, kw = _scope_cases()[objective]
    text = _block_text(dict(params, num_leaves=7, min_data_in_leaf=5),
                       lgb.Dataset(X, **kw))
    scope_of = trace_reduce.scope_map(text)
    found, work, named, bare, bare_named = set(), 0, 0, 0, 0
    for line in text.splitlines():
        m = _OP.match(line)
        if not m or m.group(2) in _NOT_WORK:
            continue
        has_name = 'op_name="' in line
        scope = scope_of.get(m.group(1), "")
        work += 1
        named += has_name
        if scope:
            found.add(scope)
        else:
            bare += 1
            bare_named += has_name
    assert found <= set(obs.PHASES), found - set(obs.PHASES)
    assert all(obs.PHASES[s][0] == "device" for s in found)
    assert bare_named <= 0.12 * named, (bare_named, named)
    assert bare <= 0.25 * work, (bare, work)
    rank = {"lgbtpu/rank_gather", "lgbtpu/rank_sort", "lgbtpu/rank_pairs",
            "lgbtpu/rank_scatter"}
    if objective == "lambdarank":
        assert rank <= found and "lgbtpu/objective" not in found
    else:
        assert "lgbtpu/objective" in found and not rank & found
    assert {"lgbtpu/route", "lgbtpu/tree_state", "lgbtpu/tree_log",
            "lgbtpu/block_setup", "lgbtpu/sample"} <= found
    # the bundle view and the routing-table translation are a phase of their
    # own beside split_scan and partition, and hold no op without bundles
    assert ("lgbtpu/efb_view" in found) == (objective == "binary_bundled")
    assert "lgbtpu/split_scan" in found
    # the categorical half of the search is a SIBLING of split_scan (named
    # inside ops/split.py, where the caller's wrap stays off), and a job
    # without categorical columns holds no op under it
    assert ("lgbtpu/cat_scan" in found) == (objective == "binary_categorical")
    assert "lgbtpu/cat_scan" in obs.PHASES
    nested = [ln for ln in text.splitlines()
              if "lgbtpu/split_scan" in ln and "lgbtpu/cat_scan" in ln]
    assert not nested, nested[:2]


def test_every_phase_site_names_a_phase_of_the_table():
    """No site invents a name: every literal ``lgbtpu/...`` handed to
    trace_phase / host_phase in the package is a key of obs.PHASES, host
    phases have a timer, and every phase of the table has a site."""
    site = re.compile(r'(trace_phase|host_phase)\(\s*"(lgbtpu/[\w\-]+)')
    used = {}
    pkg = os.path.join(REPO, "lightgbm_tpu")
    for root, _, files in os.walk(pkg):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(root, f)) as fh:
                    for fn, name in site.findall(fh.read()):
                        used.setdefault(name, set()).add(fn)
    assert set(used) <= set(obs.PHASES), set(used) - set(obs.PHASES)
    assert set(obs.PHASES) <= set(used), set(obs.PHASES) - set(used)
    for name, (kind, layer, timer) in obs.PHASES.items():
        assert kind in ("device", "host") and layer
        assert (timer is not None) == (kind == "host"), name
        if kind == "host":
            assert used[name] == {"host_phase"}, name


_JOB_PARTS = ("booster_init_s", "block_fn_s", "trace_s", "lower_s",
              "compile_or_load_s", "cost_capture_s", "other_s")


@pytest.mark.parametrize("path", ["fused", "eager"])
def test_one_job_start_record_per_train(path):
    from lightgbm_tpu import fused
    X, y = _data(seed=5)
    ds = lgb.Dataset(X, label=y)
    ds.construct(dict(PARAMS))
    kw = {"valid_sets": [ds]} if path == "eager" else {}
    fused._BLOCK_CACHE.clear()
    telemetry.reset()
    lgb.train(dict(PARAMS), ds, num_boost_round=3, **kw)
    first, = telemetry.records("job_start")
    assert first["path"] == path
    for part in _JOB_PARTS + ("objective_init_s", "learner_init_s"):
        assert first[part] >= 0.0, (part, first)
    assert sum(first[p] for p in _JOB_PARTS) == pytest.approx(
        first["entry_to_first_dispatch_s"], abs=1e-6)
    assert first["objective_init_s"] + first["learner_init_s"] <= \
        telemetry.snapshot()["timers"]["train/booster_init"]
    assert first["trace_s"] > 0 and first["lower_s"] > 0
    assert first["compile_or_load_s"] > 0
    if path == "eager":
        return
    # a second identical job: the block comes from the process-wide cache
    compiles = telemetry.counter("jit/compiles/fused/run_block")
    assert compiles == 1
    lgb.train(dict(PARAMS), ds, num_boost_round=3)
    _, second = telemetry.records("job_start")
    assert telemetry.counter("jit/compiles/fused/run_block") == compiles
    assert second["trace_s"] < 0.1 * first["trace_s"]
    assert second["entry_to_first_dispatch_s"] < \
        first["entry_to_first_dispatch_s"]


def test_one_dataset_construct_record_per_real_construction():
    X, y = _data(seed=6)
    telemetry.reset()
    ds = lgb.Dataset(X, label=y)
    ds.construct(dict(PARAMS))
    rec, = telemetry.records("dataset_construct")
    assert (rec["rows"], rec["features"]) == X.shape
    parts = ("copy_s", "find_bins_s", "bundle_s", "bin_rows_s", "other_s")
    assert all(rec[p] >= 0.0 for p in parts), rec
    assert sum(rec[p] for p in parts) == pytest.approx(rec["total_s"],
                                                       abs=1e-6)
    assert rec["find_bins_s"] > 0 and rec["bin_rows_s"] > 0
    # dense columns: one device column a feature, nothing shared or lost
    assert rec["groups"] == X.shape[1] and rec["bundled_features"] == 0
    assert rec["sample_conflicts"] == 0 and rec["conflict_rows"] == 0
    assert "efb/groups" not in telemetry.snapshot()["gauges"]
    ds.construct(dict(PARAMS))                  # cached: no new record
    lgb.train(dict(PARAMS), ds, num_boost_round=2)
    assert len(telemetry.records("dataset_construct")) == 1
    # a validation set binned with the training set's mappers is a
    # construction of its own, with nothing to find
    ds.create_valid(X[:100], label=y[:100]).construct(dict(PARAMS))
    _, valid = telemetry.records("dataset_construct")
    assert valid["rows"] == 100 and valid["find_bins_s"] == 0.0


def test_iters_finalized_follows_iter():
    """dispatched - finalized = the iterations in flight."""
    X, y = _data(seed=7)
    ds = lgb.Dataset(X, label=y)
    telemetry.reset()
    bst = lgb.train(dict(PARAMS, tpu_iter_block=2), ds, num_boost_round=5)
    assert telemetry.counter("fused/iters_finalized") == bst.inner.iter_ == 5
    assert telemetry.counter("fused/iters_dispatched") == 5
    telemetry.reset()
    bst2 = lgb.Booster(dict(PARAMS), ds)
    bst2.inner.train_block(4)                   # dispatched, in flight
    assert telemetry.counter("fused/iters_dispatched") == 4
    assert telemetry.counter("fused/iters_finalized") == 0
    bst2.predict(X[:10])                        # a read API flushes
    assert telemetry.counter("fused/iters_finalized") == bst2.inner.iter_ == 4


def test_compile_listener_times_trace_and_lowering_once():
    """jit/trace_s counts a nested jit's trace once (the caller's event
    holds the callee's), and the capture's re-lowering not at all."""
    import jax
    import jax.numpy as jnp

    obs.install_compile_listener()

    @jax.jit
    def inner(x):
        return jnp.sin(x) * 2.0

    @jax.jit
    def outer(x):
        return inner(x) + inner(x + 1.0)

    telemetry.reset()
    t0 = obs.monotonic()
    outer(jnp.arange(7.0)).block_until_ready()
    wall = obs.monotonic() - t0
    t = telemetry.snapshot()["timers"]
    assert 0 < t["jit/trace_s"] and 0 < t["jit/lower_s"]
    assert t["jit/trace_s"] + t["jit/lower_s"] \
        + t["jit/backend_compile_s"] <= wall
    before = dict(t)
    with obs.suppress_backend_compiles():
        outer.lower(jnp.arange(9.0)).compile()
    t = telemetry.snapshot()["timers"]
    assert {k: t[k] for k in before if k.startswith("jit/")} == \
        {k: v for k, v in before.items() if k.startswith("jit/")}


# ------------------------------------------------------------- surfaces

def test_cli_dump_telemetry_flag(tmp_path):
    from lightgbm_tpu.cli import parse_args
    p = parse_args(["--dump-telemetry", "/tmp/t.json", "task=train"])
    assert p["dump_telemetry"] == "/tmp/t.json"
    p = parse_args(["--dump-telemetry=/tmp/u.json"])
    assert p["dump_telemetry"] == "/tmp/u.json"

    # end-to-end: train task writes the snapshot JSON
    from lightgbm_tpu import cli
    X, y = _data(n=200, seed=5)
    data = tmp_path / "train.csv"
    np.savetxt(data, np.column_stack([y, X]), delimiter=",")
    out = tmp_path / "telemetry.json"
    model = tmp_path / "model.txt"
    cli.main(["task=train", "data=%s" % data, "objective=binary",
              "num_leaves=4", "num_iterations=2", "verbosity=-1",
              "output_model=%s" % model,
              "--dump-telemetry", str(out)])
    snap = json.loads(out.read_text())
    assert snap["counters"]["tree/trees"] >= 2


# ---------------------------------------------------------------- log.py

def test_log_level_default_is_process_global():
    from lightgbm_tpu.utils import log as L
    old = L._default_level
    try:
        L.Log.reset_log_level(L.Log.DEBUG)
        seen = {}

        def worker():
            seen["level"] = L._get_level()

        t = threading.Thread(target=worker)
        t.start()
        t.join()
        # regression: thread-local default lost main-thread verbosity
        assert seen["level"] == L.Log.DEBUG
    finally:
        L.Log.reset_log_level(old)


def test_log_sink_global_with_thread_override():
    from lightgbm_tpu.utils import log as L
    lines, thread_lines = [], []

    class _Logger:                      # register_logger wants .info()
        def info(self, m):
            lines.append(m)

    lgb.register_logger(_Logger())
    try:
        L.Log.reset_log_level(L.Log.INFO)
        L.Log.info("main")

        def worker():
            L.Log.info("inherit")                   # global sink
            L.set_thread_log_level(L.Log.WARNING)   # per-thread override
            L.Log.info("suppressed")
            L.set_thread_log_level(None)
            L.set_thread_log_sink(lambda m: thread_lines.append(m))
            L.Log.info("threaded")
            L.set_thread_log_sink(None, clear=True)

        t = threading.Thread(target=worker)
        t.start()
        t.join()
    finally:
        L.Log.reset_callback(None)
        L.Log.reset_log_level(L.Log.INFO)
    joined = "".join(lines)
    assert "main" in joined and "inherit" in joined
    assert "suppressed" not in joined
    assert "threaded" not in joined
    assert any("threaded" in m for m in thread_lines)


# The naked-walls grep that lived here is superseded by graftlint's
# naked-timer rule (lightgbm_tpu/lint/rules.py), which covers ALL of
# lightgbm_tpu/, scripts/ and bench.py — see tests/test_lint.py.
