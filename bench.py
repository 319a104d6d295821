"""Benchmark: training throughput on the reference's headline workload shapes.

Two workloads, mirroring the reference's published benchmark suite
(docs/Experiments.rst:109-150, BASELINE.md), now at REFERENCE scale:

- HIGGS-like: 10.5M rows x 28 dense numerical features, binary objective,
  num_leaves=255, max_bin=255 — the reference's primary speed benchmark
  (10.5M rows, 500 iters, 130.094 s on a 16-core CPU = 40.4 M row*iter/s).
  A 2M-row run of the same shape is reported alongside (the round 1-4
  configuration, kept for cross-round comparability).
- MSLR-like: 2.27M rows x 137 dense features, lambdarank with ~120-doc
  queries, NDCG@10 — the reference's ranking benchmark (2.27M rows,
  70.417 s = 16.1 M row*iter/s).

The metric is throughput in M row*iters/s at the same leaves/bins settings.
Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", plus
secondary fields and a phase breakdown of this script's own wall}.
"""
import json
import os
import sys

import numpy as np

N_ROWS = int(os.environ.get("BENCH_ROWS", 10_500_000))
N2_ROWS = int(os.environ.get("BENCH_ROWS_2M", 2_000_000))
N_ITER = int(os.environ.get("BENCH_ITERS", 60))
NUM_LEAVES = int(os.environ.get("BENCH_LEAVES", 255))
MAX_BIN = int(os.environ.get("BENCH_MAX_BIN", 255))
RANK_ROWS = int(os.environ.get("BENCH_RANK_ROWS", 2_270_000))
RANK_ITER = int(os.environ.get("BENCH_RANK_ITERS", 30))
SKIP_RANK = os.environ.get("BENCH_SKIP_RANK", "") == "1"
SKIP_2M = os.environ.get("BENCH_SKIP_2M", "") == "1"
SKIP_SERVE = os.environ.get("BENCH_SKIP_SERVE", "") == "1"
SKIP_LINEAR = os.environ.get("BENCH_SKIP_LINEAR", "") == "1"
LINEAR_ROWS = int(os.environ.get("BENCH_LINEAR_ROWS", 500_000))
LINEAR_ITER = int(os.environ.get("BENCH_LINEAR_ITERS", 15))
SKIP_GOSS = os.environ.get("BENCH_SKIP_GOSS", "") == "1"
GOSS_ROWS = int(os.environ.get("BENCH_GOSS_ROWS", 2_000_000))
GOSS_ITER = int(os.environ.get("BENCH_GOSS_ITERS", 30))
# non-empty = record host spans (trace_spans=on) and write the flight
# recorder as Chrome trace-event JSON (Perfetto-loadable) to this path
TRACE_PATH = os.environ.get("BENCH_TRACE", "")
# non-empty = append this bench run to the JSONL run ledger at this path
# (kind="bench"; scripts/ledger.py queries/gates it)
LEDGER_PATH = os.environ.get("BENCH_LEDGER", "")

# reference CPU: Higgs 130.094 s / (500 iter * 10.5M rows); MSLR 70.417 s /
# (500 * 2.27M)  [BASELINE.md, docs/Experiments.rst:109-123]
HIGGS_BASELINE = (500 * 10.5e6) / 130.094
MSLR_BASELINE = (500 * 2.27e6) / 70.417


def make_higgs_like(n, f=28, seed=7):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    w = rng.randn(f) / np.sqrt(f)
    logit = X @ w + 0.5 * np.sin(X[:, 0] * 2) * X[:, 1] + 0.3 * rng.randn(n)
    y = (logit > 0).astype(np.float64)
    return X.astype(np.float64), y


def make_mslr_like(n, f=137, docs_per_query=120, seed=11):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    w = rng.randn(f) / np.sqrt(f)
    rel = X @ w + 0.5 * rng.randn(n)
    # 5-grade relevance labels by global quantile, like MSLR-WEB30K
    edges = np.quantile(rel, [0.55, 0.75, 0.9, 0.97])
    y = np.digitize(rel, edges).astype(np.float64)
    sizes = []
    left = n
    while left > 0:
        s = min(left, max(20, int(rng.normal(docs_per_query, 25))))
        sizes.append(s)
        left -= s
    return X.astype(np.float64), y, np.asarray(sizes, dtype=np.int64)


_HOST_KEYS = ("fused/block_fn", "fused/dispatch", "fused/host_trees",
              "train/booster_init")
_PHASE_KEYS = _HOST_KEYS + ("fused/device_wait", "fused/logs_transfer")


def _phase_mark():
    """The registry's phase timers now; ``.grown()`` is what a train adds."""
    from lightgbm_tpu import obs
    return obs.TimerMark({k: k for k in _PHASE_KEYS})


def _phases(t, wall, traffic=None):
    """Fused-path phase dict for one timed train + its own accounting, from
    ``t``, the seconds each phase timer of the registry grew by over it.

    Device-time attribution (obs_device PR): each finalize bounds device
    execution with a forced 1-element transfer (obs.sync) BEFORE pulling
    the split-log payload, so the old ">90% in logs_transfer" catch-all
    splits into

      device_s   = fused/device_wait   — host blocked on non-overlapped
                   device execution (the pipeline overlaps block i's wait
                   with block i+1's launch, so this is the un-hidden part),
      transfer_s = fused/logs_transfer — the pure device->host log pull,
      host_s     = block trace/compile + async dispatch + per-tree model
                   reconstruction + the booster's init.

    The legacy per-phase keys stay alongside for trend continuity.

    traffic, when given, is the learner's deterministic bytes-per-row
    accounting of the per-split hot loop (SerialTreeLearner.traffic_spec) —
    merged AFTER the wall accounting so accounted_pct stays a pure
    wall-time self-check."""
    out = {k.split("/")[-1]: round(t.get(k, 0.0), 3) for k in _PHASE_KEYS}
    out["device_s"] = round(t.get("fused/device_wait", 0.0), 3)
    out["transfer_s"] = round(t.get("fused/logs_transfer", 0.0), 3)
    out["host_s"] = round(sum(t.get(k, 0.0) for k in _HOST_KEYS), 3)
    acc = sum(t.get(k, 0.0) for k in _PHASE_KEYS)
    out["other"] = round(max(wall - acc, 0.0), 3)
    out["accounted_pct"] = round(100.0 * min(acc / max(wall, 1e-9), 1.0), 1)
    if traffic:
        out["work_layout"] = traffic["work_layout"]
        out["partition_bytes_per_row_split"] = \
            traffic["partition_bytes_per_row"]
        out["hist_gather_bytes_per_row"] = traffic["hist_bytes_per_row"]
        out["effective_rows"] = traffic.get("effective_rows", 0)
        out["goss_compact"] = traffic.get("goss_compact", "off")
    return out


def run_higgs(lgb, n_rows):
    from lightgbm_tpu import obs
    with obs.wall("higgs/datagen") as w:
        X, y = make_higgs_like(n_rows)
    t_gen = w.seconds
    params = {
        "objective": "binary",
        "num_leaves": NUM_LEAVES,
        "max_bin": MAX_BIN,
        "learning_rate": 0.1,
        "verbosity": -1,
        "metric": ["auc"],
        "tpu_iter_block": 20,
    }
    with obs.wall("higgs/construct") as w:
        ds = lgb.Dataset(X, label=y)
        ds.construct()
    t_cons = w.seconds
    # short warmup train populates the persistent compile cache (reference
    # timings likewise exclude one-time setup); every train wall ends in a
    # forced 1-element transfer of the score (PERF.md discipline via obs)
    with obs.wall("higgs/warmup") as w:
        wb = lgb.train(dict(params), ds, num_boost_round=20)
        obs.sync(wb.inner.train_score.score)
    warmup_s = w.seconds
    mark = _phase_mark()
    with obs.wall("higgs/train") as w:
        bst = lgb.train(dict(params), ds, num_boost_round=N_ITER)
        obs.sync(bst.inner.train_score.score)
    train_s = w.seconds
    phases = _phases(mark.grown(), train_s,
                     bst.inner.learner.traffic_spec())
    (_, _, auc, _), = bst.eval_train()
    return ((n_rows * N_ITER) / train_s, auc, train_s, warmup_s, t_gen,
            t_cons, phases)


def run_mslr(lgb):
    from lightgbm_tpu import obs
    with obs.wall("mslr/datagen") as w:
        X, y, group = make_mslr_like(RANK_ROWS)
    t_gen = w.seconds
    params = {
        "objective": "lambdarank",
        "num_leaves": NUM_LEAVES,
        "max_bin": MAX_BIN,
        "learning_rate": 0.1,
        "verbosity": -1,
        "metric": ["ndcg"],
        "eval_at": [10],
        "tpu_iter_block": 10,
    }
    with obs.wall("mslr/construct") as w:
        ds = lgb.Dataset(X, label=y, group=group)
        ds.construct()
    t_cons = w.seconds
    with obs.wall("mslr/warmup") as w:
        wb = lgb.train(dict(params), ds, num_boost_round=10)
        obs.sync(wb.inner.train_score.score)
    warmup_s = w.seconds
    mark = _phase_mark()
    with obs.wall("mslr/train") as w:
        bst = lgb.train(dict(params), ds, num_boost_round=RANK_ITER)
        obs.sync(bst.inner.train_score.score)
    train_s = w.seconds
    phases = _phases(mark.grown(), train_s,
                     bst.inner.learner.traffic_spec())
    evals = {name: v for (_, name, v, _) in bst.eval_train()}
    ndcg = evals.get("ndcg@10", next(iter(evals.values())))
    return ((RANK_ROWS * RANK_ITER) / train_s, ndcg, train_s, warmup_s,
            t_gen, t_cons, phases)


def run_linear(lgb):
    """Piecewise-linear leaf trees: full-train wall with the host per-leaf
    solve loop (linear_device=off) vs the batched device fit (on), plus
    prediction parity between the two models. Kernel-level A/B with
    measurement discipline lives in scripts/linear_bisect.py."""
    from lightgbm_tpu import obs
    rng = np.random.RandomState(17)
    X = rng.randn(LINEAR_ROWS, 28)
    w = rng.randn(28) / np.sqrt(28)
    y = X @ w + 0.5 * np.sin(2 * X[:, 0]) + 0.1 * rng.randn(LINEAR_ROWS)
    params = {"objective": "regression", "num_leaves": 63, "max_bin": 255,
              "learning_rate": 0.1, "verbosity": -1, "linear_tree": True,
              "linear_lambda": 0.01}
    out = {}
    boosters = {}
    for dev in ("off", "on"):
        p = dict(params, linear_device=dev)
        ds = lgb.Dataset(X, label=y, params=dict(p))
        ds.construct()
        lgb.train(dict(p), ds, num_boost_round=3)          # warmup/compile
        with obs.wall("linear/train_" + dev) as wl:
            bst = lgb.train(dict(p), ds, num_boost_round=LINEAR_ITER)
            obs.sync(bst.inner.train_score.score)
        out[dev] = wl.seconds
        boosters[dev] = bst
    pred_off = boosters["off"].predict(X[:4096])
    pred_on = boosters["on"].predict(X[:4096])
    return {
        "linear_train_off_s": round(out["off"], 3),
        "linear_train_on_s": round(out["on"], 3),
        "linear_device_speedup": round(out["off"] / max(out["on"], 1e-9), 3),
        "linear_pred_maxdiff": float(np.max(np.abs(pred_off - pred_on))),
        "linear_unit": "train wall s (N=%d F=28 leaves=63 iters=%d)"
                       % (LINEAR_ROWS, LINEAR_ITER),
    }


def run_goss(lgb):
    """GOSS row-compaction A/B: full-train wall with every per-split pass
    over all N padded rows (tpu_goss_compact=off) vs the sorted/sliced
    survivor set of ceil((top_rate+other_rate)*N) rows (on). Kernel-level
    A/B with measurement discipline lives in scripts/goss_bisect.py."""
    from lightgbm_tpu import obs
    X, y = make_higgs_like(GOSS_ROWS, seed=23)
    params = {"objective": "binary", "num_leaves": NUM_LEAVES,
              "max_bin": MAX_BIN, "learning_rate": 0.1, "verbosity": -1,
              "boosting": "goss", "top_rate": 0.2, "other_rate": 0.1,
              "tpu_iter_block": 10}
    out = {}
    eff = {}
    for mode in ("off", "on"):
        p = dict(params, tpu_goss_compact=mode)
        ds = lgb.Dataset(X, label=y)
        ds.construct()
        lgb.train(dict(p), ds, num_boost_round=3)          # warmup/compile
        with obs.wall("goss/train_" + mode) as wl:
            bst = lgb.train(dict(p), ds, num_boost_round=GOSS_ITER)
            obs.sync(bst.inner.train_score.score)
        out[mode] = wl.seconds
        tr = bst.inner.learner.traffic_spec() or {}
        eff[mode] = tr.get("effective_rows", 0)
    return {
        "goss_off_s": round(out["off"], 3),
        "goss_on_s": round(out["on"], 3),
        "goss_speedup": round(out["off"] / max(out["on"], 1e-9), 3),
        "goss_effective_rows": eff["on"],
        "goss_unit": "train wall s (N=%d F=28 leaves=%d iters=%d "
                     "top=0.2 other=0.1; effective rows off=%d on=%d)"
                     % (GOSS_ROWS, NUM_LEAVES, GOSS_ITER, eff["off"],
                        eff["on"]),
    }


def main():
    import lightgbm_tpu as lgb
    from lightgbm_tpu import runtime
    runtime.enable_compile_cache()

    if TRACE_PATH:
        from lightgbm_tpu.obs_trace import tracer
        tracer.configure("on")
    h_tp, auc, h_train, h_warm, h_gen, h_cons, h_ph = run_higgs(
        lgb, N_ROWS)
    result = {
        "metric": "higgs_like_binary_train_throughput",
        "value": round(h_tp / 1e6, 4),
        "unit": "M rows*iters/s (N=%d F=28 leaves=%d bins=%d iters=%d; "
                "auc=%.4f; train=%.1fs warmup=%.1fs datagen=%.1fs "
                "construct=%.1fs)"
                % (N_ROWS, NUM_LEAVES, MAX_BIN, N_ITER, auc, h_train,
                   h_warm, h_gen, h_cons),
        "vs_baseline": round(h_tp / HIGGS_BASELINE, 4),
        "device": runtime.device_identity(),
        "train_breakdown": h_ph,
    }
    if not SKIP_2M:
        try:
            tp2, auc2, tr2, wm2, _, _, ph2 = run_higgs(lgb, N2_ROWS)
            result["value_2m"] = round(tp2 / 1e6, 4)
            result["unit_2m"] = (
                "M rows*iters/s (N=%d; auc=%.4f; train=%.1fs warmup=%.1fs)"
                % (N2_ROWS, auc2, tr2, wm2))
            result["vs_baseline_2m"] = round(tp2 / HIGGS_BASELINE, 4)
        except Exception as e:  # pragma: no cover - recorded here, non-zero exit at the end
            result["error_2m"] = "%s: %s" % (type(e).__name__, str(e)[:200])
    if not SKIP_RANK:
        try:
            (r_tp, ndcg, r_train, r_warm, r_gen, r_cons,
             r_ph) = run_mslr(lgb)
            result["rank_value"] = round(r_tp / 1e6, 4)
            result["rank_unit"] = (
                "M rows*iters/s (MSLR-like N=%d F=137 leaves=%d bins=%d "
                "iters=%d; ndcg@10=%.4f; train=%.1fs warmup=%.1fs "
                "datagen=%.1fs construct=%.1fs)"
                % (RANK_ROWS, NUM_LEAVES, MAX_BIN, RANK_ITER, ndcg,
                   r_train, r_warm, r_gen, r_cons))
            result["rank_vs_baseline"] = round(r_tp / MSLR_BASELINE, 4)
            result["rank_train_breakdown"] = r_ph
        except Exception as e:  # pragma: no cover - recorded here, non-zero exit at the end
            result["rank_error"] = "%s: %s" % (type(e).__name__, str(e)[:200])
    if not SKIP_SERVE:
        try:
            # serving sidecar: session+batcher throughput vs naive
            # Booster.predict loop (full harness: scripts/serve_bench.py)
            from lightgbm_tpu.serve.bench import run_serve_bench
            sb = run_serve_bench(requests=256, trees=60, num_leaves=63,
                                 n_features=28, train_rows=10_000,
                                 closed_loop_requests=64)
            result["serve_value"] = sb["value"]
            result["serve_unit"] = sb["unit"]
            result["serve_vs_naive"] = sb["vs_baseline"]
            # percentiles derived from the log-bucketed latency histogram
            # (the same buckets GET /metrics exports); exact cumulative
            # counts ride along for offline re-aggregation
            result["serve_p50_ms"] = sb["closed_loop_p50_ms"]
            result["serve_p90_ms"] = sb["closed_loop_p90_ms"]
            result["serve_p99_ms"] = sb["closed_loop_p99_ms"]
            result["serve_p999_ms"] = sb["closed_loop_p999_ms"]
            result["serve_hist_buckets"] = sb["closed_loop_hist_buckets"]
        except Exception as e:  # pragma: no cover - recorded here, non-zero exit at the end
            result["serve_error"] = "%s: %s" % (type(e).__name__,
                                                str(e)[:200])
    if not SKIP_LINEAR:
        try:
            result.update(run_linear(lgb))
        except Exception as e:  # pragma: no cover - recorded here, non-zero exit at the end
            result["linear_error"] = "%s: %s" % (type(e).__name__,
                                                 str(e)[:200])
    if not SKIP_GOSS:
        try:
            result.update(run_goss(lgb))
        except Exception as e:  # pragma: no cover - recorded here, non-zero exit at the end
            result["goss_error"] = "%s: %s" % (type(e).__name__,
                                               str(e)[:200])
    # full structured-counter view of the run (dataset cache traffic, fused
    # dispatch/flush, per-tree growth, auto-knob resolutions, bench walls)
    result["telemetry"] = lgb.obs.telemetry.snapshot()
    # retrace detector verdict, hoisted for headline visibility (PERF.md
    # per-train compile budget; per-entry detail under telemetry)
    result["jit_compiles"] = result["telemetry"]["jit_compiles"]["total"]
    if LEDGER_PATH:
        try:
            from lightgbm_tpu import obs_ledger
            from lightgbm_tpu.config import Config
            cfg = Config.from_params({
                "objective": "binary", "num_leaves": NUM_LEAVES,
                "max_bin": MAX_BIN, "learning_rate": 0.1, "verbosity": -1,
                "metric": ["auc"], "tpu_iter_block": 20,
                "obs_ledger": True, "obs_ledger_path": LEDGER_PATH})
            obs_ledger.record_run(
                cfg, "bench", N_ROWS, 28,
                extra={"train_s": round(h_train, 3),
                       "throughput_M": result["value"],
                       "train_breakdown": h_ph})
            result["ledger_path"] = LEDGER_PATH
        except Exception as e:  # pragma: no cover - recorded here, non-zero exit at the end
            result["ledger_error"] = "%s: %s" % (type(e).__name__,
                                                 str(e)[:200])
    if TRACE_PATH:
        from lightgbm_tpu.obs_trace import tracer
        result["trace_path"] = TRACE_PATH
        result["trace_events"] = tracer.dump(TRACE_PATH)
    print(json.dumps(result))
    failed = sorted(k for k in result
                    if k.endswith("_error") or k.startswith("error_"))
    if failed:
        # the JSON line above still carries each phase's error text
        sys.exit("bench phases failed: " + ", ".join(failed))


if __name__ == "__main__":
    main()
