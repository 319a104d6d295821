"""Chip smoke: the trainer's normal entry points, end to end, on one TPU.

    python chip_smoke.py            # needs a TPU; anything else exits non-zero

Drives ``lgb.Dataset`` -> ``lgb.train`` -> ``Booster.predict`` ->
``model_to_string`` once at the full width of the Higgs-shape binary model
(10.5M x 28, 255 leaves / 255 bins; depth cut to 10 iterations; data from a
seed) through whatever path ``auto`` resolves to on this backend, and checks
the outcome by the repo's own means. Every phase is fatal on failure. The
last stdout line is one JSON object naming the device as JAX reports it.
Every time printed here is a report from one run, never a claim.

Phases: identity gate -> canary + oracle (262K rows; the hang canary) ->
full width -> predict + round trip -> four chips (only with >= 4 devices).

``--rehearse-cpu`` walks the same phases at a tiny size on the CPU to debug
the script itself. It says so, prints ``platform=cpu``, and always exits 4:
no path through this file exits 0 without a TPU.

One process; it starts no child. The compile cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, else ``<checkout>/.jax_cache``.
"""
import argparse
import faulthandler
import importlib.metadata
import json
import sys

import numpy as np

# auc_band: how far the full-width train AUC may sit from the canary's. The
# canary trains on the first n_canary rows of the SAME draw (the generator
# draws its weights after X, so another N is another problem) for the same
# number of iterations; what is left is the canary's own overfit, which
# grows as rows per leaf shrink — hence the wider band at the tiny size.
# Seen on a v5e: canary 0.8918, full width 0.8737 (PR 21); a partition or
# histogram that loses rows lands far outside either band.
FULL = dict(n_canary=262_144, n_full=10_500_000, n_pred=65_536,
            leaves=255, max_bin=255, block=5, mesh_iters=3, auc_band=0.03)
TINY = dict(n_canary=4_096, n_full=16_384, n_pred=1_024,
            leaves=15, max_bin=63, block=2, mesh_iters=2, auc_band=0.08)

# what tpu_* = auto is documented to resolve to on a TPU at F=28 (packed row
# 40 B <= 256 B): learner.build_kwargs, PERF.md "Layers"
TPU_AUTO = dict(work_layout="planes", part_kernel="pallas",
                hist_kernel="pallas", part_chunk=1024, hist_chunk=8192)
RESOLVED_KEYS = tuple(TPU_AUTO)
# the canary's oracle: no Pallas partition, the row-major layout
ORACLE = dict(tpu_work_layout="rows", tpu_partition_kernel="xla")
AUC_TOL = 1e-3          # auto vs oracle, and four chips vs one
# f32 score accumulation over <= 20 trees of O(0.1) leaf values: ~1e-7 per
# add; the session sums tree batches in another order than the train loop
SCORE_TOL = 1e-5


def fail(msg):
    print("CHIP_SMOKE FAIL: " + msg, flush=True)
    sys.exit(1)


def check(cond, msg):
    if not cond:
        fail(msg)


def deadline(seconds):
    """Per-phase watchdog: a hung kernel dumps tracebacks and exits 1
    instead of holding the chip until the caller's limit."""
    faulthandler.cancel_dump_traceback_later()
    faulthandler.dump_traceback_later(seconds, exit=True)


def params(sz, **extra):
    p = {"objective": "binary", "num_leaves": sz["leaves"],
         "max_bin": sz["max_bin"], "learning_rate": 0.1, "verbosity": -1,
         "metric": ["auc"], "tpu_iter_block": sz["block"]}
    p.update(extra)
    return p


def resolved(bst):
    kw = bst.inner.learner.build_kwargs()
    return {k: kw[k] for k in RESOLVED_KEYS}


def check_model(bst, n_rows, iters, what):
    """Tree census + row conservation, from the model's own records."""
    trees = bst.inner.models
    check(bst.inner.iter_ == iters and len(trees) == iters,
          "%s: expected %d trees, got iter_=%d len=%d"
          % (what, iters, bst.inner.iter_, len(trees)))
    leaves = [t.num_leaves for t in trees]
    check(min(leaves) > 1, "%s: constant tree(s): leaves=%s" % (what, leaves))
    sums = [int(t.leaf_count[:t.num_leaves].sum()) for t in trees]
    check(all(s == n_rows for s in sums),
          "%s: leaf_count sums %s != N=%d" % (what, sums, n_rows))
    print("%s: %d trees, leaves min/max %d/%d, every leaf_count sum == %d"
          % (what, len(trees), min(leaves), max(leaves), n_rows))


def check_routed_counts_host(bst, X, what):
    """Recorded leaf counts (from the histograms over the PARTITIONED rows)
    against counts from routing every row through the finished tree
    (``predict(pred_leaf=True)``, which never sees the partition). A
    partition that drops, duplicates or misplaces rows breaks the equality;
    the leaf_count sum alone cannot (children are parent minus sibling)."""
    leaf = bst.predict(X, pred_leaf=True)
    for i, t in enumerate(bst.inner.models):
        routed = np.bincount(leaf[:, i], minlength=t.num_leaves)
        check(np.array_equal(routed, t.leaf_count[:t.num_leaves]),
              "%s: tree %d recorded leaf counts != routed counts "
              "(max |diff| %d)" % (what, i, int(np.abs(
                  routed - t.leaf_count[:t.num_leaves]).max())))
    print("%s: recorded == routed leaf counts, all %d rows x %d trees (host)"
          % (what, X.shape[0], leaf.shape[1]))


def check_routed_counts_device(bst, what):
    """Same check at full width, on the device: the booster's own router
    (``_route_tree_device`` -> ``assign_leaves``; not the partition) gives
    per-row leaf slots; slot order differs from leaf order, so the sorted
    count vectors are compared."""
    import jax
    import jax.numpy as jnp
    g = bst.inner

    @jax.jit
    def slot_counts(slots):
        ids = jnp.arange(256, dtype=slots.dtype)
        return jnp.sum(slots[:, None] == ids[None, :], axis=0,
                       dtype=jnp.int32)

    for i, t in enumerate(g.models):
        _, slots = g._route_tree_device(t, g.train_set)
        routed = np.sort(np.asarray(slot_counts(slots)))[::-1][:t.num_leaves]
        rec = np.sort(t.leaf_count[:t.num_leaves])[::-1]
        check(np.array_equal(routed, rec),
              "%s: tree %d recorded leaf counts != routed counts "
              "(max |diff| %d)" % (what, i, int(np.abs(routed - rec).max())))
    print("%s: recorded == routed leaf counts, all %d rows x %d trees "
          "(device router)" % (what, g.train_set.num_data, len(g.models)))


def train_auc(bst):
    (_, name, auc, _), = bst.eval_train()
    check(name == "auc" and np.isfinite(auc), "eval_train gave %r" % name)
    return float(auc)


def sample_auc(y, p):
    from lightgbm_tpu.metric import AUCMetric
    from lightgbm_tpu.config import Config
    m = AUCMetric(Config())
    (_, v), = m.eval(np.asarray(p, np.float64), np.asarray(y), None, None)
    return float(v)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny CPU walk-through of the script; exits 4")
    args = ap.parse_args()
    rehearsal = args.rehearse_cpu
    sz = TINY if rehearsal else FULL
    deadline(150)

    import jax
    import lightgbm_tpu as lgb
    from bench import make_higgs_like
    from lightgbm_tpu import io_native, obs, obs_device, runtime
    from lightgbm_tpu.ops import partition

    cache_dir = runtime.enable_compile_cache()

    # ---------------------------------------------------------- identity
    dev = runtime.device_identity()
    print("jax=%s jaxlib=%s libtpu=%s backend=%s platform=%s device_kind=%s "
          "devices=%d compile_cache=%s"
          % (jax.__version__, importlib.metadata.version("jaxlib"),
             importlib.metadata.version("libtpu"), jax.default_backend(),
             dev["platform"], dev["kind"], dev["count"], cache_dir),
          flush=True)
    if rehearsal:
        check(jax.default_backend() == "cpu",
              "--rehearse-cpu is for JAX_PLATFORMS=cpu only")
        print("REHEARSAL: platform=cpu, tiny sizes, XLA paths only — this "
              "proves the script, not the system; it exits 4 by design")
    else:
        check(runtime.on_tpu() and dev["platform"] == "tpu",
              "backend is %r, not tpu — nothing was trained"
              % jax.default_backend())
    check(not partition._INTERPRET,
          "LGBTPU_PALLAS_INTERPRET is set: kernels would run interpreted")
    # the ops modules import Pallas directly: getting here means it imported
    print("pallas: imported, interpret=off; native binning: %s"
          % io_native.binning_status(), flush=True)

    def fresh():
        obs.telemetry.reset()
        obs_device.reset()

    # host only, before the device is asked for anything
    with obs.wall("smoke/datagen") as w:
        X_all, y_all = make_higgs_like(sz["n_full"])
    t_gen = w.seconds

    # -------------------------------------------------- canary + oracle
    deadline(450)
    n = sz["n_canary"]
    iters = 2 * sz["block"]
    X, y = X_all[:n], y_all[:n]
    ds = lgb.Dataset(X, label=y)
    fresh()
    with obs.wall("smoke/canary_auto") as w:
        b_auto = lgb.train(params(sz), ds, num_boost_round=iters)
    check(not b_auto.inner.config.obs_ledger, "obs_ledger must stay off")
    res = resolved(b_auto)
    print("canary: auto resolved to %s" % json.dumps(res))
    print("canary: auto_resolution %s" % json.dumps(
        [(r["knob"], r["value"]) for r in
         obs.telemetry.snapshot()["records"].get("auto_resolution", [])]))
    print("canary: traffic_spec %s"
          % json.dumps(b_auto.inner.learner.traffic_spec()))
    if not rehearsal:
        check(res == TPU_AUTO, "auto resolved to %s, documented %s"
              % (res, TPU_AUTO))
    print("canary: auto train %.1f s (compile included)" % w.seconds,
          flush=True)
    check_model(b_auto, n, iters, "canary/auto")
    check_routed_counts_host(b_auto, X, "canary/auto")
    with obs.wall("smoke/canary_oracle") as w:
        b_orc = lgb.train(params(sz, **ORACLE), ds, num_boost_round=iters)
    print("canary: oracle %s resolved to %s, train %.1f s"
          % (json.dumps(ORACLE), json.dumps(resolved(b_orc)), w.seconds))
    check_model(b_orc, n, iters, "canary/oracle")
    auc_auto, auc_orc = train_auc(b_auto), train_auc(b_orc)
    check(abs(auc_auto - auc_orc) <= AUC_TOL,
          "canary AUC auto %.6f vs oracle %.6f differ by more than %g"
          % (auc_auto, auc_orc, AUC_TOL))
    same = b_auto.model_to_string() == b_orc.model_to_string()
    print("canary: AUC auto %.6f oracle %.6f (|diff| %.2e <= %g); "
          "model_to_string equal: %s"
          % (auc_auto, auc_orc, abs(auc_auto - auc_orc), AUC_TOL,
             "yes" if same else "no"), flush=True)
    del b_orc, ds

    # ------------------------------------------------------- full width
    deadline(600)
    n = sz["n_full"]
    X, y = X_all, y_all
    with obs.wall("smoke/construct") as w:
        ds = lgb.Dataset(X, label=y)
        ds.construct()
    print("full: N=%d F=%d datagen %.1f s, construct %.1f s (binning: %s)"
          % (n, X.shape[1], t_gen, w.seconds, io_native.binning_status()),
          flush=True)
    fresh()
    with obs.wall("smoke/full_train") as w:
        bst = lgb.train(params(sz), ds, num_boost_round=iters)
    snap = obs.telemetry.snapshot()
    res = resolved(bst)
    if not rehearsal:
        check(res == TPU_AUTO, "full: auto resolved to %s" % res)
    jc = snap["jit_compiles"]
    blocks = snap["counters"].get("fused/blocks_dispatched", 0)
    check(blocks == 2, "full: expected 2 fused blocks, got %d" % blocks)
    check(jc["per_function"].get("fused/run_block", 0) == 1,
          "full: fused/run_block compiled %s times over 2 blocks (block 2 "
          "must hit the cache)" % jc["per_function"].get("fused/run_block"))
    tm = snap["timers"]
    print("full: resolved %s" % json.dumps(res))
    print("full: train %d iters in 2 blocks: %.1f s wall, of which "
          "dispatch (block 1 trace+compile) %.1f s, device_wait %.1f s, "
          "logs_transfer %.3f s, host_trees %.3f s; backend compiles %d "
          "(%.1f s); device_cost/capture_s %.1f s"
          % (iters, w.seconds, tm.get("fused/dispatch", 0.0),
             tm.get("fused/device_wait", 0.0),
             tm.get("fused/logs_transfer", 0.0),
             tm.get("fused/host_trees", 0.0), jc["backend_compiles"],
             tm.get("jit/backend_compile_s", 0.0),
             tm.get("device_cost/capture_s", 0.0)))
    cost = snap["device_cost"]["jits"].get("fused/run_block", {})
    check(snap["counters"].get("device_cost/capture_errors", 0) == 0
          and cost.get("temp_bytes", 0) > 0,
          "full: device-cost capture failed (counted, not raised): %s"
          % snap["counters"])
    print("full: fused/run_block as compiled: temp %.3f GB, arguments %.3f "
          "GB, %.3g flops, %.3g bytes accessed per block (XLA's own count)"
          % (cost["temp_bytes"] / 1e9, cost["argument_bytes"] / 1e9,
             cost["flops"], cost["bytes_accessed"]), flush=True)
    check_model(bst, n, iters, "full")
    check_routed_counts_device(bst, "full")
    auc_full = train_auc(bst)
    lo, hi = auc_auto - sz["auc_band"], auc_auto + sz["auc_band"]
    check(lo <= auc_full <= hi, "full: AUC %.6f outside the canary band "
          "[%.4f, %.4f]" % (auc_full, lo, hi))
    print("full: AUC %.6f inside the canary band [%.4f, %.4f]"
          % (auc_full, lo, hi), flush=True)

    # ------------------------------------------- predict and round trip
    deadline(200)
    g = bst.inner
    n_pred = sz["n_pred"]
    Xp = X[:n_pred]
    raw = bst.predict(Xp, raw_score=True)
    ts = np.asarray(g.train_score.score[:n_pred], np.float64)
    d_train = float(np.abs(raw - ts).max())
    check(raw.shape == (n_pred,) and np.isfinite(raw).all(),
          "predict: bad output %s" % (raw.shape,))
    check(d_train <= SCORE_TOL, "predict: max |predict - train_score| %.3e "
          "> %g on %d rows" % (d_train, SCORE_TOL, n_pred))
    pred = bst.predict(Xp)
    model_str = bst.model_to_string()
    pred2 = lgb.Booster(model_str=model_str).predict(Xp)
    d_rt = float(np.abs(pred - pred2).max())
    check(d_rt <= SCORE_TOL, "round trip: max |diff| %.3e > %g"
          % (d_rt, SCORE_TOL))
    print("predict: %d rows, %d trees, session path; max |raw - train_score|"
          " %.2e, model_to_string round trip max |diff| %.2e (tol %g, f32)"
          % (n_pred, len(g.models), d_train, d_rt, SCORE_TOL), flush=True)

    # --------------------------------------- steady-state report (no claim)
    # further blocks of the same compiled program, on the same booster, one
    # ended the way JAX documents and one by a forced transfer (ROADMAP
    # S2's first question). After the predict phase: these trees lie beyond
    # lgb.train's best_iteration.
    deadline(200)
    k = sz["block"]
    ms = {}
    for rep in (1, 2):
        for name, wait in (("block_until_ready", jax.block_until_ready),
                           ("obs.sync", obs.sync)):
            c0 = obs.telemetry.snapshot()["jit_compiles"]
            with obs.wall("smoke/steady") as w:
                check(not g.train_block(k), "steady block stopped early")
                wait(g.train_score.score)
            g.finish_fused("chip_smoke")
            c1 = obs.telemetry.snapshot()["jit_compiles"]
            # no tracked jit may retrace; the first block outside lgb.train
            # may still compile a one-off eager helper (backend count)
            check(c1["total"] == c0["total"] and (
                rep == 1 or c1["backend_compiles"] == c0["backend_compiles"]),
                "steady block compiled: %s -> %s" % (c0, c1))
            ms["%s#%d" % (name, rep)] = 1e3 * w.seconds / k
    print("steady: block of k=%d, 0 compiles, ms/iter: %s"
          % (k, ", ".join("%s %.1f" % kv for kv in ms.items())))
    mem = jax.devices()[0].memory_stats() or {}
    print("steady: device 0 peak_bytes_in_use %s, bytes_in_use %s, "
          "bytes_limit %s" % (mem.get("peak_bytes_in_use", "not reported"),
                              mem.get("bytes_in_use", "not reported"),
                              mem.get("bytes_limit", "not reported")),
          flush=True)

    # --------------------------------------------------------- four chips
    n_dev = len(jax.devices())
    if n_dev < 4:
        print("multichip: not run, %d device" % n_dev, flush=True)
    else:
        deadline(400)
        from lightgbm_tpu.parallel.mesh import (DataParallelTreeLearner,
                                                make_mesh)
        mi = sz["mesh_iters"]
        with make_mesh(4), obs.wall("smoke/mesh_train") as w:
            b4 = lgb.train(params(sz, tree_learner="data"), ds,
                           num_boost_round=mi)
        lrn = b4.inner.learner
        check(isinstance(lrn, DataParallelTreeLearner),
              "multichip: learner is %s" % type(lrn).__name__)
        shard_devs = {s.device for s in lrn.bins.addressable_shards}
        check(len(shard_devs) == 4, "multichip: learner.bins sits on %d "
              "device(s)" % len(shard_devs))
        check_model(b4, n, mi, "multichip")
        n_s = sz["n_canary"]
        a4 = sample_auc(y[:n_s], b4.predict(X[:n_s]))
        a1 = sample_auc(y[:n_s], bst.predict(X[:n_s], num_iteration=mi))
        check(abs(a4 - a1) <= AUC_TOL, "multichip: AUC %.6f vs one chip "
              "%.6f at %d iterations" % (a4, a1, mi))
        print("multichip: tree_learner=data on 4 devices, %d iters %.1f s "
              "(compile included); resolved %s; bins on %d devices; AUC "
              "(first %d rows) 4-chip %.6f vs 1-chip %.6f"
              % (mi, w.seconds, json.dumps(resolved(b4)), len(shard_devs),
                 n_s, a4, a1))
        for d in jax.devices():
            st = d.memory_stats() or {}
            print("multichip: device %d bytes_in_use %s peak %s"
                  % (d.id, st.get("bytes_in_use", "not reported"),
                     st.get("peak_bytes_in_use", "not reported")))

    faulthandler.cancel_dump_traceback_later()
    if rehearsal:
        print("REHEARSAL on cpu: every phase ran; exit 4 by design")
        print(json.dumps({"ok": False, "rehearsal": True, "device": dev}))
        sys.exit(4)
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
