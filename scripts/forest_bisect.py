"""Interleaved A/B: forest-at-once kernel vs the per-depth-gather oracle.

Measures what ISSUE 16 fused — per dispatch, the retained oracle
(``ops/predict.predict_raw_impl``: one gather round per routing depth
over the whole batch) against ONE pallas_call holding a (row-tile x
trees) traversal front in VMEM (``ops/forest.forest_predict_impl``) —
under measurement discipline v2 (PERF.md):

- single process, A and B INTERLEAVED trial-by-trial (the device clock
  drifts between runs; only same-process comparisons are trusted);
- each trial is a K-chained scan whose body threads a CHANGING carry
  (the input rows roll by one each link), so no two
  executions are bit-identical;
- every wall ends in a forced 1-element device_get;
- per-dispatch time = (t_K - t_1) / (K - 1), best-of-R, which cancels
  the dispatch + sync overhead shared by both chain lengths.

Parity is asserted before any timing: the two arms must agree on every
row (byte-identical under the CPU interpreter — the tested contract —
and allclose(1e-6) on real Mosaic, whose ulp behavior this script
exists to measure).

This is the validation gate for the ``tpu_forest_kernel`` auto knob:
auto stays "off" until a TPU session runs this script, confirms the
Mosaic lowering and a wall win, and flips the knob (or lets the run
ledger carry the measured answer forward).

On a TPU backend the kernel runs natively; elsewhere it is skipped
unless LGBTPU_PALLAS_INTERPRET=1 (interpreter numbers are
correctness-only — never quote them as perf).

Usage: python scripts/forest_bisect.py [n_rows] [num_feat] [trees]
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from lightgbm_tpu import runtime
runtime.enable_compile_cache()

from lightgbm_tpu import obs
from lightgbm_tpu.ops.forest import forest_predict_impl
from lightgbm_tpu.ops.predict import predict_raw_impl

REPS = 5
K = 4
LEAVES = 63


def build(n_rows, f, trees, seed=0):
    import lightgbm_tpu as lgb
    from lightgbm_tpu.serve.session import PredictSession

    rng = np.random.RandomState(seed)
    # grid-quantized features (f32-exact values incl. bin midpoints) so
    # the byte-parity contract is testable off-TPU
    X = np.round(rng.randn(20000, f) * 16) / 64.0
    y = (X @ rng.randn(f) > 0).astype(np.float64)
    bst = lgb.train({"objective": "binary", "num_leaves": LEAVES,
                     "verbosity": -1, "tpu_iter_block": 10},
                    lgb.Dataset(X, label=y), num_boost_round=trees)
    sess = PredictSession(bst, buckets=(n_rows,), forest="on")
    ent = sess._ensure_forest()
    if ent is None:
        raise SystemExit("model is ineligible for the forest kernel "
                         "(see the forest_ineligible telemetry record)")
    fp, f_cat, f_lin = ent
    Xq = np.round(rng.randn(n_rows, f) * 16) / 64.0
    bins, Xr = sess._bin_rows(np.ascontiguousarray(Xq, np.float32))
    pack, has_cat, has_linear = sess._ensure_pack()
    return (bst, fp, f_cat, f_lin, jnp.asarray(bins), jnp.asarray(Xr),
            pack, has_cat, has_linear,
            jnp.asarray(np.ascontiguousarray(Xq, np.float32)))


def make_oracle(X, pack, num_class, has_cat, has_linear):
    """B: the retained per-depth-gather oracle (the serve default)."""
    def make(k):
        @jax.jit
        def run(X, pack):
            def body(carry, _):
                x, acc = carry
                s = predict_raw_impl(x, pack, num_class=num_class,
                                     has_cat=has_cat,
                                     has_linear=has_linear)
                return (jnp.roll(x, 1, axis=0), acc + jnp.sum(s)), None
            (x, acc), _ = jax.lax.scan(
                body, (X, jnp.float32(0)), None, length=k)
            return x.reshape(-1)[:1], acc
        return lambda: run(X, pack)
    return make


def make_forest(bins, Xr, fp, num_class, f_cat, f_lin):
    """A: the fused op — the whole ensemble per row tile in one launch."""
    def make(k):
        @jax.jit
        def run(bins, Xr, fp):
            def body(carry, _):
                b, x, acc = carry
                s = forest_predict_impl(b, x, fp, num_class=num_class,
                                        has_cat=f_cat, has_linear=f_lin)
                return (jnp.roll(b, 1, axis=0), jnp.roll(x, 1, axis=0),
                        acc + jnp.sum(s)), None
            (b, x, acc), _ = jax.lax.scan(
                body, (bins, Xr, jnp.float32(0)), None, length=k)
            return b.reshape(-1)[:1], acc
        return lambda: run(bins, Xr, fp)
    return make


def main(n_rows, f, trees):
    backend = jax.default_backend()
    interp = os.environ.get("LGBTPU_PALLAS_INTERPRET") == "1"
    if not runtime.on_tpu() and not interp:
        print(f"backend={backend}: no Mosaic and LGBTPU_PALLAS_INTERPRET "
              "unset — nothing to bisect (the forest arm needs the "
              "pallas kernel). Exiting.")
        return
    (bst, fp, f_cat, f_lin, bins, Xr, pack, has_cat, has_linear,
     Xq) = build(n_rows, f, trees)
    K_cls = max(1, int(bst.inner.num_tree_per_iteration))
    print(f"backend={backend} n={n_rows} F={f} trees={trees} "
          f"leaves={LEAVES} rounds={int(fp.slot.shape[0])} "
          f"tpad={int(fp.slot.shape[1])}"
          + (" [INTERPRET — correctness only, not perf]"
             if not runtime.on_tpu() else ""))

    # parity before any timing: a fast wrong answer is not a result
    a = np.asarray(forest_predict_impl(bins, Xr, fp, num_class=K_cls,
                                       has_cat=f_cat, has_linear=f_lin))
    b = np.asarray(predict_raw_impl(Xq, pack, num_class=K_cls,
                                    has_cat=has_cat,
                                    has_linear=has_linear))
    byte_equal = a.tobytes() == b.tobytes()
    max_err = float(np.max(np.abs(a - b))) if a.size else 0.0
    print(f"parity: byte_equal={byte_equal} max_abs_err={max_err:.3e}")
    if not runtime.on_tpu() and not byte_equal:
        raise SystemExit("interpret-mode byte parity FAILED — the kernel "
                         "broke its oracle contract; do not time this")
    if not np.allclose(a, b, rtol=0, atol=1e-6):
        raise SystemExit("parity FAILED (max_abs_err %.3e) — fix before "
                         "timing" % max_err)

    res = obs.ab_interleaved(
        [("forest/oracle_gather",
          make_oracle(Xq, pack, K_cls, has_cat, has_linear)),
         ("forest/one_kernel",
          make_forest(bins, Xr, fp, K_cls, f_cat, f_lin))],
        reps=REPS, k=K)
    print()
    for name, per in res.items():
        print(f"{name:24s} {per * 1e3:8.3f} ms/dispatch  "
              f"({n_rows / per / 1e6:7.2f} M rows/s)")
    base = res.get("forest/oracle_gather")
    one = res.get("forest/one_kernel")
    if base and one:
        verdict = ("WIN — flip tpu_forest_kernel auto to on"
                   if base / one > 1.02 and byte_equal
                   else "NO WIN — keep auto=off")
        if base / one > 1.02 and not byte_equal:
            verdict = ("faster but NOT byte-identical on this backend — "
                       "decide whether ulp drift is acceptable before "
                       "flipping auto")
        print(f"\nforest-kernel speedup: {base / one:.2f}x ({verdict})")


if __name__ == "__main__":
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 16384
    f = int(sys.argv[2]) if len(sys.argv) > 2 else 28
    t = int(sys.argv[3]) if len(sys.argv) > 3 else 120
    main(n, f, t)
