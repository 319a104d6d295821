"""Interleaved A/B: int8 one-hot MXU histogram kernel vs segment einsum.

Measures what ISSUE 17 landed — per smaller-child histogram, the
gather/one-hot einsum oracle (ops/histogram.py hist16_segment /
hist16_segment_q) against the Pallas kernel that builds per-chunk
one-hot matrices in VMEM and contracts them on the MXU
(ops/histogram.py hist_mxu_segment: int8 x int8 -> i32 accumulation on
the quantized path, bf16 hi/lo-16 splits with f32 accumulation on the
float path) — under measurement discipline v2 (PERF.md):

- single process, A and B INTERLEAVED trial-by-trial (the device clock
  drifts between runs; only same-process comparisons are trusted);
- each trial is a K-chained scan whose body threads a CHANGING carry
  (a rotating segment start), so no two executions are
  bit-identical;
- every wall ends in a forced 1-element device_get;
- per-pass time = (t_K - t_1) / (K - 1), best-of-R, which cancels the
  dispatch + sync overhead shared by both chain lengths;
- a bitwise gate runs FIRST: kernel vs oracle histograms must be
  byte-identical (f32) / integer-identical (int8) before any timing.

This is the validation gate for the tpu_hist_mxu auto knob: auto stays
"off" until a v5e session runs this script, confirms the Mosaic
lowering of the one-hot dot_general plus a wall win, and flips the
knob (or lets the run ledger carry the measured answer forward).

On a TPU backend the kernel runs natively; elsewhere it is skipped
unless LGBTPU_PALLAS_INTERPRET=1 (interpreter numbers are
correctness-only — never quote them as perf).

Usage: python scripts/hist_mxu_bisect.py [n_rows] [num_feat] [train_rows]
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
from lightgbm_tpu.ops import partition as P
from lightgbm_tpu.ops.histogram import (hist16_segment, hist16_segment_q,
                                        hist_mxu_segment)

CH = 2048        # histogram chunk (DMA window; must be a multiple of 32)
NUM_BIN = 64
REPS = 5
K = 4


def build_rows(n, f, quantized, seed=0):
    rng = np.random.RandomState(seed)
    guard, width = P.work_spec(f, quantized, "pallas", CH, CH, layout="rows")
    bins = jnp.asarray(rng.randint(0, NUM_BIN, (n, f)).astype(np.uint8))
    ghc = rng.randn(n, 3).astype(np.float32)
    ghc[:, 1] = np.abs(ghc[:, 1])
    ghc[:, 2] = 1.0
    ghc = jnp.asarray(ghc)
    pad = ((guard, guard), (0, 0))
    gscale = hscale = None
    if quantized:
        gscale = jnp.float32(127.0) / (jnp.max(jnp.abs(ghc[:, 0])) + 1e-12)
        hscale = jnp.float32(127.0) / (jnp.max(jnp.abs(ghc[:, 1])) + 1e-12)
        w0 = P.pack_rows_quantized(jnp.pad(bins, pad), jnp.pad(ghc, pad),
                                   jax.random.PRNGKey(seed), gscale, hscale)
    else:
        w0 = P.pack_rows(jnp.pad(bins, pad), jnp.pad(ghc, pad))
    if w0.shape[1] < width:
        w0 = jnp.pad(w0, ((0, 0), (0, width - w0.shape[1])))
    work = jnp.stack([w0, jnp.zeros_like(w0)])
    return work, guard, gscale, hscale


def bitwise_gate(work, guard, n, f, gscale, hscale, quantized):
    """Kernel output must equal the einsum oracle exactly before timing."""
    a, c = jnp.int32(guard + 32), jnp.int32(n - 64)
    if quantized:
        ho = hist16_segment_q(work, jnp.int32(0), a, c, gscale, hscale,
                              num_bins=NUM_BIN, num_feat=f, chunk=CH)
        hk, _ = hist_mxu_segment(work, jnp.int32(0), a, c, num_bins=NUM_BIN,
                                 num_feat=f, quantized=True, gscale=gscale,
                                 hscale=hscale, chunk=CH)
    else:
        ho = hist16_segment(work, jnp.int32(0), a, c, num_bins=NUM_BIN,
                            num_feat=f, chunk=CH)
        hk, _ = hist_mxu_segment(work, jnp.int32(0), a, c, num_bins=NUM_BIN,
                                 num_feat=f, chunk=CH)
    same = bool(jnp.all(ho == hk))
    print("bitwise gate (%s): %s" % ("int8" if quantized else "f32",
                                     "IDENTICAL" if same else "DIVERGED"))
    return same


def make_arm(fn, work, guard, n, f, **kw):
    def make(k):
        @jax.jit
        def run(w):
            def body(carry, _):
                s, acc = carry
                h = fn(w, jnp.int32(0), jnp.int32(guard) + s,
                       jnp.int32(n - 64), num_bins=NUM_BIN, num_feat=f,
                       chunk=CH, **kw)
                if isinstance(h, tuple):
                    h = h[0]
                return ((s + 1) % 32, acc + h[0, 0, 0]), None
            (_, acc), _ = jax.lax.scan(
                body, (jnp.int32(0), jnp.float32(0)), None, length=k)
            return acc.reshape(1), acc
        return lambda: run(work)
    return make


def train_wall(mxu, n, f, iters=10, seed=3):
    """Wall of one warm `lgb.train` with the knob forced on/off (rows
    layout + pallas partition, the kernel's eligibility envelope)."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X @ rng.randn(f) > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 255, "max_bin": NUM_BIN,
              "verbosity": -1, "tpu_iter_block": 5,
              "tpu_work_layout": "rows", "tpu_partition_kernel": "pallas",
              "tpu_hist_mxu": mxu}
    ds = lgb.Dataset(X, label=y)
    ds.construct()
    lgb.train(dict(params), ds, num_boost_round=5)        # warmup/compile
    def run():
        with obs.wall("bisect/train_hist_mxu_" + mxu, record=False) as w:
            bst = lgb.train(dict(params), ds, num_boost_round=iters)
            obs.sync(bst.inner.train_score.score)   # trusted wall end
        return w.seconds
    return run


def main(n, f, train_n):
    backend = jax.default_backend()
    pallas_ok = runtime.on_tpu() or P._INTERPRET
    if not pallas_ok:
        print(f"backend={backend}: no Mosaic and LGBTPU_PALLAS_INTERPRET "
              "unset — nothing to bisect (the MXU arm needs the pallas "
              "kernel). Exiting.")
        return
    print(f"backend={backend} n={n} F={f} bins={NUM_BIN} chunk={CH}"
          + (" [INTERPRET — correctness only, not perf]"
             if P._INTERPRET and not runtime.on_tpu() else ""))

    for quantized in (False, True):
        work, guard, gscale, hscale = build_rows(n, f, quantized)
        if not bitwise_gate(work, guard, n, f, gscale, hscale, quantized):
            print("REFUSING to time a diverging configuration.")
            return
        tag = "int8" if quantized else "f32"
        if quantized:
            arms = [(f"hist/{tag}_einsum",
                     make_arm(hist16_segment_q, work, guard, n, f,
                              gscale=gscale, hscale=hscale)),
                    (f"hist/{tag}_mxu",
                     make_arm(hist_mxu_segment, work, guard, n, f,
                              quantized=True, gscale=gscale,
                              hscale=hscale))]
        else:
            arms = [(f"hist/{tag}_einsum",
                     make_arm(hist16_segment, work, guard, n, f)),
                    (f"hist/{tag}_mxu",
                     make_arm(hist_mxu_segment, work, guard, n, f))]
        res = obs.ab_interleaved(arms, reps=REPS, k=K)
        print()
        for name, per in res.items():
            print(f"{name:24s} {per * 1e3:8.3f} ms/pass  "
                  f"({n / per / 1e6:7.1f} M rows/s)")
        base = res.get(f"hist/{tag}_einsum")
        mxu = res.get(f"hist/{tag}_mxu")
        if base and mxu:
            verdict = ("WIN — flip tpu_hist_mxu auto to on"
                       if base / mxu > 1.02 else "NO WIN — keep auto=off")
            print(f"\n{tag} MXU speedup: {base / mxu:.2f}x ({verdict})\n")

    if train_n > 0:
        runs = [("train/off", train_wall("off", train_n, f)),
                ("train/on", train_wall("on", train_n, f))]
        best = {name: np.inf for name, _ in runs}
        for _ in range(3):
            for name, run in runs:           # A, B, A, B per rep
                best[name] = min(best[name], run())
        print()
        for name, w in best.items():
            print(f"{name:24s} {w:8.3f} s  (10 iters, n={train_n})")


if __name__ == "__main__":
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 2_000_000
    f = int(sys.argv[2]) if len(sys.argv) > 2 else 28
    train_n = int(sys.argv[3]) if len(sys.argv) > 3 else 300_000
    main(n, f, train_n)
