"""Interleaved A/B: GOSS row compaction vs the dense-mask oracle.

Measures what ISSUE 17 landed — after `make_sampler` zeroes the
out-of-bag rows, the compact path sorts the in-bag survivors to the
front (ops/partition.py compact_rows_by_inbag) and every downstream
per-split pass (partition, histogram, leaf routing) runs over the
static ceil((top_rate+other_rate)*N)-row slice instead of all N padded
rows — under measurement discipline v2 (PERF.md):

- single process, A and B INTERLEAVED trial-by-trial (the device clock
  drifts between runs; only same-process comparisons are trusted);
- each trial is a K-chained scan whose body threads a CHANGING carry
  (the mutated work buffer and alternating plane parity), so no
  two executions are bit-identical;
- every wall ends in a forced 1-element device_get;
- per-split time = (t_K - t_1) / (K - 1), best-of-R, which cancels the
  dispatch + sync overhead shared by both chain lengths;
- a byte-parity gate runs FIRST: compact on/off `lgb.train` must give
  identical model_to_string() before any timing is trusted.

This is the validation gate for the tpu_goss_compact auto knob: auto
stays "off" until a v5e session runs this script, confirms parity plus
a wall win at the production shape, and flips the knob (or lets the
run ledger carry the measured answer forward).

The compaction itself is pure XLA (argsort + take + lax.cond), so the
op-level A/B runs on any backend; train walls with the pallas
partition stream need a TPU (or LGBTPU_PALLAS_INTERPRET=1 — interpreter
numbers are correctness-only, never quote them as perf).

Usage: python scripts/goss_bisect.py [n_rows] [num_feat] [train_rows]
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from lightgbm_tpu.runtime import enable_compile_cache
enable_compile_cache()

from lightgbm_tpu import obs
from lightgbm_tpu.ops import partition as P
from lightgbm_tpu.ops.histogram import hist16_segment

CH = 1024        # partition/histogram chunk
NUM_BIN = 64
REPS = 5
K = 4
TOP_RATE, OTHER_RATE = 0.2, 0.1


def parity_gate(n, f, seed=3):
    """Byte-identical models, compact off vs on, before any timing."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X @ rng.randn(f) > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 63, "max_bin": NUM_BIN,
              "verbosity": -1, "boosting": "goss", "top_rate": TOP_RATE,
              "other_rate": OTHER_RATE, "learning_rate": 0.5,
              "tpu_iter_block": 2}
    out = {}
    for mode in ("off", "on"):
        ds = lgb.Dataset(X, label=y)
        bst = lgb.train(dict(params, tpu_goss_compact=mode), ds,
                        num_boost_round=6)
        out[mode] = bst.model_to_string()
    same = out["off"] == out["on"]
    print("parity gate (n=%d, 6 rounds, lr=0.5): %s"
          % (n, "BYTE-IDENTICAL" if same else "DIVERGED"))
    return same


def build_rows(n, f, seed=0):
    """Dense rows-layout work buffer with a GOSS-like in-bag mask, and its
    compacted counterpart (in-bag survivors sorted to the front)."""
    rng = np.random.RandomState(seed)
    guard, width = P.work_spec(f, False, "xla", CH, CH, layout="rows")
    bins = jnp.asarray(rng.randint(0, NUM_BIN, (n, f)).astype(np.uint8))
    ghc = rng.randn(n, 3).astype(np.float32)
    inbag = rng.rand(n) < (TOP_RATE + OTHER_RATE)
    ghc[:, 2] = inbag
    ghc[:, 0] *= inbag
    ghc[:, 1] = np.abs(ghc[:, 1]) * inbag
    ghc = jnp.asarray(ghc)
    m = P.goss_compact_rows(n, TOP_RATE, OTHER_RATE)
    bc, gc, _ = P.compact_rows_by_inbag(bins, ghc, m)

    def pack(b, g):
        pad = ((guard, guard), (0, 0))
        w0 = P.pack_rows(jnp.pad(b, pad), jnp.pad(g, pad))
        if w0.shape[1] < width:
            w0 = jnp.pad(w0, ((0, 0), (0, width - w0.shape[1])))
        return jnp.stack([w0, jnp.zeros_like(w0)])

    return pack(bins, ghc), pack(bc, gc), guard, m


def make_pass(work, guard, rows, f):
    """One per-split pass over `rows` rows: partition + histogram (the two
    passes compaction shrinks). XLA kernels, so any backend measures."""
    go_left = jnp.asarray(np.arange(NUM_BIN) < NUM_BIN // 3)

    def make(k):
        @jax.jit
        def run(w):
            def body(carry, _):
                w, c, acc = carry
                w, lt = P.partition_segment(
                    w, c % 2, jnp.int32(guard), jnp.int32(rows),
                    jnp.int32(3), go_left, ch=CH)
                h = hist16_segment(w, 1 - c % 2, jnp.int32(guard),
                                   jnp.int32(rows), num_bins=NUM_BIN,
                                   num_feat=f, chunk=CH)
                return (w, 1 - c, acc + h[0, 0, 0] + lt), None
            (w, _, acc), _ = jax.lax.scan(
                body, (w, jnp.int32(0), jnp.float32(0)), None, length=k)
            return w.reshape(-1)[:1], acc
        return lambda: run(work)
    return make


def train_wall(compact, n, f, iters=10, seed=3):
    """Wall of one warm GOSS `lgb.train` with the knob forced on/off."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X @ rng.randn(f) > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 255, "max_bin": NUM_BIN,
              "verbosity": -1, "boosting": "goss", "top_rate": TOP_RATE,
              "other_rate": OTHER_RATE, "tpu_iter_block": 5,
              "tpu_goss_compact": compact}
    ds = lgb.Dataset(X, label=y)
    ds.construct()
    lgb.train(dict(params), ds, num_boost_round=5)        # warmup/compile
    def run():
        with obs.wall("bisect/train_goss_" + compact, record=False) as w:
            bst = lgb.train(dict(params), ds, num_boost_round=iters)
            obs.sync(bst.inner.train_score.score)   # trusted wall end
        return w.seconds
    return run


def main(n, f, train_n):
    backend = jax.default_backend()
    if not parity_gate(min(n, 4000), min(f, 8)):
        print("REFUSING to time a diverging configuration.")
        return
    wd, wc, guard, m = build_rows(n, f)
    print(f"backend={backend} n={n} F={f} compact_rows={m} "
          f"({100.0 * m / n:.0f}% of dense) bins={NUM_BIN}")

    res = obs.ab_interleaved(
        [("goss/dense_pass", make_pass(wd, guard, n, f)),
         ("goss/compact_pass", make_pass(wc, guard, m, f))],
        reps=REPS, k=K)
    print()
    for name, per in res.items():
        print(f"{name:24s} {per * 1e3:8.3f} ms/split")
    base = res.get("goss/dense_pass")
    comp = res.get("goss/compact_pass")
    if base and comp:
        verdict = ("WIN — flip tpu_goss_compact auto to on"
                   if base / comp > 1.02 else "NO WIN — keep auto=off")
        print(f"\ncompaction speedup: {base / comp:.2f}x ({verdict})")

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
