"""Interleaved A/B: one-kernel split vs the three-launch chain.

Measures what ISSUE 13 fused — per split, the three-launch oracle
(fused partition pallas_call, smaller-child segment histogram, vmapped
find_best_split scan) against ONE pallas_call running all three phases
back-to-back in VMEM (ops/partition.py one_kernel_split_planes) — under
measurement discipline v2 (PERF.md):

- single process, A and B INTERLEAVED trial-by-trial (the device clock
  drifts between runs; only same-process comparisons are trusted);
- each trial is a K-chained scan whose body threads a CHANGING carry
  (alternating src/dst plane parity and the mutated work buffer), so no
  two executions are bit-identical;
- every wall ends in a forced 1-element device_get;
- per-split time = (t_K - t_1) / (K - 1), best-of-R, which cancels the
  dispatch + sync overhead shared by both chain lengths.

This is the validation gate for the tpu_split_kernel auto knob: auto
stays "off" until a v5e session runs this script, confirms the Mosaic
lowering of the in-kernel scan tail and a wall win, and flips the knob
(or lets the run ledger carry the measured answer forward).

On a TPU backend the kernels run natively; elsewhere they are skipped
unless LGBTPU_PALLAS_INTERPRET=1 (interpreter numbers are
correctness-only — never quote them as perf).

Usage: python scripts/split_bisect.py [n_rows] [num_feat] [train_rows]
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
from lightgbm_tpu.ops.histogram import hist16_segment_planes
from lightgbm_tpu.ops.split import FeatureMeta, SplitHyper, find_best_split

CH = 1024        # partition chunk (pallas optimum, PERF.md round 5)
HCH = 2048       # histogram chunk (one-kernel DMA window)
NUM_BIN = 64
REPS = 5
K = 4


def build_inputs(n, f, seed=0):
    rng = np.random.RandomState(seed)
    guard = max(P.guard_rows(CH), CH + 2 * P.PLANE_ALIGN,
                HCH + 2 * P.PLANE_ALIGN)
    npad = max(P.planes_npad(n, guard, "pallas"),
               ((n + 2 * guard + 127) // 128) * 128)
    bins = jnp.asarray(rng.randint(0, NUM_BIN, (n, f)).astype(np.uint8))
    ghc = rng.randn(n, 3).astype(np.float32)
    ghc[:, 2] = 1.0
    ghc = jnp.asarray(ghc)
    _, w_pl = P.work_spec(f, False, "pallas", CH, HCH, layout="planes")
    work = jnp.zeros((2, w_pl, npad), jnp.uint8)
    work, root = P.pack_planes_fold_root(work, bins, ghc, guard,
                                         num_bins=NUM_BIN, exact=True,
                                         chunk=HCH)
    meta = FeatureMeta(
        num_bins=jnp.full((f,), NUM_BIN, jnp.int32),
        movable_missing=jnp.zeros((f,), bool),
        missing_bin=jnp.zeros((f,), jnp.int32),
        is_categorical=jnp.zeros((f,), bool),
        monotone=jnp.zeros((f,), jnp.int8),
        penalty=jnp.ones((f,), jnp.float32),
        cegb_coupled=jnp.zeros((f,), jnp.float32))
    hp = SplitHyper(min_data_in_leaf=2.0)
    fmask = jnp.ones((f,), bool)
    info0 = find_best_split(root, jnp.sum(ghc, axis=0), meta, fmask, hp)
    return work, root, guard, meta, hp, fmask, info0


def make_three_launch(work, root, guard, meta, hp, fmask, info0, n, f):
    """B: the retained oracle — partition launch, smaller-child histogram
    launch, split-scan launch (exactly what the learner's off path runs)."""
    ls = info0.left_sum[2] <= info0.right_sum[2]
    sums2 = jnp.stack([info0.left_sum, info0.right_sum])
    aux = (jnp.zeros((2,), jnp.float32),
           jnp.full((2,), -jnp.inf, jnp.float32),
           jnp.full((2,), jnp.inf, jnp.float32))
    scan = jax.vmap(lambda hg, tg, po, lo, up: find_best_split(
        hg, tg, meta, fmask, hp, parent_output=po, leaf_lower=lo,
        leaf_upper=up, node_depth=jnp.int32(1)))

    def make(k):
        @jax.jit
        def run(work):
            def body(carry, _):
                w, c, acc = carry
                w, lt = P.partition_segment_planes_fused(
                    w, c % 2, jnp.int32(guard), jnp.int32(n),
                    info0.feature, info0.go_left, ch=CH)
                ss = jnp.where(ls, jnp.int32(guard), jnp.int32(guard) + lt)
                sc = jnp.where(ls, lt, jnp.int32(n) - lt)
                hs = hist16_segment_planes(w, 1 - c % 2, ss, sc,
                                           num_bins=NUM_BIN, num_feat=f,
                                           chunk=HCH)
                hg = root - hs
                hl = jnp.where(ls, hs, hg)
                hr = jnp.where(ls, hg, hs)
                infos = scan(jnp.stack([hl, hr]), sums2, *aux)
                return (w, 1 - c, acc + infos.gain[0]), None
            (w, _, acc), _ = jax.lax.scan(
                body, (work, jnp.int32(0), jnp.float32(0)), None, length=k)
            return w.reshape(-1)[:1], acc
        return lambda: run(work)
    return make


def make_one_kernel(work, root, guard, meta, hp, fmask, info0, n, f):
    """A: the fused op — one pallas_call per split."""
    ls = info0.left_sum[2] <= info0.right_sum[2]
    sums2 = jnp.stack([info0.left_sum, info0.right_sum])
    aux = (jnp.zeros((2,), jnp.float32),
           jnp.full((2,), -jnp.inf, jnp.float32),
           jnp.full((2,), jnp.inf, jnp.float32))

    def make(k):
        @jax.jit
        def run(work):
            def body(carry, _):
                w, c, acc = carry
                w, _lt, _hl, _hr, infos = P.one_kernel_split_planes(
                    w, c % 2, jnp.int32(guard), jnp.int32(n), info0.feature,
                    info0.go_left, ls, jnp.int32(1), root, meta, fmask,
                    sums2, *aux, hp, num_bins=NUM_BIN, num_feat=f,
                    ch=CH, hist_chunk=HCH)
                return (w, 1 - c, acc + infos.gain[0]), None
            (w, _, acc), _ = jax.lax.scan(
                body, (work, jnp.int32(0), jnp.float32(0)), None, length=k)
            return w.reshape(-1)[:1], acc
        return lambda: run(work)
    return make


def train_wall(split_kernel, n, f, iters=10, seed=3):
    """Wall of one warm `lgb.train` with the knob forced on/off (the fused
    trainer, sampling and transfers all ride in)."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X @ rng.randn(f) > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 255, "max_bin": NUM_BIN,
              "verbosity": -1, "tpu_iter_block": 5,
              "tpu_work_layout": "planes", "tpu_partition_kernel": "pallas",
              "tpu_split_kernel": split_kernel}
    ds = lgb.Dataset(X, label=y)
    ds.construct()
    lgb.train(dict(params), ds, num_boost_round=5)        # warmup/compile
    def run():
        with obs.wall("bisect/train_split_" + split_kernel,
                      record=False) as w:
            bst = lgb.train(dict(params), ds, num_boost_round=iters)
            obs.sync(bst.inner.train_score.score)   # trusted wall end
        return w.seconds
    return run


def main(n, f, train_n):
    backend = jax.default_backend()
    pallas_ok = runtime.on_tpu() or P._INTERPRET
    if not pallas_ok:
        print(f"backend={backend}: no Mosaic and LGBTPU_PALLAS_INTERPRET "
              "unset — nothing to bisect (both arms need the pallas "
              "partition stream). Exiting.")
        return
    work, root, guard, meta, hp, fmask, info0 = build_inputs(n, f)
    print(f"backend={backend} n={n} F={f} planes_w={work.shape[1]} "
          f"guard={guard} bins={NUM_BIN}"
          + (" [INTERPRET — correctness only, not perf]"
             if P._INTERPRET and not runtime.on_tpu() else ""))

    args = (work, root, guard, meta, hp, fmask, info0, n, f)
    res = obs.ab_interleaved(
        [("split/three_launch", make_three_launch(*args)),
         ("split/one_kernel", make_one_kernel(*args))],
        reps=REPS, k=K)
    print()
    for name, per in res.items():
        print(f"{name:24s} {per * 1e3:8.3f} ms/split  "
              f"({n / per / 1e6:7.1f} M rows/s)")
    base = res.get("split/three_launch")
    one = res.get("split/one_kernel")
    if base and one:
        verdict = ("WIN — flip tpu_split_kernel auto to on"
                   if base / one > 1.02 else "NO WIN — keep auto=off")
        print(f"\none-kernel speedup: {base / one:.2f}x ({verdict})")

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
