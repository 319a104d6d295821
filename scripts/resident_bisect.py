"""Interleaved A/B: resident slim payload vs planes vs rows work layouts.

Measures the per-split hot paths the resident state changes — partition
(route pre-pass + slim payload move vs full packed-row move) and segment
histogram (gather through the permuted ridx plane vs unit-stride payload
read) — plus a full-train wall per layout, under measurement discipline v2
(PERF.md):

- single process, A and B INTERLEAVED trial-by-trial (the device clock
  drifts between runs; only same-process comparisons are trusted);
- each trial is a K-chained scan whose body threads a CHANGING carry
  (alternating src/dst plane parity and the mutated work buffer), so no
  two executions are bit-identical;
- every wall ends in a forced 1-element device_get (`np.asarray(..)[:1]`);
- per-op time = (t_K - t_1) / (K - 1), best-of-R, which cancels the
  dispatch + sync overhead shared by both chain lengths.

Also prints the deterministic bytes-moved-per-row traffic table (the
CPU-measurable half of the acceptance bar: the resident partition must
move >= 2x less data per split than planes at F=28).

On a TPU backend the pallas kernels run natively; elsewhere they are
skipped unless LGBTPU_PALLAS_INTERPRET=1 (interpreter numbers are
correctness-only — never quote them as perf).

Usage: python scripts/resident_bisect.py [n_rows] [num_feat] [train_rows]
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
from lightgbm_tpu.ops.histogram import (
    hist16_segment, hist16_segment_planes, hist16_segment_resident)

CH = 1024        # partition chunk (pallas optimum, PERF.md round 5)
HCH = 4096       # histogram chunk
REPS = 5
K = 4


def build_inputs(n, f, num_bin=256, seed=0):
    rng = np.random.RandomState(seed)
    guard = max(P.guard_rows(CH), CH + 2 * P.PLANE_ALIGN)
    npad = ((n + 2 * guard + 127) // 128) * 128
    bins_pad = np.zeros((npad, f), np.uint8)
    bins_pad[guard:guard + n] = rng.randint(0, num_bin, (n, f))
    ghc_pad = np.zeros((npad, 3), np.float32)
    ghc_pad[guard:guard + n] = rng.randn(n, 3).astype(np.float32)
    ghc_pad[guard:guard + n, 2] = 1.0
    bins = jnp.asarray(bins_pad[guard:guard + n])
    ghc = jnp.asarray(ghc_pad[guard:guard + n])

    w_r = P.pack_rows(jnp.asarray(bins_pad), jnp.asarray(ghc_pad))
    if w_r.shape[1] % 128:           # rows pallas kernel wants 128-mult width
        w_r = jnp.pad(w_r, ((0, 0), (0, 128 - w_r.shape[1] % 128)))
    work_r = jnp.stack([w_r, jnp.zeros_like(w_r)])

    w_p = P.pack_planes(jnp.asarray(bins_pad), jnp.asarray(ghc_pad))
    wpad = (-w_p.shape[0]) % 32
    if wpad:
        w_p = jnp.pad(w_p, ((0, wpad), (0, 0)))
    work_p = jnp.stack([w_p, jnp.zeros_like(w_p)])

    res = P.resident_bin_planes(bins, guard, npad)
    _, w_rs = P.work_spec(f, False, "pallas", CH, HCH, layout="resident")
    work_s = jnp.zeros((2, w_rs, npad), jnp.uint8)
    work_s, _ = P.pack_resident_fold_root(
        work_s, bins, ghc, guard, num_bins=num_bin, exact=True, chunk=HCH)

    table = jnp.asarray(rng.rand(num_bin) < 0.5)
    return work_r, work_p, work_s, res, table, guard


def part_make(fn, work, guard, n, table, ch):
    def make(k):
        @jax.jit
        def f(work):
            def body(carry, _):
                w, c = carry
                w2, _lt = fn(w, c % 2, jnp.int32(guard), jnp.int32(n),
                             jnp.int32(3), table, ch=ch)
                return (w2, 1 - c), None
            (w, _), _ = jax.lax.scan(body, (work, jnp.int32(0)), None,
                                     length=k)
            return w.reshape(-1)[:1]
        return lambda: f(work)
    return make


def part_make_resident(fn, work, res, guard, n, table, ch):
    """Resident partition = route-plane gather pre-pass + the SAME planes
    partition (XLA or fused Mosaic) routing on plane 0 (feat=0)."""
    def make(k):
        @jax.jit
        def f(work, res):
            def body(carry, _):
                w, c = carry
                w = P.write_route_plane(w, res, c % 2, jnp.int32(guard),
                                        jnp.int32(n), jnp.int32(3), ch=ch)
                w2, _lt = fn(w, c % 2, jnp.int32(guard), jnp.int32(n),
                             jnp.int32(0), table, ch=ch)
                return (w2, 1 - c), None
            (w, _), _ = jax.lax.scan(body, (work, jnp.int32(0)), None,
                                     length=k)
            return w.reshape(-1)[:1]
        return lambda: f(work, res)
    return make


def hist_make(fn, work, guard, n, f_real, shift, *extra):
    def make(k):
        @jax.jit
        def f(work, *extra):
            def body(carry, _):
                s, acc = carry
                h = fn(work, *extra, jnp.int32(0),
                       jnp.int32(guard + s % 64), jnp.int32(n - 64),
                       num_bins=256, num_feat=f_real, chunk=HCH)
                return (s + shift, acc + h[0, 0, 0]), None
            (_, acc), _ = jax.lax.scan(body, (jnp.int32(0), jnp.float32(0)),
                                       None, length=k)
            return acc.reshape(1)
        return lambda: f(work, *extra)
    return make


def train_wall(layout, resident, n, f, iters=10, seed=3):
    """Wall of one warm `lgb.train` at the given layout (high-level API:
    the fused trainer, sampling, split scan and transfers all ride in)."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X @ rng.randn(f) > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 255, "max_bin": 255,
              "verbosity": -1, "tpu_iter_block": 5,
              "tpu_work_layout": layout,
              "tpu_resident_state": "on" if resident else "off"}
    ds = lgb.Dataset(X, label=y)
    ds.construct()
    lgb.train(dict(params), ds, num_boost_round=5)        # warmup/compile
    def run():
        with obs.wall("bisect/train_" + ("resident" if resident else layout),
                      record=False) as w:
            bst = lgb.train(dict(params), ds, num_boost_round=iters)
            obs.sync(bst.inner.train_score.score)   # trusted wall end
        return w.seconds
    return run


def main(n, f, train_n):
    backend = jax.default_backend()
    pallas_ok = runtime.on_tpu() or P._INTERPRET
    work_r, work_p, work_s, res, table, guard = build_inputs(n, f)
    print(f"backend={backend} n={n} F={f} row_w={work_r.shape[2]} "
          f"planes_w={work_p.shape[1]} resident_w={work_s.shape[1]} "
          f"guard={guard} (pallas {'on' if pallas_ok else 'SKIPPED — no TPU'})")

    # ---- deterministic traffic table (bytes per parent row per split) ----
    print("\ntraffic (bytes moved per parent row per split, XLA widths):")
    w_rows = f + P.GH_BYTES
    w_planes = f + P.GH_BYTES
    w_res = P.RST_WIDTH
    rows = [("rows", 2 * w_rows, w_rows),
            ("planes", 2 * w_planes, w_planes),
            ("resident", 2 * w_res + P.RST_GH_OFF + 1, w_res + f)]
    for name, part_b, hist_b in rows:
        print(f"  {name:10s} partition={part_b:4d} B/row   "
              f"hist={hist_b:4d} B/row")
    cut = rows[1][1] / rows[2][1]
    print(f"  resident partition cut vs planes: {cut:.2f}x "
          f"({'MEETS' if cut >= 2.0 else 'BELOW'} the >=2x acceptance bar)")

    # ---- kernel-level interleaved A/B ----
    pairs = [
        ("part/rows/xla",
         part_make(P.partition_segment, work_r, guard, n, table, CH)),
        ("part/planes/xla",
         part_make(P.partition_segment_planes, work_p, guard, n, table, CH)),
        ("part/resident/xla",
         part_make_resident(P.partition_segment_planes, work_s, res, guard,
                            n, table, CH)),
    ]
    if pallas_ok:
        pairs += [
            ("part/planes/pallas",
             part_make(P.partition_segment_planes_fused, work_p, guard, n,
                       table, CH)),
            ("part/resident/pallas",
             part_make_resident(P.partition_segment_planes_fused, work_s,
                                res, guard, n, table, CH)),
        ]
    pairs += [
        ("hist/rows/xla",
         hist_make(hist16_segment, work_r, guard, n, f, 1)),
        ("hist/planes/xla",
         hist_make(hist16_segment_planes, work_p, guard, n, f, 1)),
        ("hist/resident/xla",
         hist_make(hist16_segment_resident, work_s, guard, n, f, 1, res)),
    ]
    res_t = obs.ab_interleaved(pairs, reps=REPS, k=K)
    print()
    for name, per in res_t.items():
        print(f"{name:24s} {per * 1e3:8.3f} ms  ({n / per / 1e6:7.1f} M rows/s)")
    for stem in ("part", "hist"):
        base = res_t.get(f"{stem}/planes/xla")
        if base:
            for k, v in res_t.items():
                if k.startswith(stem):
                    print(f"  {k:22s} {base / v:5.2f}x vs {stem} planes/xla")

    # ---- full-train wall, interleaved across layouts ----
    if train_n > 0:
        runs = [("train/rows", train_wall("rows", False, train_n, f)),
                ("train/planes", train_wall("planes", False, train_n, f)),
                ("train/resident", train_wall("planes", True, train_n, f))]
        best = {name: np.inf for name, _ in runs}
        for _ in range(3):
            for name, run in runs:           # A, B, C, A, B, C per rep
                best[name] = min(best[name], run())
        print()
        for name, w in best.items():
            print(f"{name:24s} {w:8.3f} s  (10 iters, n={train_n})")


if __name__ == "__main__":
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 2_000_000
    f = int(sys.argv[2]) if len(sys.argv) > 2 else 28
    train_n = int(sys.argv[3]) if len(sys.argv) > 3 else 300_000
    main(n, f, train_n)
