"""Interleaved A/B: rows (2, Npad, W) vs planes (2, W, Npad) work layout.

The hardware harness behind the ``tpu_work_layout`` and
``tpu_partition_kernel`` auto knobs: it times the XLA and the Pallas
partition in both layouts side by side.

Measures the three hot paths the layout change touches — partition,
segment histogram, and pack + root histogram — under measurement discipline v2
(PERF.md):

- single process, A and B INTERLEAVED trial-by-trial (the device clock
  drifts between runs; only same-process comparisons are trusted);
- each trial is a K-chained scan whose body threads a CHANGING carry
  (alternating src/dst plane parity and the mutated work buffer), so no
  two executions are bit-identical;
- every wall ends in a forced 1-element device_get (`np.asarray(..)[:1]`);
- per-op time = (t_K - t_1) / (K - 1), best-of-R, which cancels the
  dispatch + sync overhead shared by both chain lengths.

On a TPU backend the pallas kernels run natively; elsewhere they are
skipped unless LGBTPU_PALLAS_INTERPRET=1 (interpreter numbers are
correctness-only — never quote them as perf).

Usage: python scripts/layout_bisect.py [n_rows] [num_feat]
"""
import os
import sys
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from lightgbm_tpu import runtime
runtime.enable_compile_cache()

from lightgbm_tpu import obs
from lightgbm_tpu.ops import partition as P
from lightgbm_tpu.ops.histogram import hist16_segment, hist16_segment_planes

CH = 1024        # partition chunk (pallas optimum, PERF.md round 5)
HCH = 4096       # histogram chunk
REPS = 5
K = 4


def chain_per_op(make):
    """Best-of-REPS (t_K - t_1)/(K - 1) for one chained-scan bench."""
    return obs.ab_interleaved([("x", make)], reps=REPS, k=K)["x"]


def build_inputs(n, f, num_bin=256, seed=0):
    rng = np.random.RandomState(seed)
    guard_r = P.guard_rows(CH)
    guard_p = CH + 2 * P.PLANE_ALIGN
    guard = max(guard_r, guard_p)
    npad_p = ((n + 2 * guard + 127) // 128) * 128
    bins = np.zeros((npad_p, f), np.uint8)
    bins[guard:guard + n] = rng.randint(0, num_bin, (n, f))
    ghc = np.zeros((npad_p, 3), np.float32)
    ghc[guard:guard + n] = rng.randn(n, 3).astype(np.float32)
    ghc[guard:guard + n, 2] = 1.0
    w_r = P.pack_rows(jnp.asarray(bins), jnp.asarray(ghc))
    if w_r.shape[1] % 128:           # rows pallas kernel wants 128-mult width
        w_r = jnp.pad(w_r, ((0, 0), (0, 128 - w_r.shape[1] % 128)))
    w_p = P.pack_planes(jnp.asarray(bins), jnp.asarray(ghc))
    wpad = (-w_p.shape[0]) % 32
    if wpad:
        w_p = jnp.pad(w_p, ((0, wpad), (0, 0)))
    work_r = jnp.stack([w_r, jnp.zeros_like(w_r)])
    work_p = jnp.stack([w_p, jnp.zeros_like(w_p)])
    table = jnp.asarray(rng.rand(num_bin) < 0.5)
    return work_r, work_p, table, guard, bins, ghc


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


def hist_make(fn, work, guard, n, f_real, shift):
    def make(k):
        @jax.jit
        def f(work):
            def body(carry, _):
                s, acc = carry
                h = fn(work, jnp.int32(0), jnp.int32(guard + s % 64),
                       jnp.int32(n - 64), num_bins=256, num_feat=f_real,
                       chunk=HCH)
                return (s + shift, acc + h[0, 0, 0]), None
            (_, acc), _ = jax.lax.scan(body, (jnp.int32(0), jnp.float32(0)),
                                       None, length=k)
            return acc.reshape(1)
        return lambda: f(work)
    return make


def pack_make_rows(bins, ghc, guard, n, f_real, work_shape):
    binsd, ghcd = jnp.asarray(bins), jnp.asarray(ghc)

    def make(k):
        @jax.jit
        def f(b, g):
            def body(carry, _):
                s, acc = carry
                w0 = P.pack_rows(b, g + s)          # changing carry -> no dedup
                work = jnp.zeros(work_shape, jnp.uint8).at[
                    0, :, :w0.shape[1]].set(w0)
                h = hist16_segment(work, jnp.int32(0), jnp.int32(guard),
                                   jnp.int32(n), num_bins=256,
                                   num_feat=f_real, chunk=HCH)
                return (s + 1.0, acc + h[0, 0, 0]), None
            (_, acc), _ = jax.lax.scan(body, (jnp.float32(0),
                                              jnp.float32(0)), None, length=k)
            return acc.reshape(1)
        return lambda: f(binsd, ghcd)
    return make


def pack_make_planes(bins, ghc, guard, n, f_real, work_shape):
    binsd = jnp.asarray(bins[guard:guard + n])
    ghcd = jnp.asarray(ghc[guard:guard + n])

    def make(k):
        @jax.jit
        def f(b, g):
            def body(carry, _):
                s, acc = carry
                work = jnp.zeros(work_shape, jnp.uint8)
                work = P.pack_planes_write(work, b, g + s, guard,
                                           chunk=HCH)
                h = hist16_segment_planes(work, jnp.int32(0),
                                          jnp.int32(guard), jnp.int32(n),
                                          num_bins=256, num_feat=f_real,
                                          chunk=HCH)
                return (s + 1.0, acc + h[0, 0, 0]), None
            (_, acc), _ = jax.lax.scan(body, (jnp.float32(0),
                                              jnp.float32(0)), None, length=k)
            return acc.reshape(1)
        return lambda: f(binsd, ghcd)
    return make


def main(n, f):
    backend = jax.default_backend()
    pallas_ok = runtime.on_tpu() or P._INTERPRET
    work_r, work_p, table, guard, bins, ghc = build_inputs(n, f)
    print(f"backend={backend} n={n} F={f} row_w={work_r.shape[2]} "
          f"planes_w={work_p.shape[1]} guard={guard} "
          f"(pallas {'on' if pallas_ok else 'SKIPPED — no TPU'})")

    pairs = [
        ("part/rows/xla",
         part_make(P.partition_segment, work_r, guard, n, table, CH)),
        ("part/planes/xla",
         part_make(P.partition_segment_planes, work_p, guard, n, table, CH)),
    ]
    if pallas_ok:
        pairs += [
            ("part/rows/pallas",
             part_make(P.partition_segment_fused, work_r, guard, n, table,
                       CH)),
            ("part/planes/pallas",
             part_make(P.partition_segment_planes_fused, work_p, guard, n,
                       table, CH)),
        ]
    pairs += [
        ("hist/rows/xla",
         hist_make(hist16_segment, work_r, guard, n, f, 1)),
        ("hist/planes/xla",
         hist_make(hist16_segment_planes, work_p, guard, n, f, 1)),
        ("pack+root/rows",
         pack_make_rows(bins, ghc, guard, n, f, work_r.shape)),
        ("pack+root/planes",
         pack_make_planes(bins, ghc, guard, n, f, work_p.shape)),
    ]
    res = obs.ab_interleaved(pairs, reps=REPS, k=K)
    for name, per in res.items():
        print(f"{name:28s} {per * 1e3:8.3f} ms  ({n / per / 1e6:7.1f} M rows/s)")
    for stem in ("part", "hist", "pack+root"):
        rows = {k: v for k, v in res.items() if k.startswith(stem)}
        base = rows.get(f"{stem}/rows/xla") or rows.get(f"{stem}/rows")
        if base:
            for k, v in rows.items():
                print(f"  {k:26s} {base / v:5.2f}x vs {stem} rows baseline")


if __name__ == "__main__":
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 2_000_000
    f = int(sys.argv[2]) if len(sys.argv) > 2 else 28
    main(n, f)
