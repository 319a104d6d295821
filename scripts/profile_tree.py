"""Decompose the partitioned tree builder's per-iteration cost on the TPU.

Chained-execution methodology (see calibrate.py): a host sync per
measurement would swamp the op, so each primitive is chained K times inside one jit
with a data dependency and per-op cost = (t_K - t_1)/(K-1).

Measures, at the bench shape (N=2M, F=28, B=256, L=255):
  - build_tree_partitioned end-to-end (ms per tree)
  - hist16_segment at several segment sizes (slope + fixed cost)
  - partition_segment at several segment sizes (slope + per-chunk cost)
  - find_best_split per call
"""
import os
import sys
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from lightgbm_tpu import obs

from lightgbm_tpu.runtime import enable_compile_cache
enable_compile_cache()

N = int(os.environ.get("PROF_N", 2_000_000))
F = 28
B = 256
L = int(os.environ.get("PROF_LEAVES", 255))


# trusted wall per PERF.md discipline: warm once, then time one call
# ended by a forced 1-element transfer (obs.timed_sync)
timed = obs.timed_sync


def chain_cost(make_chain, K=4):
    f1 = make_chain(1)
    fK = make_chain(K)
    t1 = min(timed(f1), timed(f1))
    tK = min(timed(fK), timed(fK))
    return (tK - t1) / (K - 1)


def main():
    from lightgbm_tpu.learner import (SerialTreeLearner, build_tree_partitioned)
    from lightgbm_tpu.ops.split import FeatureMeta, SplitHyper, find_best_split
    from lightgbm_tpu.ops.histogram import hist16_segment
    from lightgbm_tpu.ops.partition import (pack_rows, partition_segment,
                                            DEFAULT_CH)

    print("devices:", jax.devices())
    rng = np.random.RandomState(0)
    bins = jnp.asarray(rng.randint(0, B, size=(N, F)), jnp.uint8)
    g = rng.randn(N).astype(np.float32)
    h = np.abs(rng.randn(N)).astype(np.float32) + 0.1
    ghc = jnp.asarray(np.stack([g, h, np.ones(N, np.float32)], axis=1))
    meta = FeatureMeta(
        num_bins=jnp.full((F,), B, jnp.int32),
        movable_missing=jnp.zeros((F,), bool),
        missing_bin=jnp.zeros((F,), jnp.int32),
        is_categorical=jnp.zeros((F,), bool),
        monotone=jnp.zeros((F,), jnp.int8),
        penalty=jnp.ones((F,), jnp.float32),
        cegb_coupled=jnp.zeros((F,), jnp.float32),
    )
    hp = SplitHyper()
    fmask = jnp.ones((F,), bool)
    key = jax.random.PRNGKey(0)
    cegb_used = jnp.zeros((F,), bool)

    # ---------------- full tree ----------------
    def make_tree(k):
        @jax.jit
        def f(bins, ghc):
            def body(c, _):
                log = build_tree_partitioned(
                    bins, ghc + c * 1e-30, meta, fmask, key, cegb_used, hp,
                    num_leaves=L, num_bin=B)
                return jnp.float32(log.num_splits), None
            c, _ = jax.lax.scan(body, jnp.float32(0), None, length=k)
            return c
        return lambda: f(bins, ghc)

    per = chain_cost(make_tree, K=3)
    print(f"build_tree_partitioned N={N} L={L}: {per*1e3:.1f} ms/tree")

    # ---------------- histogram segment ----------------
    guard = DEFAULT_CH
    work0 = pack_rows(jnp.pad(bins, ((guard, guard), (0, 0))),
                      jnp.pad(ghc, ((guard, guard), (0, 0))))
    work = jnp.stack([work0, jnp.zeros_like(work0)])

    def make_hist(k, cnt):
        @jax.jit
        def f(work):
            def body(c, _):
                hg = hist16_segment(work, jnp.int32(0),
                                    jnp.int32(guard) + c.astype(jnp.int32) * 0,
                                    jnp.int32(cnt), num_bins=B, num_feat=F)
                return jnp.sum(hg) * 1e-30, None
            c, _ = jax.lax.scan(body, jnp.float32(0), None, length=k)
            return c
        return lambda: f(work)

    for cnt in (N, N // 4, 65536, 8192, 2048):
        per = chain_cost(partial(make_hist, cnt=cnt), K=4)
        print(f"hist16_segment cnt={cnt}: {per*1e3:.2f} ms "
              f"({cnt/per/1e6:.0f} M rows/s)")

    # ---------------- partition segment ----------------
    table = jnp.asarray(rng.rand(B) < 0.5)

    def make_part(k, cnt):
        @jax.jit
        def f(work):
            def body(carry, _):
                w, c = carry
                w2, lt = partition_segment(
                    w, c % 2, jnp.int32(guard), jnp.int32(cnt),
                    jnp.int32(3), table)
                return (w2, 1 - c), None
            (w, _), _ = jax.lax.scan(body, (work, jnp.int32(0)), None, length=k)
            return w[0, 0, 0]
        return lambda: f(work)

    for cnt in (N, N // 4, 65536, 8192, 2048):
        per = chain_cost(partial(make_part, cnt=cnt), K=4)
        nch = (cnt + DEFAULT_CH - 1) // DEFAULT_CH
        print(f"partition_segment cnt={cnt}: {per*1e3:.2f} ms "
              f"({cnt/per/1e6:.0f} M rows/s, {per/nch*1e6:.1f} us/chunk)")

    # ---------------- split scan ----------------
    hist = jnp.asarray(rng.randn(F, B, 3).astype(np.float32))
    psum = jnp.sum(hist, axis=(0, 1)) / F

    def make_split(k):
        @jax.jit
        def f(hist):
            def body(c, _):
                info = find_best_split(hist + c * 1e-30, psum, meta, fmask, hp)
                return info.gain * 1e-30, None
            c, _ = jax.lax.scan(body, jnp.float32(0), None, length=k)
            return c
        return lambda: f(hist)

    per = chain_cost(make_split, K=16)
    print(f"find_best_split (F={F},B={B}): {per*1e6:.0f} us/call")


if __name__ == "__main__":
    main()
