"""Correctness + speed of the fused Pallas partition kernel vs the XLA path."""
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

from lightgbm_tpu.ops.partition import (pack_rows, partition_segment,
                                        partition_segment_fused, unpack_ghc)

CH = 2048


def check(n, start_off, cnt, seed=0):
    rng = np.random.RandomState(seed)
    F, B = 28, 256
    bins = jnp.asarray(rng.randint(0, B, size=(n, F)), jnp.uint8)
    ghc = jnp.asarray(rng.randn(n, 3).astype(np.float32))
    guard = CH + 64
    work0 = pack_rows(jnp.pad(bins, ((guard, guard), (0, 0))),
                      jnp.pad(ghc, ((guard, guard), (0, 0))))
    work = jnp.stack([work0, jnp.zeros_like(work0)])
    work128 = jnp.pad(work, ((0, 0), (0, 0), (0, 128 - work.shape[2])))
    table = jnp.asarray(rng.rand(B) < 0.4)
    feat = jnp.int32(rng.randint(F))
    start = jnp.int32(guard + start_off)
    cntj = jnp.int32(cnt)

    w_ref, lt_ref = jax.jit(partial(partition_segment, ch=CH))(
        work, jnp.int32(0), start, cntj, feat, table)
    w_pal, lt_pal = jax.jit(partial(partition_segment_fused, ch=CH))(
        work128, jnp.int32(0), start, cntj, feat, table)
    lt_ref, lt_pal = int(lt_ref), int(lt_pal)
    assert lt_ref == lt_pal, (lt_ref, lt_pal)
    # left segments must match exactly (stable); right segments are
    # chunk-reversed in both, so compare as row SETS via sorted bytes
    a = np.asarray(w_ref[1])[guard + start_off: guard + start_off + cnt]
    b = np.asarray(w_pal[1])[guard + start_off: guard + start_off + cnt, :w_ref.shape[2]]
    np.testing.assert_array_equal(a[:lt_ref], b[:lt_ref])
    ra = a[lt_ref:]
    rb = b[lt_ref:]
    order_a = np.lexsort(ra.T)
    order_b = np.lexsort(rb.T)
    np.testing.assert_array_equal(ra[order_a], rb[order_b])
    print(f"ok n={n} cnt={cnt} lt={lt_ref}")


# trusted wall per PERF.md discipline: warm once, then time one call
# ended by a forced 1-element transfer (obs.timed_sync)
timed = obs.timed_sync


def chain(make, K=4):
    f1, fK = make(1), make(K)
    t1 = min(timed(f1), timed(f1)); tK = min(timed(fK), timed(fK))
    return (tK - t1) / (K - 1)


def bench(n):
    rng = np.random.RandomState(0)
    F, B = 28, 256
    bins = jnp.asarray(rng.randint(0, B, size=(n, F)), jnp.uint8)
    ghc = jnp.asarray(rng.randn(n, 3).astype(np.float32))
    guard = CH + 64
    work0 = pack_rows(jnp.pad(bins, ((guard, guard), (0, 0))),
                      jnp.pad(ghc, ((guard, guard), (0, 0))))
    work = jnp.stack([work0, jnp.zeros_like(work0)])
    work128 = jnp.pad(work, ((0, 0), (0, 0), (0, 128 - work.shape[2])))
    table = jnp.asarray(rng.rand(B) < 0.5)

    for name, fn, wk in (("xla", partition_segment, work),
                         ("pallas", partition_segment_fused, work128)):
        def make(k, fn=fn, work=wk):
            @jax.jit
            def f(work):
                def body(carry, _):
                    w, c = carry
                    w2, lt = fn(w, c % 2, jnp.int32(guard), jnp.int32(n),
                                jnp.int32(3), table, ch=CH)
                    return (w2, 1 - c), None
                (w, _), _ = jax.lax.scan(body, (work, jnp.int32(0)), None, length=k)
                return w[0, 0, 0]
            return lambda: f(work)
        per = chain(make, K=4)
        nch = (n + CH - 1) // CH
        print(f"{name} n={n}: {per*1e3:.2f} ms ({n/per/1e6:.0f} M rows/s, "
              f"{per/nch*1e6:.1f} us/chunk)")


if __name__ == "__main__":
    check(10000, 0, 10000)
    check(10000, 1000, 3000, seed=1)
    check(5000, 100, 1, seed=2)
    check(300000, 7, 299000, seed=3)
    bench(2_000_000)
