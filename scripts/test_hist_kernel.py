"""Correctness + speed: hist_pallas_segment vs the XLA einsum path."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from lightgbm_tpu import obs

from lightgbm_tpu.runtime import enable_compile_cache
enable_compile_cache()

from lightgbm_tpu.ops.histogram import hist16_segment, hist_pallas_segment
from lightgbm_tpu.ops.partition import pack_rows, work_spec

B = 256


def build(n, F, seed=0):
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, B, size=(n, F)).astype(np.uint8)
    ghc = rng.randn(n, 3).astype(np.float32)
    ghc[:, 2] = 1.0
    guard, width = work_spec(F, False, "pallas", 1024, 4096)
    pad = ((guard, guard), (0, 0))
    w0 = pack_rows(jnp.pad(jnp.asarray(bins), pad),
                   jnp.pad(jnp.asarray(ghc), pad))
    w0 = jnp.pad(w0, ((0, 0), (0, width - w0.shape[1])))
    return jnp.stack([w0, jnp.zeros_like(w0)]), guard


def check(n, F, start_off, cnt, chunk=4096):
    work, guard = build(n, F)
    args = (work, jnp.int32(0), jnp.int32(guard + start_off), jnp.int32(cnt))
    kw = dict(num_bins=B, num_feat=F, exact=True, chunk=chunk)
    ref = np.asarray(jax.jit(lambda *a: hist16_segment(*a, **kw))(*args))
    out = np.asarray(jax.jit(lambda *a: hist_pallas_segment(*a, **kw))(*args))
    same = np.array_equal(ref, out)
    close = np.allclose(ref, out, rtol=1e-6, atol=1e-4)
    print("n=%d F=%d off=%d cnt=%d: bitexact=%s close=%s maxdiff=%.3g"
          % (n, F, start_off, cnt, same, close, np.abs(ref - out).max()))
    assert close


def speed(n, F, chunk=4096, reps=60):
    work, guard = build(n, F)
    kw = dict(num_bins=B, num_feat=F, exact=True, chunk=chunk)

    def mk(fn):
        @jax.jit
        def chain(work):
            def body(i, acc):
                h = fn(work, jnp.int32(0), jnp.int32(guard), jnp.int32(n),
                       **kw)
                return acc + h[0, 0, 0]
            return jax.lax.fori_loop(0, reps, body, jnp.float32(0))
        obs.sync(chain(work))
        best = 1e9
        for _ in range(3):
            with obs.wall("test_hist_kernel/chain", record=False) as w:
                obs.sync(chain(work))
            best = min(best, w.seconds)
        return best / reps

    t_x = mk(hist16_segment)
    t_p = mk(hist_pallas_segment)
    print("n=%d F=%d chunk=%d: xla %.2f ms (%.2f ns/row)  pallas %.2f ms "
          "(%.2f ns/row)" % (n, F, chunk, t_x * 1e3, t_x / n * 1e9,
                             t_p * 1e3, t_p / n * 1e9))


if __name__ == "__main__":
    check(20000, 28, 0, 20000)
    check(20000, 28, 37, 12345)
    check(20000, 28, 1, 1)
    speed(2_000_000, 28)
    speed(2_000_000, 28, chunk=8192)
