"""Measure the fused partition kernel's fixed per-call cost.

Chains many partition calls at several segment sizes in ONE jit; the
per-call time vs cnt line gives (fixed, per-row) directly.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from lightgbm_tpu import obs

from lightgbm_tpu.runtime import enable_compile_cache
enable_compile_cache()

from lightgbm_tpu.ops.partition import (guard_rows, pack_rows,
                                        partition_segment_fused, work_spec)

N = int(os.environ.get("PN", 1 << 21))
F = 28
CH = int(os.environ.get("PCH", 1024))
REPS = 254

rng = np.random.RandomState(0)
bins = rng.randint(0, 255, size=(N, F), dtype=np.uint8)
ghc = rng.randn(N, 3).astype(np.float32)
guard, width = work_spec(F, False, "pallas", CH, 4096)
pad = ((guard, guard), (0, 0))
w0 = pack_rows(jnp.pad(jnp.asarray(bins), pad), jnp.pad(jnp.asarray(ghc), pad))
w0 = jnp.pad(w0, ((0, 0), (0, width - w0.shape[1])))
work = jnp.stack([w0, jnp.zeros_like(w0)])
table = jnp.asarray(rng.rand(255) < 0.5)


@jax.jit
def chain(work, cnt):
    def body(i, carry):
        work, tot = carry
        work, lt = partition_segment_fused(
            work, jax.lax.rem(i, 2), jnp.int32(guard), cnt,
            jax.lax.rem(i, F), table, ch=CH)
        return work, tot + lt

    return jax.lax.fori_loop(0, REPS, body, (work, jnp.int32(0)))


for cnt in (256, 1024, 4096, 16384, 65536, 262144):
    obs.sync(chain(work, jnp.int32(cnt)))
    best = 1e9
    for _ in range(3):
        with obs.wall("part_fixed/chain", record=False) as w:
            obs.sync(chain(work, jnp.int32(cnt)))
        best = min(best, w.seconds)
    per = best / REPS * 1e6
    print("cnt=%7d  %8.1f us/call  (%5.2f ns/row)" %
          (cnt, per, per * 1e3 / cnt))
