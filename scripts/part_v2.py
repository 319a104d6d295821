"""Correctness + perf harness for the v2 fused partition kernel
(lightgbm_tpu/ops/partition.py _partition_kernel). Run on TPU.

Design vs v1 (ops/partition.py _partition_kernel):
- compaction permutation matmuls at SB=256 instead of CH (8x less MXU work
  per row: the perm cost is CH*W MACs/row);
- left/right frontier rows accumulate in circular VMEM stages (2*CH + CH
  physical rows; the top CH is a wrap margin) and flush to HBM as ALIGNED
  PURE WRITES of CH rows — no per-chunk read-modify-write windows and no
  lout.wait()/rin serialization;
- neighbor bytes at the aligned edges are prefilled once per call; the
  final sub-CH leftovers drain as full tiles plus one overlapping RMW tile.

Row order inside a leaf segment is insignificant (histograms are
order-free; sub-splits re-partition), and the kernel preserves exactly the
SET of rows per side; neighbor rows outside [start, start+cnt) are
byte-preserved.
"""
import os
import sys
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from lightgbm_tpu import obs
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lightgbm_tpu.runtime import enable_compile_cache
enable_compile_cache()

ALIGN = 32

from lightgbm_tpu.ops.partition import partition_segment_fused


def partition_segment_v2(work, src_plane, start, cnt, feat, go_left, *,
                         ch=1024, sb=256):
    """The integrated library kernel (ops/partition.py) under test."""
    return partition_segment_fused(work, src_plane, start, cnt, feat,
                                   go_left, ch=ch, sb=sb)


# ---------------------------------------------------------------- testing

def ref_partition(work_np, plane, start, cnt, feat, table):
    """NumPy reference: stable set-preserving partition."""
    seg = work_np[plane, start:start + cnt]
    go = table[seg[:, feat].astype(np.int64)]
    left = seg[go]
    right = seg[~go]
    out = work_np.copy()
    out[1 - plane, start:start + cnt] = np.concatenate([left, right], axis=0)
    return out, len(left)


def main():
    print("devices:", jax.devices())
    rng = np.random.RandomState(0)
    ch = int(os.environ.get("CH", 1024))
    sb = int(os.environ.get("SB", 256))
    W = int(os.environ.get("W", 128))
    F = 28
    B = 256
    guard = ch + 2 * ALIGN
    jit_part = jax.jit(partial(partition_segment_v2, ch=ch, sb=sb))

    # correctness across many segment shapes
    N = 200_000
    npad = N + 2 * guard
    base = rng.randint(0, 256, size=(2, npad, W)).astype(np.uint8)
    table = (rng.rand(B) < 0.47)
    work = jnp.asarray(base)
    tab = jnp.asarray(table)
    ok = True
    for (start, cnt) in [(guard, N), (guard + 5, 33), (guard, 1),
                         (guard + 31, 2), (guard + 1000, 65536),
                         (guard + 7, 4096), (guard + 12345, 99991),
                         (guard + 3, ch - 1), (guard, ch),
                         (guard + 17, ch + 1), (guard, 2 * ch + 77)]:
        for plane in (0, 1):
            w2, lt = jit_part(work, jnp.int32(plane), jnp.int32(start),
                              jnp.int32(cnt), jnp.int32(3), tab)
            w2 = np.asarray(w2)
            refw, ref_lt = ref_partition(base, plane, start, cnt, 3, table)
            lt = int(lt)
            # left/right row SETS must match (order within side is free)
            got_l = w2[1 - plane, start:start + lt]
            got_r = w2[1 - plane, start + lt:start + cnt]
            ref_l = refw[1 - plane, start:start + lt]
            ref_r = refw[1 - plane, start + lt:start + cnt]
            def rowset(a):
                return set(map(bytes, a))
            sl = lt == ref_lt and rowset(got_l) == rowset(ref_l) \
                and rowset(got_r) == rowset(ref_r)
            # neighbor bytes preserved on the destination plane
            nb = (w2[1 - plane, :start] == base[1 - plane, :start]).all() \
                and (w2[1 - plane, start + cnt:]
                     == base[1 - plane, start + cnt:]).all()
            # source plane untouched
            sp = (w2[plane] == base[plane]).all()
            if not (sl and nb and sp):
                ok = False
                print(f"FAIL start={start} cnt={cnt} plane={plane}: "
                      f"lt={lt}/{ref_lt} sets={sl} neigh={nb} src={sp}")
    print("correctness:", "OK" if ok else "FAILED")
    if not ok:
        return

    # benchmark vs v1 at bench shape
    from lightgbm_tpu.ops.partition import partition_segment_fused
    N = 2_000_000
    npad = N + 2 * guard
    base = rng.randint(0, 256, size=(2, npad, W)).astype(np.uint8)
    work = jnp.asarray(base)

    # trusted wall per PERF.md discipline (obs.timed_sync): warm once,
    # then time one call ended by a forced 1-element transfer
    timed = obs.timed_sync

    def chain(K, fn, cnt, ch_):
        @jax.jit
        def f(work):
            def body(carry, _):
                w, c = carry
                w2, lt = fn(w, c % 2, jnp.int32(guard), jnp.int32(cnt),
                            jnp.int32(3), tab)
                return (w2, 1 - c), None
            (w, _), _ = jax.lax.scan(body, (work, jnp.int32(0)), None,
                                     length=K)
            return w[0, guard, 0]
        return lambda: f(work)

    for cnt in (N, 65536, 8192):
        for name, fn in (("v2", partial(partition_segment_v2, ch=ch, sb=sb)),):
            t1 = min(timed(chain(1, fn, cnt, ch)) for _ in range(3))
            tK = min(timed(chain(9, fn, cnt, ch)) for _ in range(3))
            per = (tK - t1) / 8
            print(f"{name} cnt={cnt}: {per*1e6:9.1f} us "
                  f"({per/cnt*1e9:6.2f} ns/row)")


if __name__ == "__main__":
    main()
