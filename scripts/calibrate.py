"""Calibrate TPU kernel costs with chained executions.

A host sync per measurement would swamp a sub-millisecond kernel, so every
measurement chains k executions inside one jit
(lax.scan with data dependency) and compares k=1 vs k=K to cancel the
fixed overhead: per-op = (t_K - t_1) / (K - 1).
"""
import os
import sys
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from lightgbm_tpu import obs


def timed(fn, *args):
    """Run once (compiled), sync via scalar transfer, return seconds."""
    fn(*args)
    return obs.timed_sync(lambda: fn(*args))


def chain_cost(make_chain, K=8):
    f1 = make_chain(1)
    fK = make_chain(K)
    t1 = min(timed(f1), timed(f1))
    tK = min(timed(fK), timed(fK))
    return (tK - t1) / (K - 1)


def main():
    print("devices:", jax.devices())
    rng = np.random.RandomState(0)

    # ---------- matmul sanity ----------
    a = jnp.asarray(rng.randn(8192, 8192), jnp.bfloat16)
    b = jnp.asarray(rng.randn(8192, 8192), jnp.bfloat16)

    def make_mm(k):
        @jax.jit
        def f(a, b):
            def body(c, _):
                return jnp.tanh(c @ b), None
            c, _ = jax.lax.scan(body, a, None, length=k)
            return c.sum()
        return lambda: f(a, b)

    per = chain_cost(make_mm)
    print(f"matmul 8192^3 bf16: {per*1e3:.2f} ms -> {2*8192**3/per/1e12:.1f} TFLOP/s")

    # ---------- histogram variants ----------
    from lightgbm_tpu.ops.histogram import build_histogram

    N, F, B = 2_000_000, 28, 256
    bins = jnp.asarray(rng.randint(0, B, size=(N, F)), jnp.uint8)
    ghc = jnp.asarray(rng.randn(N, 3), jnp.float32)

    def make_hist(k, chunk, mxu_bf16):
        @jax.jit
        def f(bins, ghc):
            def body(acc, i):
                h = build_histogram(bins, ghc + acc[0, 0, :][None], B, chunk,
                                    mxu_bf16=mxu_bf16)
                return h * 1e-9, None
            acc0 = jnp.zeros((F, B, 3), jnp.float32)
            acc, _ = jax.lax.scan(body, acc0, None, length=k)
            return acc.sum()
        return lambda: f(bins, ghc)

    for mxu_bf16 in (False, True):
        for chunk in (8192, 32768, 131072):
            per = chain_cost(partial(make_hist, chunk=chunk, mxu_bf16=mxu_bf16), K=4)
            print(f"hist einsum bf16={int(mxu_bf16)} chunk={chunk}: {per*1e3:.1f} ms "
                  f"({N/per/1e6:.0f} M rows/s, {N*F*B*3*2/per/1e12:.2f} TFLOP/s)")

    # ---------- gather ----------
    C = 65536
    idx0 = jnp.asarray(rng.randint(0, N, size=(C,)), jnp.int32)

    def make_gather(k):
        @jax.jit
        def f(idx):
            def body(carry, _):
                s, idx = carry
                g1 = bins[idx]
                g2 = ghc[idx]
                s2 = s + g1.astype(jnp.float32).sum() + g2.sum()
                idx2 = (idx + 1) % N
                return (s2, idx2), None
            (s, _), _ = jax.lax.scan(body, (jnp.float32(0), idx), None, length=k)
            return s
        return lambda: f(idx0)

    per = chain_cost(make_gather, K=16)
    print(f"gather {C} rows (F=28 u8 + 3 f32): {per*1e3:.2f} ms "
          f"({C/per/1e6:.0f} M rows/s)")

    # ---------- compaction ----------
    mask0 = jnp.asarray(rng.rand(N) < 0.25)

    def make_compact(k, how):
        @jax.jit
        def f(mask):
            def body(carry, _):
                s, mask = carry
                if how == "scatter":
                    pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
                    buf = jnp.zeros((N,), jnp.int32)
                    buf = buf.at[jnp.where(mask, pos, N)].set(
                        jnp.arange(N, dtype=jnp.int32), mode="drop")
                    out = buf
                elif how == "argsort":
                    out = jnp.argsort(~mask, stable=True).astype(jnp.int32)
                else:
                    out = jnp.cumsum(mask.astype(jnp.int32))
                s2 = s + out[0] + out[-1]
                return (s2, jnp.roll(mask, 1)), None
            (s, _), _ = jax.lax.scan(body, (jnp.int32(0), mask), None, length=k)
            return s
        return lambda: f(mask0)

    for how in ("cumsum", "scatter", "argsort"):
        per = chain_cost(partial(make_compact, how=how), K=4)
        print(f"compact {how} N={N}: {per*1e3:.2f} ms")


if __name__ == "__main__":
    main()
