"""A/B the hi/lo split width of the segment histogram einsum.

Current: hi=B/16 (SH), lo=16 -> log_ = lo_oh*ch materializes 16*NCH wide.
Candidates: lo=8 (SH=32), lo=4 (SH=64). Narrower lo shrinks the
materialized (C, F, LO*NCH) product and raises the hi-side matmul M dim
(better MXU tiling); wider hi grows the (C, F, SH) one-hot.
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
F = int(os.environ.get("PROF_F", 28))
B = 256
CHUNK = int(os.environ.get("PROF_CHUNK", 4096))


# trusted wall per PERF.md discipline: warm once, then time one call
# ended by a forced 1-element transfer (obs.timed_sync)
timed = obs.timed_sync


def chain_cost(make_chain, K=4):
    f1 = make_chain(1)
    fK = make_chain(K)
    t1 = min(timed(f1) for _ in range(3))
    tK = min(timed(fK) for _ in range(3))
    return (tK - t1) / (K - 1)


def _split_bf16(x):
    hi = jax.lax.optimization_barrier(x.astype(jnp.bfloat16))
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def hist_chunk_lo(cb, cgm, lo_w: int):
    dt = jnp.bfloat16
    sh = B // lo_w
    shift = {2: 1, 4: 2, 8: 3, 16: 4}[lo_w]
    hi = (cb >> shift).astype(jnp.uint8)
    lo = (cb & (lo_w - 1)).astype(jnp.uint8)
    hi_oh = (hi[:, :, None] == jnp.arange(sh, dtype=jnp.uint8)).astype(dt)
    lo_oh = (lo[:, :, None] == jnp.arange(lo_w, dtype=jnp.uint8))
    g_hi, g_lo = _split_bf16(cgm[:, 0])
    h_hi, h_lo = _split_bf16(cgm[:, 1])
    ch = jnp.stack([g_hi, g_lo, h_hi, h_lo,
                    cgm[:, 2].astype(jnp.bfloat16)], axis=1)
    c, f = cb.shape
    log_ = (lo_oh[:, :, :, None].astype(dt)
            * ch[:, None, None, :].astype(dt)).reshape(c, f, lo_w * 5)
    return jnp.einsum("cfh,cfx->fhx", hi_oh, log_,
                      preferred_element_type=jnp.float32)


def hist_seg(work, start, cnt, lo_w):
    f = F
    sh = B // lo_w
    nchunks = (cnt + CHUNK - 1) // CHUNK
    width = work.shape[1]

    def body(i, acc):
        off = start + i * CHUNK
        cw = jax.lax.dynamic_slice(work, (off, 0), (CHUNK, width))
        cb = cw[:, :f]
        gb = cw[:, f:f + 12].reshape(CHUNK, 3, 4)
        cg = jax.lax.bitcast_convert_type(gb, jnp.float32)
        rows_left = cnt - i * CHUNK
        valid = jnp.arange(CHUNK, dtype=jnp.int32) < rows_left
        cgm = cg * valid[:, None].astype(jnp.float32)
        return acc + hist_chunk_lo(cb, cgm, lo_w)

    acc = jax.lax.fori_loop(0, nchunks, body,
                            jnp.zeros((f, sh, lo_w * 5), jnp.float32))
    h = acc.reshape(f, sh, lo_w, 5).reshape(f, sh * lo_w, 5)[:, :B]
    return jnp.stack([h[..., 0] + h[..., 1], h[..., 2] + h[..., 3],
                      h[..., 4]], axis=-1)


def main():
    print("devices:", jax.devices(), "N=%d F=%d chunk=%d" % (N, F, CHUNK))
    rng = np.random.RandomState(0)
    bins = rng.randint(0, B, size=(N, F)).astype(np.uint8)
    ghc = np.stack([rng.randn(N), np.abs(rng.randn(N)) + 0.1,
                    np.ones(N)], axis=1).astype(np.float32)
    gb = ghc.view(np.uint8).reshape(N, 12)
    work = jnp.asarray(np.concatenate([bins, gb], axis=1))

    ref = None
    for lo_w in (16, 8, 4):
        def make(k, lo_w=lo_w):
            @jax.jit
            def f(work):
                def body(c, _):
                    # non-foldable carry dependency: keeps XLA from
                    # hoisting the loop-invariant body out of the scan
                    start = (c > 1e30).astype(jnp.int32)
                    hg = hist_seg(work, start, N, lo_w)
                    return c + jnp.sum(hg) * 1e-30, None
                c, _ = jax.lax.scan(body, jnp.float32(0), None, length=k)
                return c
            return lambda: f(work)

        per = chain_cost(make, K=9)
        print(f"lo_w={lo_w}: {per*1e3:.2f} ms ({N/per/1e6:.0f} M rows/s, "
              f"{per/N*1e9*1e3/F:.3f} ns/row*feat)")
        h = jax.jit(partial(hist_seg, lo_w=lo_w))(work, jnp.int32(0),
                                                  jnp.int32(N))
        h = np.asarray(h)
        if ref is None:
            ref = h
        else:
            print("   max abs diff vs lo16:", np.abs(h - ref).max())


if __name__ == "__main__":
    main()
