"""Interleaved A/B: host per-leaf ridge solve loop vs the batched device fit.

The linear-leaf fit is L independent ridge solves over branch-path
features. The host oracle (boosting._fit_linear_tree) gathers each leaf's
rows and calls ``np.linalg.solve`` sequentially — O(L) host round trips of
Python-side gather + BLAS. The device kernel (lightgbm_tpu/linear/fit.py)
accumulates ALL leaves' normal equations with chunked one-hot matmuls and
solves them in one batched ``jnp.linalg.solve`` — two MXU contractions per
chunk, one solve, one transfer.

Measurement discipline (PERF.md): single process, A/B interleaved
trial-by-trial, best-of-R, every device wall ends in a forced 1-element
``np.asarray(..)[:1]`` transfer. Parity (f32 device vs f64 host) is
reported alongside so a fast-but-wrong kernel can't sneak through.

On a CPU backend the batched fit runs through XLA:CPU against numpy's
native BLAS — those numbers are correctness-only, never quote them as
perf. The speedup claim only means anything on a TPU backend, where the
host loop additionally pays L device->host residual transfers.

Usage: python scripts/linear_bisect.py [n_rows] [num_leaves] [k_feats] [n_feats]
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from lightgbm_tpu.runtime import enable_compile_cache
enable_compile_cache()

from lightgbm_tpu import obs
from lightgbm_tpu.linear.fit import fit_leaves

REPS = 5
LAM = 0.01


def build(n, L, k, f, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    row_leaf = rng.randint(0, L, n).astype(np.int32)
    g = rng.randn(n).astype(np.float64)
    h = np.ones(n, np.float64)
    feat_idx = np.zeros((L, k), np.int32)
    for l in range(L):
        feat_idx[l] = np.sort(rng.choice(f, k, replace=False))
    feat_mask = np.ones((L, k), bool)
    return X, row_leaf, g, h, feat_idx, feat_mask


def host_fit(X, row_leaf, g, h, feat_idx, feat_mask):
    """The oracle's sequential shape: per leaf, gather rows, build the
    design matrix, one f64 ridge solve (boosting._fit_linear_tree)."""
    L, k = feat_idx.shape
    betas = np.zeros((L, k + 1))
    for l in range(L):
        rows = np.flatnonzero(row_leaf == l)
        Z = np.column_stack([X[rows][:, feat_idx[l]],
                             np.ones(len(rows))])
        hw = h[rows]
        A = Z.T @ (Z * hw[:, None])
        A[np.arange(k), np.arange(k)] += LAM
        b = Z.T @ g[rows]
        betas[l] = -np.linalg.solve(A, b)
    return betas


def main(n, L, k, f):
    backend = jax.default_backend()
    X, row_leaf, g, h, feat_idx, feat_mask = build(n, L, k, f)
    Xd = jnp.asarray(X, jnp.float32)
    rl = jnp.asarray(row_leaf, jnp.int32)
    gd = jnp.asarray(g, jnp.float32)
    hd = jnp.asarray(h, jnp.float32)
    fid = jnp.asarray(feat_idx, jnp.int32)
    fmd = jnp.asarray(feat_mask, jnp.bool_)
    lam = jnp.asarray(LAM, jnp.float32)
    print(f"backend={backend} n={n} L={L} k={k} F={f}")

    # warmup: compile the batched fit, prime BLAS
    beta_d, ok_d = fit_leaves(Xd, rl, gd, hd, fid, fmd, lam)
    beta_dh = np.asarray(beta_d, np.float64)
    assert bool(np.asarray(ok_d).all()), "device fit declined some leaves"
    beta_h = host_fit(X, row_leaf, g, h, feat_idx, feat_mask)

    print("parity |beta_dev - beta_host| max: %.3e"
          % np.max(np.abs(beta_dh - beta_h)))

    best = {"host": np.inf, "device": np.inf}
    for _ in range(REPS):                    # A, B, A, B ... interleaved
        with obs.wall("linear_bisect/host", record=False) as w:
            host_fit(X, row_leaf, g, h, feat_idx, feat_mask)
        best["host"] = min(best["host"], w.seconds)
        with obs.wall("linear_bisect/device", record=False) as w:
            bd, _ = fit_leaves(Xd, rl, gd, hd, fid, fmd, lam)
            np.asarray(bd)[:1]               # forced transfer: trusted end
        best["device"] = min(best["device"], w.seconds)

    for name, s in best.items():
        print(f"{name:8s} {s * 1e3:9.3f} ms  ({n / s / 1e6:7.1f} M rows/s)")
    print(f"device speedup: {best['host'] / best['device']:.2f}x "
          f"(L={L} sequential host solves -> 1 batched device solve)")


if __name__ == "__main__":
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    L = int(sys.argv[2]) if len(sys.argv) > 2 else 63
    k = int(sys.argv[3]) if len(sys.argv) > 3 else 8
    f = int(sys.argv[4]) if len(sys.argv) > 4 else 28
    main(n, L, k, f)
