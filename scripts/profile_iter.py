"""Decompose the fused per-iteration cost at bench shape on the TPU.

Chained-execution methodology (calibrate.py): per-op = (t_K - t_1)/(K-1).
Measures the full fused iteration and its components: tree build, the
end-of-tree assign_leaves routing pass, leaf_values_by_row, gradients,
row packing, and the partition/histogram kernels at representative sizes.
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
    import lightgbm_tpu as lgb
    from bench import make_higgs_like
    from lightgbm_tpu.fused import FusedTrainer
    from lightgbm_tpu.learner import assign_leaves, leaf_values_by_row
    from lightgbm_tpu.basic import Booster

    print("devices:", jax.devices())
    X, y = make_higgs_like(N)
    params = {
        "objective": "binary", "num_leaves": 255, "max_bin": 255,
        "learning_rate": 0.1, "verbosity": -1, "tpu_iter_block": 1,
    }
    ds = lgb.Dataset(X, label=y)
    ds.construct()
    b = Booster(params=dict(params), train_set=ds)
    g = b.inner
    ft = FusedTrainer(g)
    lrn = g.learner
    obj = g.objective
    build = lrn.make_build_fn()
    kw = lrn.build_kwargs()
    print("build kwargs:", {k: v for k, v in kw.items()
                            if k in ("hist_chunk", "part_chunk", "hist_mode",
                                     "part_kernel")})

    # ---------------- full fused iteration ----------------
    from lightgbm_tpu.fused import _obj_array_state
    ostate = _obj_array_state(obj)

    def make_blockk(k):
        g.config.tpu_iter_block = k
        ft2 = FusedTrainer(g)
        fn = ft2._block_fn(k)

        def run():
            out = fn(g.train_score.score, jnp.asarray(g._cegb_used),
                     g._key, jnp.int32(0), lrn.bins, lrn.meta, ostate)
            return out[0][0]
        return run

    per = chain_cost(make_blockk, K=4)
    print(f"fused iteration: {per*1e3:.1f} ms/iter "
          f"({N/per/1e6:.1f} M rows/s)")

    # ---------------- one tree build (incl. assign_leaves) ----------------
    score0 = g.train_score.score
    gg, hh = obj.get_gradients(score0)
    ghc = jnp.stack([gg, hh, jnp.ones_like(gg)], axis=1)
    fmask = jnp.ones((lrn.bins.shape[1],), bool)
    key = jax.random.PRNGKey(0)
    cegb_used = jnp.zeros((lrn.bins.shape[1],), bool)

    def make_tree(k):
        @jax.jit
        def f(bins, ghc):
            def body(c, _):
                log = build(bins, ghc + c * 1e-30, lrn.meta, fmask, key,
                            cegb_used)
                return jnp.float32(log.num_splits), None
            c, _ = jax.lax.scan(body, jnp.float32(0), None, length=k)
            return c
        return lambda: f(lrn.bins, ghc)

    per_tree = chain_cost(make_tree, K=3)
    print(f"build_tree(+assign): {per_tree*1e3:.1f} ms/tree")

    # ---------------- assign_leaves ----------------
    log1 = jax.jit(build)(lrn.bins, ghc, lrn.meta, fmask, key, cegb_used)
    jax.block_until_ready(log1.row_leaf)

    def make_assign(k):
        @jax.jit
        def f(bins, log):
            def body(c, _):
                rl = assign_leaves(bins, log._replace(
                    num_splits=log.num_splits + c.astype(jnp.int32) * 0),
                    has_categorical=False, bundle=None)
                return jnp.float32(rl[0]), None
            c, _ = jax.lax.scan(body, jnp.float32(0), None, length=k)
            return c
        return lambda: f(lrn.bins, log1)

    per = chain_cost(make_assign, K=3)
    print(f"assign_leaves: {per*1e3:.1f} ms/tree")

    # ---------------- leaf_values_by_row ----------------
    def make_lvbr(k):
        @jax.jit
        def f(rl, lv):
            def body(c, _):
                v = leaf_values_by_row(lv + c * 1e-30, rl, 255)
                return v[0], None
            c, _ = jax.lax.scan(body, jnp.float32(0), None, length=k)
            return c
        return lambda: f(log1.row_leaf, log1.leaf_value)

    per = chain_cost(make_lvbr, K=6)
    print(f"leaf_values_by_row: {per*1e3:.1f} ms")

    # ---------------- gradients ----------------
    def make_grad(k):
        @jax.jit
        def f(score):
            def body(c, _):
                gg, hh = obj.get_gradients(score + c * 1e-30)
                return gg[0], None
            c, _ = jax.lax.scan(body, jnp.float32(0), None, length=k)
            return c
        return lambda: f(score0)

    per = chain_cost(make_grad, K=6)
    print(f"gradients: {per*1e3:.1f} ms")

    # ---------------- pack + buffer write ----------------
    from lightgbm_tpu.ops.partition import pack_rows, work_spec
    guard, width = work_spec(lrn.bins.shape[1], False, kw["part_kernel"],
                             kw["part_chunk"], kw["hist_chunk"])
    npad = N + 2 * guard
    wbuf0 = jnp.zeros((2, npad, width), jnp.uint8)

    def make_pack(k):
        @jax.jit
        def f(bins, ghc, wbuf):
            def body(c, _):
                w0 = pack_rows(jnp.pad(bins, ((guard, guard), (0, 0))),
                               jnp.pad(ghc + c * 1e-30, ((guard, guard), (0, 0))))
                w = wbuf.at[0, :, :w0.shape[1]].set(w0)
                return w[0, guard, 0].astype(jnp.float32), None
            c, _ = jax.lax.scan(body, jnp.float32(0), None, length=k)
            return c
        return lambda: f(lrn.bins, ghc, wbuf0)

    per = chain_cost(make_pack, K=4)
    print(f"pack+buffer write: {per*1e3:.1f} ms")


if __name__ == "__main__":
    main()
