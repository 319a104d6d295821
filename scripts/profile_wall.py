"""Phase breakdown of the driver-visible bench wall (VERDICT r4 item 1).

Runs the binary bench shape and reports where every second goes:
dataset construction, warmup (trace/compile vs execute), the timed train's
dispatch / logs-transfer / host-tree phases, and the pure device time of one
fused block (block_until_ready around the cached block fn).

Usage: python scripts/profile_wall.py [N_ROWS] [N_ITER]
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

N = int(sys.argv[1]) if len(sys.argv) > 1 else 2_000_000
ITERS = int(sys.argv[2]) if len(sys.argv) > 2 else 60
BLOCK = int(os.environ.get("BENCH_BLOCK", 20))


def main():
    import jax
    from lightgbm_tpu.runtime import enable_compile_cache
    enable_compile_cache()
    from lightgbm_tpu import obs
    with obs.wall("profile/import") as w:
        import lightgbm_tpu as lgb
    t_import = w.seconds

    rng = np.random.RandomState(7)
    with obs.wall("profile/datagen") as wt:
        X = rng.randn(N, 28).astype(np.float32)
        w = rng.randn(28) / np.sqrt(28)
        logit = X @ w + 0.5 * np.sin(X[:, 0] * 2) * X[:, 1] \
            + 0.3 * rng.randn(N)
        y = (logit > 0).astype(np.float64)
        X = X.astype(np.float64)
    t_datagen = wt.seconds

    params = {
        "objective": "binary", "num_leaves": 255, "max_bin": 255,
        "learning_rate": 0.1, "verbosity": -1, "metric": ["auc"],
        "tpu_iter_block": BLOCK,
    }
    with obs.wall("profile/construct") as wt:
        ds = lgb.Dataset(X, label=y)
        ds.construct()
    t_construct = wt.seconds

    def phase_mark():
        """The registry's host-phase timers now: ``grown()`` is a train's."""
        return obs.TimerMark({t: t for _, _, t in obs.PHASES.values() if t})

    # every train wall ends in a forced 1-element transfer of the score
    # (obs.sync): block_until_ready alone does not reliably synchronize
    mark = phase_mark()
    with obs.wall("profile/warmup") as wt:
        wb = lgb.train(dict(params), ds, num_boost_round=BLOCK)
        obs.sync(wb.inner.train_score.score)
    t_warmup = wt.seconds
    warm_t = mark.grown()

    mark = phase_mark()
    with obs.wall("profile/train") as wt:
        bst = lgb.train(dict(params), ds, num_boost_round=ITERS)
        obs.sync(bst.inner.train_score.score)
    t_train = wt.seconds
    train_t = mark.grown()

    # pure device time of one cached block: re-dispatch through the booster
    # machinery and block on the result
    mark = phase_mark()
    with obs.wall("profile/train_warm_block") as wt:
        bst2 = lgb.train(dict(params), ds, num_boost_round=BLOCK)
        obs.sync(bst2.inner.train_score.score)
    t_train1 = wt.seconds
    one_t = mark.grown()

    with obs.wall("profile/eval_train") as wt:
        (_, _, auc, _), = bst.eval_train()
    t_eval = wt.seconds

    def fmt(d):
        return {k: round(v, 3) for k, v in sorted(d.items())}

    print("== profile_wall N=%d iters=%d block=%d ==" % (N, ITERS, BLOCK))
    print("import: %.2fs  datagen: %.2fs  construct: %.2fs" %
          (t_import, t_datagen, t_construct))
    print("warmup(%d it): %.2fs  %s" % (BLOCK, t_warmup, fmt(warm_t)))
    print("train(%d it): %.2fs  %s" % (ITERS, t_train, fmt(train_t)))
    print("train(%d it, warm): %.2fs  %s" % (BLOCK, t_train1, fmt(one_t)))
    print("eval_train: %.2fs auc=%.4f" % (t_eval, auc))
    acc = sum(train_t.values())
    print("timed-train accounted: %.2fs / %.2fs (%.0f%%)" %
          (acc, t_train, 100 * acc / max(t_train, 1e-9)))


if __name__ == "__main__":
    main()
