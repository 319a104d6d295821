"""Single-chip capacity demonstration (VERDICT r4 item 6).

Trains at BIG_N rows x 28 features on one chip and records peak HBM.
PERF.md's capacity model claims ~40M rows at Higgs width on a 16 GB v5e;
this script demonstrates >= 30M (0.75x the claimed ceiling).

Usage: python scripts/capacity.py [rows]   (default 30M)
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

BIG_N = int(sys.argv[1]) if len(sys.argv) > 1 else 30_000_000


def main():
    import jax
    from lightgbm_tpu.runtime import enable_compile_cache
    enable_compile_cache()
    import lightgbm_tpu as lgb
    from bench import make_higgs_like

    from lightgbm_tpu import obs
    with obs.wall("capacity/datagen", record=False) as w:
        X, y = make_higgs_like(BIG_N)
    print("datagen %.1fs" % w.seconds, flush=True)
    with obs.wall("capacity/construct", record=False) as w:
        ds = lgb.Dataset(X, label=y)
        ds.construct()
    print("construct %.1fs" % w.seconds, flush=True)
    params = {"objective": "binary", "num_leaves": 255, "max_bin": 255,
              "learning_rate": 0.1, "verbosity": -1, "metric": ["auc"],
              "tpu_iter_block": 5}
    with obs.wall("capacity/train", record=False) as w:
        bst = lgb.train(dict(params), ds, num_boost_round=10)
    train_s = w.seconds
    (_, _, auc, _), = bst.eval_train()
    stats = {}
    try:
        stats = jax.local_devices()[0].memory_stats() or {}
    except Exception:
        pass
    peak = stats.get("peak_bytes_in_use") or stats.get("bytes_in_use")
    print("rows=%d train(10 iters)=%.1fs auc=%.4f peak_hbm=%s"
          % (BIG_N, train_s, auc,
             ("%.2f GB" % (peak / 1e9)) if peak else "unavailable"),
          flush=True)


if __name__ == "__main__":
    main()
