"""Measure trace/lower/compile cost of the fused training block at bench shape."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from lightgbm_tpu import obs

from lightgbm_tpu.runtime import enable_compile_cache
enable_compile_cache()

import lightgbm_tpu as lgb
from bench import make_higgs_like

N = int(os.environ.get("PROF_N", 2_000_000))
X, y = make_higgs_like(N)
params = {
    "objective": "binary", "num_leaves": 255, "max_bin": 255,
    "learning_rate": 0.1, "verbosity": -1, "tpu_iter_block": 20,
}

with obs.wall("trace_cost/construct", record=False) as w:
    ds = lgb.Dataset(X, label=y)
    ds.construct()
print(f"dataset construct: {w.seconds:.1f}s")

for rep in range(3):
    with obs.wall("trace_cost/train", record=False) as w:
        bst = lgb.train(dict(params), ds, num_boost_round=20)
    print(f"train#{rep} 20 iters: {w.seconds:.1f}s")
