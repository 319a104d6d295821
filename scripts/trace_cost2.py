"""Split per-train-call fixed cost into trace / lower / compile / run."""
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
from lightgbm_tpu.fused import FusedTrainer
from bench import make_higgs_like

N = int(os.environ.get("PROF_N", 2_000_000))
X, y = make_higgs_like(N)
params = {
    "objective": "binary", "num_leaves": 255, "max_bin": 255,
    "learning_rate": 0.1, "verbosity": -1, "tpu_iter_block": 20,
}
ds = lgb.Dataset(X, label=y)
ds.construct()

bst = lgb.train(dict(params), ds, num_boost_round=1)  # warm small pieces

from lightgbm_tpu.basic import Booster
with obs.wall("trace_cost2/init", record=False) as w:
    b2 = Booster(params=dict(params), train_set=ds)
print(f"Booster init: {w.seconds:.1f}s")
g = b2.inner
ft = FusedTrainer(g)
with obs.wall("trace_cost2/block_fn", record=False) as w:
    fn = ft._block_fn(20)
print(f"_block_fn build (no trace): {w.seconds:.1f}s")
args = (g.train_score.score, jnp.asarray(g._cegb_used), g._key, jnp.int32(0))
with obs.wall("trace_cost2/trace", record=False) as w:
    lowered = fn.trace(*args)
print(f"jit trace: {w.seconds:.1f}s")
with obs.wall("trace_cost2/lower", record=False) as w:
    low = lowered.lower()
print(f"lower: {w.seconds:.1f}s")
with obs.wall("trace_cost2/compile", record=False) as w:
    comp = low.compile()
print(f"compile (persistent cache): {w.seconds:.1f}s")
with obs.wall("trace_cost2/run", record=False) as w:
    out = comp(*args)
    obs.sync(out)
print(f"run block of 20: {w.seconds:.1f}s")
with obs.wall("trace_cost2/run2", record=False) as w:
    out = comp(*args)
    obs.sync(out)
print(f"run block of 20 (2nd): {w.seconds:.1f}s")
