"""Op-level device cost profile of one fused block via jax.profiler.

Written before this round of work, when the profiler's per-op costs
reproduced to 0.01 ms while wall-clock moved 30-50% run to run; PERF.md
later found those costs were modelled for Pallas custom calls. Re-test
queued (ROADMAP S2) before trusting either.

env: PROF_N (2M), PROF_K (3 iters/block), and any lightgbm params via
PROF_PARAMS as a JSON dict (merged over the bench defaults).
"""
import collections
import glob
import gzip
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from lightgbm_tpu.runtime import enable_compile_cache
enable_compile_cache()


def profile_block(params_extra=None, n=None, k=None, top=18,
                  rank=False):
    import lightgbm_tpu as lgb
    import lightgbm_tpu.fused as F
    from bench import make_higgs_like, make_mslr_like
    from lightgbm_tpu.basic import Booster

    n = n or int(os.environ.get("PROF_N", 2_000_000))
    k = k or int(os.environ.get("PROF_K", 3))
    params = {"objective": "binary", "num_leaves": 255, "max_bin": 255,
              "learning_rate": 0.1, "verbosity": -1, "tpu_iter_block": k}
    if rank:
        X, y, group = make_mslr_like(n)
        params["objective"] = "lambdarank"
        kw = {"group": group}
    else:
        X, y = make_higgs_like(n)
        kw = {}
    params.update(params_extra or {})
    params.update(json.loads(os.environ.get("PROF_PARAMS", "{}")))
    ds = lgb.Dataset(X, label=y, **kw)
    ds.construct()
    b = Booster(params=dict(params), train_set=ds)
    g = b.inner
    ft = F.FusedTrainer(g)
    fn = ft._block_fn(k)
    ostate = F._obj_array_state(g.objective)
    args = (g.train_score.score, jnp.asarray(g._cegb_used), g._key,
            jnp.int32(0), g.learner.bins, g.learner.meta, ostate)
    out = fn(*args)
    jax.block_until_ready(out)
    tdir = "/tmp/jaxtrace_cm"
    shutil.rmtree(tdir, ignore_errors=True)
    with jax.profiler.trace(tdir):
        out = fn(*args)
        jax.block_until_ready(out)
    path = sorted(glob.glob(tdir + "/plugins/profile/*/*.trace.json.gz"))[-1]
    data = json.load(gzip.open(path, "rt"))
    events = data["traceEvents"]
    pids = {e["pid"]: e["args"].get("name", "") for e in events
            if e.get("ph") == "M" and e.get("name") == "process_name"}
    tot = collections.Counter()
    cnt = collections.Counter()
    for e in events:
        if e.get("ph") != "X":
            continue
        if "TPU" not in pids.get(e["pid"], ""):
            continue
        tot[e["name"]] += e.get("dur", 0)
        cnt[e["name"]] += 1
    rows = tot.most_common(top)
    for name, d in rows:
        print(f"{d/1e3/k:9.2f} ms/iter  x{cnt[name]/k:8.1f}  {name[:84]}")
    return tot, cnt, k


if __name__ == "__main__":
    profile_block(rank=os.environ.get("PROF_RANK", "") == "1")
