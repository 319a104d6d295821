"""Job kind ``train``: one ``lgb.train`` call on a constructed Dataset, read at
the block boundary (``GBDT.train_block``), which is wrapped from here: the
program is not edited and does not know it is measured.

Each return records the host clock and the iterations dispatched so far. The
call that gives return i dispatches block i and then finalises block i-1
behind a forced device read (``obs.sync``); that read's one-element slice is
itself queued behind block i, so on the chip return i comes when block i is
done (chip run, PR 24). Return 0 is the exception: it has no block to
finalise and comes at once, so the window opens at return 1 (``OPEN_AT``),
from where every return is one block after the one before. It closes at
the first return ``--seconds`` later; the next call then returns True
without dispatching, which makes ``engine.train`` stop and finalise what is
in flight: every dispatched tree is kept. Whether the block's output was
ready at a return is recorded too, for ``job_start_s`` alone; the time per
iteration does not rest on it. With ``--trace 1`` the window is one block,
inside the profiler, and the call that runs it carries the host annotation
``bench/train_block``: the traced period on the trace's own clock."""
import glob
import os
import time

import arith

OPEN_AT = 1   # the return that opens the window: the first that waited for a block


def run(ctx):
    """ctx: params, mix, dataset, seconds, trace, trace_dir -> readings."""
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs_device
    from lightgbm_tpu.boosting import GBDT
    from lightgbm_tpu.obs import telemetry

    open_idx = OPEN_AT
    w = {"returns": [], "dispatched": 0, "close": None, "snap_open": None,
         "snap_close": None, "self_stopped": False, "hlo": None, "trace": {}}
    inner = GBDT.train_block

    def train_block(gbdt, k):
        if w["close"] is not None:
            return True
        i = len(w["returns"])
        if ctx["trace"] and i == open_idx + 1:
            with jax.profiler.TraceAnnotation("bench/train_block"):
                stop = inner(gbdt, k)
        else:
            stop = inner(gbdt, k)
        now = time.perf_counter()
        w["dispatched"] += int(k)
        score = getattr(getattr(gbdt, "train_score", None), "score", None)
        ready = bool(score.is_ready()) if hasattr(score, "is_ready") else None
        w["returns"].append((now, w["dispatched"], ready))
        if stop:
            w["self_stopped"] = True
        elif i == open_idx:
            w["snap_open"] = telemetry.snapshot()
            if ctx["trace"]:
                w["trace"]["start_s"] = _timed(lambda: jax.profiler.start_trace(
                    ctx["trace_dir"], profiler_options=_profiler_options(jax)))
        elif i > open_idx and (ctx["trace"] or arith.closing_return(
                w["returns"], open_idx, ctx["seconds"]) is not None):
            w["snap_close"] = telemetry.snapshot()
            w["close"] = i
            if ctx["trace"]:
                w["trace"]["stop_s"] = _timed(jax.profiler.stop_trace)
        return stop

    capture = obs_device.on_compile

    def on_compile(name, fn, args, kwargs):
        capture(name, fn, args, kwargs)
        if ctx["trace"] and name == "fused/run_block" and w["hlo"] is None:
            # scopes of device ops come from the compiled module's op_name
            # metadata; the program's own capture keeps no text
            w["hlo"] = fn.lower(*args, **kwargs).compile().as_text()

    GBDT.train_block, obs_device.on_compile = train_block, on_compile
    enter = time.perf_counter()
    try:
        booster = lgb.train(dict(ctx["params"]), ctx["dataset"],
                            num_boost_round=int(ctx["mix"]["num_boost_round"]))
    finally:
        GBDT.train_block, obs_device.on_compile = inner, capture
    leave = time.perf_counter()
    cost = telemetry.snapshot().get("device_cost", {}).get("jits", {})
    xplane = glob.glob(os.path.join(ctx["trace_dir"], "plugins", "profile",
                                    "*", "*.xplane.pb")) if ctx["trace"] else []
    return {"booster": booster, "returns": w["returns"], "open": open_idx,
            "close": w["close"], "self_stopped": w["self_stopped"],
            "enter_s": enter, "leave_s": leave, "snap_open": w["snap_open"],
            "snap_close": w["snap_close"], "program_cost": cost.get("fused/run_block"),
            "hlo_text": w["hlo"], "xplane": xplane[0] if xplane else None,
            "trace_calls": w["trace"]}


def _timed(fn):
    t = time.perf_counter()
    fn()
    return time.perf_counter() - t


def _profiler_options(jax):
    """Device and host (TraceMe) events, no Python tracer: idle gaps are
    attributed to the program's lgbtpu/* host annotations, which do not need
    it, and the Python tracer is most of what stop_trace costs."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts
