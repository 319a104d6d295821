"""The benchmark's command: one cell, one run, one JSON line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix, one metric,
one check or one generator is a file of its own, found by the name in
BENCHMARK.json (benchmark/README.md). Earlier lines of the output (PHASES,
READINGS, CHECK, MEMORY, TRACE) are for people; the last line is the
contract's.
"""
import time

T0 = time.perf_counter()   # process start, as near as Python gets to it

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, HERE]

import arith  # noqa: E402


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """benchmark/<kind>/<name>.py, found by name."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location("bench_%s_%s" % (kind, name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def say(tag, obj):
    print(tag + " " + json.dumps(obj, sort_keys=True, default=float), flush=True)


def delta(after, before, section):
    return {k: v - before[section].get(k, 0) for k, v in after[section].items()}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", default=None, metavar="JSON",
                    help="tiny-size walk on any backend, e.g. "
                    '\'{"rows": 30000, "params": {"num_leaves": 31}}\'; always exits 4')
    a = ap.parse_args()

    bench = load_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == a.workload), None)
    if cell is None:
        sys.exit("benchmark: no workload %r in BENCHMARK.json" % a.workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = load_json(ROOT, entry["file"])
    mix = load_json(HERE, "traffic", cell["traffic"] + ".json")
    rehearse = json.loads(a.rehearse) if a.rehearse else None
    if rehearse:
        cfg["shape"]["rows"] = int(rehearse["rows"])
        cfg["params"].update(rehearse.get("params", {}))

    def in_cell(m):   # the contract's optional "workloads" key on a metric
        return a.workload in m.get("workloads", [a.workload])
    e2e = [m for m in bench["end_to_end"] if in_cell(m)]
    per_layer = [m for m in bench["per_layer"] if in_cell(m)]

    # ---- data from --seed: numpy only, on a thread of its own, so that it
    # overlaps the ~20 s the TPU runtime takes to start ----
    made = {}

    def make_data():
        t = time.perf_counter()
        made["data"] = load_module("datagen", cfg["datagen"]["kind"]).make(
            cfg["shape"], cfg["datagen"]["args"], a.seed)
        made["seconds"] = time.perf_counter() - t
    maker = threading.Thread(target=make_data)
    maker.start()

    # ---- identity gate: a TPU that is in the peaks table, or nothing ----
    import jax
    from lightgbm_tpu import runtime
    cache_dir = runtime.enable_compile_cache()
    device = runtime.device_identity()
    say("DEVICE", dict(device, compile_cache=cache_dir, rehearse=bool(rehearse)))
    if not rehearse:
        if device["platform"] != "tpu" or device["count"] < cell["chips"]:
            maker.join()
            sys.exit("benchmark: needs %d TPU chip(s), found platform=%s count=%d"
                     % (cell["chips"], device["platform"], device["count"]))
    peaks = arith.peaks(device["kind"]) if device["platform"] == "tpu" else None
    cache = {"requests": 0, "hits": 0}

    def on_event(event, **kw):
        if event.endswith("/compile_requests_use_cache"):
            cache["requests"] += 1
        elif event.endswith("/cache_hits"):
            cache["hits"] += 1
    jax.monitoring.register_event_listener(on_event)
    import lightgbm_tpu as lgb
    t_ready = time.perf_counter()
    maker.join()
    if "data" not in made:
        sys.exit("benchmark: the data generator failed")
    data = made["data"]

    # ---- the program's own Dataset.construct ----
    t_data = time.perf_counter()
    dataset = lgb.Dataset(data["X"], label=data["label"], group=data["group"],
                          params=cfg["params"])
    binned = dataset.construct()
    t_construct = time.perf_counter()

    # ---- the job ----
    trace_dir = os.path.join(ROOT, ".bench_trace", "%s.%d" % (a.workload, a.seed))
    shutil.rmtree(trace_dir, ignore_errors=True)
    job = load_module("jobs", mix["job"]).run({
        "params": cfg["params"], "mix": mix, "dataset": dataset,
        "seconds": a.seconds, "trace": bool(a.trace), "trace_dir": trace_dir})
    returns, first, last = job["returns"], job["open"], job["close"]
    ran = last is not None and not job["self_stopped"]
    iters = returns[last][1] - returns[first][1] if ran else 0
    ms_per_iter = arith.window_ms_per_iter(returns, first, last) if ran else None
    periods = arith.block_periods_ms(returns, first, last) if ran else []
    say("READINGS", {"boundary": "GBDT.train_block", "returns": len(returns),
                     "window": [first, last], "ms_per_iter": ms_per_iter,
                     "iterations": iters, "block_ms_per_iter": periods,
                     "block_median_ms_per_iter": statistics.median(periods) if periods else None,
                     "return_s_after_entry": [t - job["enter_s"] for t, _, _ in returns],
                     "output_ready_at_return": [r for _, _, r in returns],
                     "self_stopped": job["self_stopped"]})
    if not ran:
        sys.exit("benchmark: the window never closed (returns: %d)" % len(returns))

    so, sc = job["snap_open"], job["snap_close"]
    compile_s = so["timers"].get("jit/backend_compile_s", 0.0)
    # trees the device had finished at the window's open: all dispatched by
    # then, or a block fewer where the block's output was not ready yet
    block = returns[first][1] - returns[first - 1][1] if first else returns[first][1]
    iters_done = returns[first][1] - (0 if returns[first][2] is not False else block)
    values = {
        "train_ms_per_iter": ms_per_iter,
        "setup_s": returns[first][0] - T0,
        "job_start_s": arith.job_start_s(job["enter_s"], returns[first][0],
                                         iters_done, ms_per_iter, compile_s),
        "construct_s": t_construct - t_data,
        "compile_s": compile_s,
    }
    prog = job["program_cost"]
    if prog:
        values["hbm_program_gb"] = (prog["temp_bytes"] + prog["argument_bytes"]) / 1e9
    say("PHASES", {"import_and_runtime_s": t_ready - T0, "datagen_s": made["seconds"],
                   "datagen_wait_s": t_data - t_ready,
                   "construct_s": values["construct_s"],
                   "train_enter_to_window_s": returns[first][0] - job["enter_s"],
                   "compile_or_load_s": compile_s, "cache_requests": cache["requests"],
                   "cache_hits": cache["hits"],
                   "backend_compiles_before_window": so["counters"].get("jit/backend_compiles", 0),
                   "after_window_to_train_return_s": job["leave_s"] - returns[last][0],
                   "setup_s": values["setup_s"], "job_start_s": values["job_start_s"]})

    # ---- checks, outside every timing ----
    t_check = time.perf_counter()
    from reference import model_text
    header, trees = model_text.parse(job["booster"].model_to_string())
    c = {"params": cfg["params"], "rows": cfg["shape"]["rows"], "X": data["X"],
         "label": data["label"], "group": data["group"], "booster": job["booster"],
         "binned": binned, "header": header, "trees": trees}
    correct = True
    compared = {}   # check -> its verdict and, in its detail, each number compared beside its limit
    for chk in cfg["checks"]:
        t = time.perf_counter()
        ok, detail = load_module("checks", chk["kind"]).run(chk, c)
        say("CHECK", {"check": chk["kind"], "ok": bool(ok), "detail": detail,
                      "seconds": time.perf_counter() - t})
        compared[chk["kind"]] = {"ok": bool(ok), "detail": detail}
        correct = correct and bool(ok)
    window_trees, failed = model_text.window(header, trees, iters_done, iters)
    counters = delta(sc, so, "counters")
    compiles = counters.get("jit/backend_compiles", 0)
    compared["no_compile_in_window"] = {
        "ok": not compiles, "detail": "%d backend compiles inside the window (at most 0)" % compiles}
    if compiles:
        say("CHECK", dict(compared["no_compile_in_window"], check="no_compile_in_window"))
        correct = False
    check_s = time.perf_counter() - t_check

    # ---- per-layer readings ----
    facts = {"iters": iters, "timers": delta(sc, so, "timers"), "counters": counters,
             "values": values, "features": cfg["shape"]["features"], "peaks": peaks,
             "trace": None, "trace_iters": 0, "trace_trees": None}
    busy = {}
    breakdown = None
    if a.trace:
        import trace_reduce
        t = time.perf_counter()
        codes, vocab, start, dur, spans, modules = trace_reduce.read_xplane(job["xplane"])
        if not codes:
            sys.exit("benchmark: the trace holds no device operation")
        # the traced period on the trace's own clock: the benchmark's host
        # annotation around the one traced call, which dispatches a block,
        # waits for it and does the host's work on it with the device idle
        call = [sp for sp in spans if sp[0] == "bench/train_block"]
        if len(call) != 1:
            sys.exit("benchmark: %d bench/train_block spans in the trace, want 1" % len(call))
        window = (call[0][1], call[0][2])
        # the call ran one whole block if one program, wholly inside it, took
        # most of it; then the iterations of the trace are the block's
        blocks = [m for m in modules if m[1] >= window[0] and m[2] <= window[1]
                  and m[2] - m[1] > 0.5 * (window[1] - window[0])]
        if len(blocks) != 1:
            sys.exit("benchmark: the traced call holds %d whole block programs, want 1"
                     % len(blocks))
        tr = trace_reduce.reduce_events(codes, vocab, start, dur,
                                        trace_reduce.scope_map(job["hlo_text"] or ""),
                                        spans, window)
        facts.update(trace=tr, trace_iters=iters, trace_trees=window_trees)
        values["device_ms_per_iter"] = 1e3 * tr["busy_s"] / iters
        values["device_idle_share"] = 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
        busy = {"busy_s": tr["busy_s"], "window_s": tr["window_s"]}

        def top(d):
            return sorted(([k, v] for k, v in d.items()), key=lambda kv: -kv[1])[:10]
        breakdown = {"device_ops": top(tr["by_op"]), "idle_gaps": top(tr["idle_gaps"])}
        say("TRACE", dict(job["trace_calls"], reduce_s=time.perf_counter() - t,
                          events=tr["events"], events_in_window=tr["events_in_window"],
                          leaf_events=tr["leaf_events"],
                          host_window_s=returns[last][0] - returns[first][0],
                          traced_call_s=tr["window_s"],
                          block_program=blocks[0][0],
                          block_program_s=(blocks[0][2] - blocks[0][1]) / 1e9,
                          host_spans=len(spans), by_scope=tr["by_scope"],
                          xplane_bytes=os.path.getsize(job["xplane"])))
        shutil.rmtree(trace_dir, ignore_errors=True)

    # the allocator's peak in use leaves out a running program's temporaries;
    # while the block runs, its arguments and its temporaries are resident
    # together, and the compiler says how much that is
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    in_use = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    program = (prog["temp_bytes"] + prog["argument_bytes"]) if prog else 0
    peak = max(in_use, program)
    say("MEMORY", {"memory_stats_device0": stats[0] if stats else None,
                   "compiled_program": prog, "allocator_peak_in_use": in_use,
                   "program_temp_plus_arguments": program})

    metrics = {}
    if a.trace:
        for m in per_layer:
            spec = load_json(HERE, "metrics", m["name"] + ".json")
            v = load_module("readers", spec["reader"]).read(spec["args"], facts)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in e2e:
            metrics[m["name"]] = {"value": float(values[m["name"]]), "unit": m["unit"]}
    line = {"correct": bool(correct), "attempted": int(iters), "failed": int(failed),
            "metrics": metrics,
            "device": dict(device, memory_peak_bytes=int(peak), **busy),
            "workload": a.workload, "seed": a.seed, "check_s": check_s,
            "total_s": time.perf_counter() - T0}
    if breakdown:
        line["breakdown"] = breakdown
    # what decided `correct`: last in the line and last on standard error,
    # which is what the driver's record keeps of a run that is not correct
    line["compared"] = compared
    for kind, verdict in compared.items():
        print("COMPARED %s ok=%s: %s" % (kind, verdict["ok"], verdict["detail"]),
              file=sys.stderr, flush=True)
    if rehearse:
        say("REHEARSAL", line)
        sys.exit(4)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
