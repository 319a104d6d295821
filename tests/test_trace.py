"""Span tracer, flight recorder, Chrome trace export (ISSUE 7 tentpole).

Covers the SpanTracer unit surface (nesting, trace-id inheritance, ring
bounds, mode gating), the zero-cost-when-off guarantee pinned
compile-budget style (a whole train with tracing off starts ZERO spans),
the traced-code refusal (trace_phase inside a jit trace records nothing),
the Chrome trace-event JSON schema (ph/ts/dur/pid/tid + per-tid nesting
consistency, Perfetto-loadable), the serve span chain (one HTTP /predict
-> queue_wait/coalesce/batch/session_dispatch/slice_back under ONE trace
id), Booster.dump_trace, the SIGUSR2 dump hook and the periodic
telemetry dump thread, and the cli --dump-trace flag end to end.
"""
import json
import os
import signal
import threading
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.obs_trace import (
    NULL_SPAN,
    SpanTracer,
    install_signal_handlers,
    start_periodic_telemetry_dump,
    tracer,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PARAMS = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
          "verbosity": -1}


@pytest.fixture(autouse=True)
def _global_tracer_off():
    """Tests that flip the module tracer must not leak mode into the rest
    of the suite (trace_spans is process-global, like verbosity)."""
    yield
    tracer.configure("off")
    tracer.clear()


def _data(n=400, f=6, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f)
    y = (X[:, 0] + X[:, 1] > 1).astype(np.float64)
    return X, y


# ---------------------------------------------------------------- tracer unit

def test_span_nesting_and_trace_id_inheritance():
    t = SpanTracer().configure("on")
    with t.span("outer", trace_id=7, rows=3):
        with t.span("inner"):          # inherits 7 from the stack
            pass
    with t.span("sibling"):            # fresh stack: no id to inherit
        pass
    by_name = {sp.name: sp for sp in t.events()}
    assert set(by_name) == {"outer", "inner", "sibling"}
    assert by_name["inner"].trace_id == 7
    assert by_name["outer"].trace_id == 7
    assert by_name["outer"].args == {"rows": 3}
    assert by_name["sibling"].trace_id is None
    # inner closed first and fits inside outer
    assert by_name["inner"].dur <= by_name["outer"].dur
    assert all(sp.dur >= 0 for sp in t.events())


def test_ring_is_bounded_and_keeps_newest():
    t = SpanTracer(capacity=8).configure("on")
    for i in range(20):
        t.record("s%d" % i, 0.0, 0.001)
    names = [sp.name for sp in t.events()]
    assert names == ["s%d" % i for i in range(12, 20)]
    t.configure("on", capacity=4)      # shrink keeps the newest tail
    assert [sp.name for sp in t.events()] == ["s16", "s17", "s18", "s19"]


def test_configure_rejects_unknown_mode():
    with pytest.raises(ValueError):
        SpanTracer().configure("everything")


def test_mode_gating_and_shared_noop_identity():
    t = SpanTracer()                   # default off
    assert t.span("x") is NULL_SPAN
    assert t.span("x", domain="serve") is NULL_SPAN
    assert t.phase_begin("x") is None
    t.configure("serve_only")
    assert t.span("x") is NULL_SPAN            # train domain stays off
    assert t.phase_begin("x") is None
    with t.span("s", domain="serve"):
        pass
    assert [sp.name for sp in t.events()] == ["s"]
    t.configure("off")
    assert t.span("s", domain="serve") is NULL_SPAN


def test_trace_id_header_round_trip_and_identity():
    from lightgbm_tpu.obs_trace import (format_trace_id, parse_trace_id)
    t = SpanTracer().configure("on")
    # ids are pid-salted so merged multi-process exports never collide
    tid = t.new_trace_id()
    assert (tid >> 40) == (os.getpid() & 0x3FFFFF)
    # header wire format: decimal string there, int back
    assert parse_trace_id(format_trace_id(tid)) == tid
    assert parse_trace_id(None) is None
    assert parse_trace_id("   ") is None
    assert parse_trace_id("client-abc") == "client-abc"   # opaque ids pass
    # current_trace_id reads the innermost open span on THIS thread
    assert t.current_trace_id() is None
    with t.span("outer", trace_id=99):
        assert t.current_trace_id() == 99
        with t.span("inner"):
            assert t.current_trace_id() == 99
    assert t.current_trace_id() is None
    # process identity lands in the chrome process_name meta (and ONLY
    # there — the schema gains no new keys)
    t.set_identity(role="replica", holder="host-1:42")
    assert t.identity() == {"pid": os.getpid(), "role": "replica",
                            "holder": "host-1:42"}
    pname = [m["args"]["name"] for m in t.chrome_trace()["traceEvents"]
             if m["ph"] == "M" and m["name"] == "process_name"]
    assert pname == ["lightgbm-tpu [replica host-1:42]"]
    _assert_chrome_schema(t.chrome_trace())
    t.set_identity(None, None)
    pname = [m["args"]["name"] for m in t.chrome_trace()["traceEvents"]
             if m["ph"] == "M" and m["name"] == "process_name"]
    assert pname == ["lightgbm-tpu"]


def test_new_trace_ids_are_unique_across_threads():
    t = SpanTracer()
    got = []

    def take():
        got.extend(t.new_trace_id() for _ in range(50))

    threads = [threading.Thread(target=take, name="trace-id-%d" % i)
               for i in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert len(set(got)) == 200


# --------------------------------------------------------- zero-cost-when-off

def test_off_path_starts_zero_spans_during_train():
    """The compile-budget-style overhead pin: with trace_spans off
    (default), a full train through every trace_phase site must not
    start a single span or touch the recorder."""
    assert tracer.mode == "off"
    before = tracer.spans_started
    X, y = _data()
    lgb.train(dict(PARAMS), lgb.Dataset(X, label=y), num_boost_round=4)
    assert tracer.spans_started == before
    assert tracer.events() == []


def test_block_tail_spans_nest_inside_train_block():
    """With trace_spans=on the flight recorder holds the host's work on a
    finished block (host trees, commit) inside the engine's train_block
    span, each with the timer of its phase in the registry."""
    obs.telemetry.reset()
    X, y = _data(seed=3)
    lgb.train(dict(PARAMS, trace_spans="on", tpu_iter_block=2),
              lgb.Dataset(X, label=y), num_boost_round=6)
    spans = tracer.events()
    by_name = {}
    for sp in spans:
        by_name.setdefault(sp.name, []).append(sp)
    blocks = by_name["lgbtpu/train_block"]
    assert len(blocks) == 3
    for name in ("lgbtpu/fused_host_trees", "lgbtpu/fused_commit",
                 "lgbtpu/fused_block_fn", "lgbtpu/fused_dispatch"):
        assert by_name.get(name), name
    # blocks 0 and 1 are finalised inside calls 1 and 2, the last one by
    # the flush at the end of lgb.train, outside every train_block
    for name in ("lgbtpu/fused_host_trees", "lgbtpu/fused_commit"):
        inside = [sp for sp in by_name[name]
                  if any(b.tid == sp.tid and b.t0 <= sp.t0
                         and sp.t0 + sp.dur <= b.t0 + b.dur for b in blocks)]
        assert len(by_name[name]) == 3 and len(inside) == 2, name
    # lgbtpu/train and lgbtpu/booster_init open before the booster's
    # params switch the recorder on: timers only, on a first traced job
    assert "lgbtpu/train" not in by_name
    calls = obs.telemetry.snapshot()["timer_calls"]
    assert calls["fused/host_trees"] == calls["fused/commit"] == 3
    assert calls["train/block"] == 3 and calls["train/total"] == 1


def test_trace_phase_refuses_inside_jit_trace():
    """trace_phase sites living in traced code (learner/boosting) must
    not record trace-time spans — only eager host executions count."""
    import jax
    import jax.numpy as jnp

    tracer.configure("on")
    tracer.clear()

    @jax.jit
    def f(x):
        with obs.trace_phase("unit/traced_region"):
            return x * 2.0

    f(jnp.arange(4.0)).block_until_ready()     # traces + runs: no span
    assert "unit/traced_region" not in {sp.name for sp in tracer.events()}
    with obs.trace_phase("unit/traced_region"):    # eager: records
        pass
    assert "unit/traced_region" in {sp.name for sp in tracer.events()}


def test_span_end_feeds_phase_histogram():
    tracer.configure("on")
    obs.telemetry.reset()
    with tracer.span("unit/hist_feed"):
        pass
    h = obs.telemetry.histogram("span_ms/unit/hist_feed")
    assert h is not None and h["count"] == 1


# --------------------------------------------------------- chrome trace JSON

def _assert_chrome_schema(doc):
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    assert doc["displayTimeUnit"] == "ms"
    xs, metas = [], []
    for ev in doc["traceEvents"]:
        assert ev["ph"] in ("X", "M")
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        assert isinstance(ev["name"], str) and ev["name"]
        if ev["ph"] == "X":
            assert ev["ts"] >= 0 and ev["dur"] >= 0
            xs.append(ev)
        else:
            assert ev["name"] in ("process_name", "thread_name")
            assert ev["args"]["name"]
            metas.append(ev)
    # every tid with spans has a thread_name metadata event
    named = {m["tid"] for m in metas if m["name"] == "thread_name"}
    assert {e["tid"] for e in xs} <= named
    # nesting consistency per tid: spans either nest or are disjoint —
    # partial overlap would render as garbage in Perfetto
    for tid in {e["tid"] for e in xs}:
        evs = sorted((e for e in xs if e["tid"] == tid),
                     key=lambda e: (e["ts"], -e["dur"]))
        eps = 0.5   # rounding slack, microseconds
        for a, b in zip(evs, evs[1:]):
            a_end = a["ts"] + a["dur"]
            assert (b["ts"] + eps >= a_end           # disjoint
                    or b["ts"] + b["dur"] <= a_end + eps), \
                "partial overlap %s / %s" % (a["name"], b["name"])
    return xs


def test_chrome_trace_schema_multi_thread(tmp_path):
    t = SpanTracer().configure("on")
    with t.span("main/outer"):
        with t.span("main/inner"):
            pass

    def worker():
        with t.span("worker/span", trace_id=t.new_trace_id()):
            pass

    th = threading.Thread(target=worker, name="trace-test-worker")
    th.start()
    th.join()
    doc = t.chrome_trace()
    xs = _assert_chrome_schema(doc)
    assert {e["name"] for e in xs} == {"main/outer", "main/inner",
                                       "worker/span"}
    assert len({e["tid"] for e in xs}) == 2
    thread_names = {m["args"]["name"] for m in doc["traceEvents"]
                    if m["ph"] == "M" and m["name"] == "thread_name"}
    assert "trace-test-worker" in thread_names
    # the whole document must survive a json round-trip on disk
    p = tmp_path / "trace.json"
    n = t.dump(str(p))
    assert n == len(json.loads(p.read_text())["traceEvents"])


def test_booster_dump_trace(tmp_path):
    X, y = _data(seed=1)
    tracer.clear()
    bst = lgb.train(dict(PARAMS, trace_spans="on"),
                    lgb.Dataset(X, label=y), num_boost_round=4)
    p = tmp_path / "train_trace.json"
    n = bst.dump_trace(str(p))
    doc = json.loads(p.read_text())
    assert n == len(doc["traceEvents"])
    xs = _assert_chrome_schema(doc)
    names = {e["name"] for e in xs}
    assert "lgbtpu/train_block" in names       # engine block span
    assert "lgbtpu/fused_dispatch" in names    # fused host-side span


# ------------------------------------------------------------- serve chain

SERVE_CHAIN = ("serve/http_request", "serve/queue_wait", "serve/coalesce",
               "serve/batch", "serve/session_dispatch", "serve/slice_back")


def test_one_served_request_yields_full_span_chain(tmp_path):
    from urllib.request import Request, urlopen
    from lightgbm_tpu.serve import PredictServer

    X, y = _data(seed=2)
    bst = lgb.train(dict(PARAMS), lgb.Dataset(X, label=y),
                    num_boost_round=6)
    server = PredictServer(bst, port=0, buckets=(64,), warmup=True,
                           max_wait_ms=1.0)
    tracer.configure("serve_only")     # after warmup: only the request
    tracer.clear()
    host, port = server.address
    th = threading.Thread(target=server.serve_forever, daemon=True,
                          name="trace-test-http")
    th.start()
    try:
        body = json.dumps({"rows": X[:3].tolist()}).encode()
        req = Request("http://%s:%d/predict" % (host, port), data=body,
                      headers={"Content-Type": "application/json"})
        with urlopen(req, timeout=30) as resp:
            assert json.loads(resp.read())["rows"] == 3
    finally:
        server.shutdown()
        th.join(timeout=10)
        server.close()
    spans = tracer.events()
    by_name = {}
    for sp in spans:
        by_name.setdefault(sp.name, sp)
    assert set(SERVE_CHAIN) <= set(by_name), sorted(by_name)
    # the whole chain carries the request's trace id
    rid = by_name["serve/http_request"].trace_id
    assert rid is not None
    for name in SERVE_CHAIN:
        assert by_name[name].trace_id == rid, name
    # chain crosses threads: handler thread != batcher worker thread
    assert by_name["serve/http_request"].tid != by_name["serve/batch"].tid
    # and the export is schema-valid
    xs = _assert_chrome_schema(tracer.chrome_trace())
    assert set(SERVE_CHAIN) <= {e["name"] for e in xs}


# ------------------------------------------------------------ dump surfaces

def test_sigusr2_dumps_trace(tmp_path):
    if not hasattr(signal, "SIGUSR2"):
        pytest.skip("platform without SIGUSR2")
    tracer.configure("on")
    with tracer.span("unit/sig"):
        pass
    trace_path = tmp_path / "sig_trace.json"
    tele_path = tmp_path / "sig_tele.json"
    old2 = signal.getsignal(signal.SIGUSR2)
    old1 = signal.getsignal(signal.SIGUSR1)
    try:
        installed = install_signal_handlers(telemetry_path=str(tele_path),
                                            trace_path=str(trace_path))
        assert "SIGUSR2" in installed and "SIGUSR1" in installed
        os.kill(os.getpid(), signal.SIGUSR2)
        os.kill(os.getpid(), signal.SIGUSR1)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not (
                trace_path.exists() and tele_path.exists()):
            time.sleep(0.01)
        doc = json.loads(trace_path.read_text())
        assert "unit/sig" in {e["name"] for e in doc["traceEvents"]}
        assert "counters" in json.loads(tele_path.read_text())
    finally:
        signal.signal(signal.SIGUSR2, old2)
        signal.signal(signal.SIGUSR1, old1)


def test_periodic_telemetry_dump(tmp_path):
    p = tmp_path / "periodic.json"
    stop = start_periodic_telemetry_dump(str(p), 0.05)
    try:
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not p.exists():
            time.sleep(0.01)
        assert p.exists()
        assert "counters" in json.loads(p.read_text())
    finally:
        stop.set()


# -------------------------------------------------------------------- cli

def test_cli_dump_trace_flag(tmp_path):
    from lightgbm_tpu import cli
    from lightgbm_tpu.cli import parse_args

    p = parse_args(["--dump-trace", "/tmp/t.json", "task=train"])
    assert p["dump_trace"] == "/tmp/t.json"
    p = parse_args(["--dump-trace=/tmp/u.json"])
    assert p["dump_trace"] == "/tmp/u.json"

    X, y = _data(n=200, seed=3)
    data = tmp_path / "train.csv"
    np.savetxt(data, np.column_stack([y, X]), delimiter=",")
    out = tmp_path / "cli_trace.json"
    model = tmp_path / "model.txt"
    cli.main(["task=train", "data=%s" % data, "objective=binary",
              "num_leaves=4", "num_iterations=2", "verbosity=-1",
              "trace_spans=on", "output_model=%s" % model,
              "--dump-trace", str(out)])
    doc = json.loads(out.read_text())
    assert any(e["ph"] == "X" for e in doc["traceEvents"])
