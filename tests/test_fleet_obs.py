"""Fleet observatory tests (ISSUE 15): cross-process trace correlation,
convergence-lag metrics and the federated fleet status plane.

The contracts under test: a replica poll running under serve tracing
carries its trace id over the HTTP transport as ``X-Trace-Id``, so the
trainer-side handler spans and the replica-side poll/swap spans share
ONE trace id across two processes (one merged Perfetto load, two
distinct process identities); every node — trainer, standby, replica,
local or remote — heartbeats a compact latest-wins summary into the
store, and one ``fleetctl status`` call against the trainer renders the
whole fleet (role, version, skew, publish->adopt lag) from a single
``GET /fleet/status``; and heartbeats are pure observability — they
never grow the event log, never perturb replay/compaction, and work on
read-only replica store opens.
"""
import json
import os
import subprocess
import sys
import textwrap
import threading
import time
from urllib.error import HTTPError
from urllib.request import Request, urlopen

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu.fleet import FleetStore, ReplicaWatcher  # noqa: E402
from lightgbm_tpu.fleet.transport import RemoteStore  # noqa: E402
from lightgbm_tpu.obs import telemetry  # noqa: E402
from lightgbm_tpu.obs_trace import TRACE_HEADER, tracer  # noqa: E402
from lightgbm_tpu.online import OnlineTrainer  # noqa: E402
from lightgbm_tpu.serve import PredictServer  # noqa: E402

from tests.conftest import clean_cpu_env  # noqa: E402

W = np.array([1.2, -0.8, 0.5, 0.0, 0.3, -0.4])


@pytest.fixture(autouse=True)
def _tracer_reset():
    """Tests here flip the process-global tracer mode and identity; both
    must not leak into the rest of the suite."""
    yield
    tracer.configure("off")
    tracer.clear()
    tracer.set_identity(None, None)


def _data(n, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, len(W))
    y = (X @ W + 0.2 * rng.randn(n) > 0).astype(np.float64)
    return X, y


def _train(n=300, seed=0, rounds=6):
    X, y = _data(n, seed)
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "min_data_in_leaf": 5}
    return lgb.train(params, lgb.Dataset(X, label=y),
                     num_boost_round=rounds)


def _request(url, obj=None, headers=None, timeout=30):
    """(status, response headers, parsed body) — non-2xx included."""
    data = json.dumps(obj).encode() if obj is not None else None
    hdrs = {"Content-Type": "application/json"} if obj is not None else {}
    hdrs.update(headers or {})
    req = Request(url, data=data, headers=hdrs)
    try:
        with urlopen(req, timeout=timeout) as resp:
            return resp.status, dict(resp.headers), json.loads(resp.read())
    except HTTPError as exc:
        return exc.code, dict(exc.headers), json.loads(exc.read())


def _start_server(server):
    th = threading.Thread(target=server.serve_forever,
                          name="fleet-obs-test-http", daemon=True)
    th.start()
    return th


def _fleetctl():
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import fleetctl
    finally:
        sys.path.pop(0)
    return fleetctl


# ----------------------------------------------------- federated status plane

@pytest.mark.slow
def test_fleetctl_status_federates_trainer_and_replicas(tmp_path, capsys):
    """Acceptance e2e: trainer + 2 replicas (one over RemoteStore), one
    ``fleetctl status`` call reports per-node role, model version,
    version skew and publish->adopt lag."""
    fleetctl = _fleetctl()
    bst = _train()
    store = FleetStore(str(tmp_path), "default")
    store.publish(bst.model_to_string(), event="boot")

    trainer = OnlineTrainer(bst, trigger_rows=10**9, min_rows=64,
                            shadow_rows=10**6, promote_threshold=2.0,
                            promote_patience=2, store=store,
                            holder_id="trainer-1", start=False)
    server = PredictServer(_train(seed=1), port=0, warmup=False)
    server.fleet_store = store
    _start_server(server)
    host, port = server.address
    base = "http://%s:%d" % (host, port)
    try:
        # replica A: shared-filesystem store, replica-role read_only open
        bst_fs = lgb.Booster(model_str=_train(seed=2).model_to_string())
        w_fs = ReplicaWatcher(
            bst_fs, FleetStore(str(tmp_path), "default", read_only=True),
            node_id="replica-fs", start=False)
        # replica B: behind the HTTP transport
        bst_remote = lgb.Booster(model_str=_train(seed=3).model_to_string())
        w_remote = ReplicaWatcher(
            bst_remote, RemoteStore(base, timeout_s=10.0),
            node_id="replica-remote", start=False)
        assert w_fs.poll_once() and w_remote.poll_once()

        # every node beats once: trainer straight into the store, the
        # fs replica likewise, the remote replica POSTs over the wire
        assert trainer.maybe_heartbeat(force=True)
        assert w_fs.maybe_heartbeat(force=True)
        assert w_remote.maybe_heartbeat(force=True)

        doc = fleetctl.fetch_status(base)
        assert doc["head_version"] == 1
        assert doc["model_id"] == "default"
        nodes = {n["node"]: n for n in doc["nodes"]}
        assert set(nodes) == {"trainer-1", "replica-fs", "replica-remote"}
        assert nodes["trainer-1"]["role"] == "solo"   # no lease configured
        for name in ("replica-fs", "replica-remote"):
            n = nodes[name]
            assert n["role"] == "replica"
            assert n["version"] == 1 and n["skew"] == 0
            # publish->adopt lag measured off the publish event's ts
            assert n["lag_ms"]["last"] is not None
            assert 0.0 <= n["lag_ms"]["last"] < 60_000.0
            assert n["lag_ms"]["p50"] is not None
            assert n["consec_poll_errors"] == 0
            assert n["age_s"] >= 0.0
        # the rollup carries the store vitals fleetctl's header line shows
        assert doc["log_bytes"] > 0 and doc["compactions"] >= 0
        assert "lease" in doc

        # the rendered table names every node with its role and version
        lines = fleetctl.render_status(doc)
        text = "\n".join(lines)
        for fragment in ("trainer-1", "replica-fs", "replica-remote",
                         "solo", "replica"):
            assert fragment in text
        assert fleetctl.main(["status", base]) == 0
        assert fleetctl.main(["lag", base]) == 0
        assert fleetctl.main(["tail", base, "-n", "5"]) == 0
        out = capsys.readouterr().out
        assert "replica-remote" in out and "v" in out
    finally:
        server.close()
        trainer.close()


def test_fleet_status_and_heartbeat_routes(tmp_path):
    server = PredictServer(_train(), port=0, warmup=False)
    _start_server(server)
    host, port = server.address
    base = "http://%s:%d" % (host, port)
    try:
        # no store attached: both surfaces answer 404, not a crash
        code, _, body = _request(base + "/fleet/status")
        assert code == 404 and "error" in body
        code, _, _ = _request(base + "/fleet/heartbeat", {"node": "n1"})
        assert code == 404

        store = FleetStore(str(tmp_path), "default")
        server.fleet_store = store
        code, _, body = _request(base + "/fleet/status")
        assert code == 200 and body["nodes"] == []

        # federation intake: a remote node's POST lands in the store
        code, _, body = _request(base + "/fleet/heartbeat",
                                 {"node": "edge-1", "role": "replica",
                                  "version": 0})
        assert code == 200 and body == {"ok": True}
        assert [h["node"] for h in store.heartbeats()] == ["edge-1"]
        # and the rollup serves it back, skew computed server-side
        code, _, body = _request(base + "/fleet/status")
        assert code == 200
        assert body["nodes"][0]["node"] == "edge-1"
        assert body["nodes"][0]["skew"] == 0

        # a heartbeat without a node id is a client error
        code, _, _ = _request(base + "/fleet/heartbeat", {"role": "x"})
        assert code == 400
    finally:
        server.close()


def test_fleetctl_unreachable_exits_nonzero():
    fleetctl = _fleetctl()
    # nothing listens on a fresh ephemeral port 1: connection refused
    assert fleetctl.main(["status", "http://127.0.0.1:9",
                          "--timeout", "0.5"]) == 1


# --------------------------------------------------- cross-process tracing

_REPLICA_CHILD = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, %(repo)r)
    import lightgbm_tpu as lgb
    from lightgbm_tpu.fleet import ReplicaWatcher
    from lightgbm_tpu.fleet.transport import RemoteStore
    from lightgbm_tpu.obs_trace import tracer

    base, model_path, out_path = sys.argv[1:4]
    tracer.configure("serve_only")
    tracer.set_identity(role="replica", holder="replica-child")
    bst = lgb.Booster(model_file=model_path)
    w = ReplicaWatcher(bst, RemoteStore(base, timeout_s=30.0),
                       node_id="replica-child", start=False)
    assert w.poll_once(), "expected the child to adopt v1"
    assert w.maybe_heartbeat(force=True)
    with open(out_path, "w") as f:
        json.dump(tracer.chrome_trace(), f)
    print("ADOPTED", w.applied_version, flush=True)
""")


def _span_trace_ids(doc, name):
    return {ev["args"]["trace_id"] for ev in doc["traceEvents"]
            if ev.get("ph") == "X" and ev["name"] == name
            and "trace_id" in ev.get("args", {})}


def _process_meta(doc):
    names = [ev["args"]["name"] for ev in doc["traceEvents"]
             if ev.get("ph") == "M" and ev["name"] == "process_name"]
    assert len(names) == 1
    return names[0]


@pytest.mark.slow
def test_remote_adoption_is_one_trace_across_two_processes(tmp_path):
    """Acceptance: a Chrome/Perfetto export from a remote-replica
    adoption contains trainer-side and replica-side spans sharing one
    trace id, under two distinct process identities."""
    bst = _train()
    store = FleetStore(str(tmp_path), "default")
    store.publish(bst.model_to_string(), event="boot")
    base_model = str(tmp_path / "base.txt")
    _train(seed=4).save_model(base_model)

    tracer.configure("serve_only")
    tracer.clear()
    tracer.set_identity(role="trainer", holder="trainer-parent")
    server = PredictServer(_train(seed=1), port=0, warmup=False)
    server.fleet_store = store
    _start_server(server)
    host, port = server.address
    base = "http://%s:%d" % (host, port)
    out_path = str(tmp_path / "replica_trace.json")
    script = tmp_path / "replica_child.py"
    script.write_text(_REPLICA_CHILD % {"repo": REPO})
    try:
        proc = subprocess.run(
            [sys.executable, str(script), base, base_model, out_path],
            env=clean_cpu_env(4), capture_output=True, text=True,
            timeout=600)
        assert "ADOPTED 1" in proc.stdout, (proc.stdout, proc.stderr)
        doc_trainer = tracer.chrome_trace()
        with open(out_path, encoding="utf-8") as f:
            doc_replica = json.load(f)

        # the replica's poll id crossed the wire: the trainer handler
        # spans for /fleet/* carry the SAME trace id
        poll_ids = _span_trace_ids(doc_replica, "fleet/replica_poll")
        serve_ids = _span_trace_ids(doc_trainer, "serve/fleet_request")
        assert len(poll_ids) == 1
        shared = poll_ids & serve_ids
        assert shared, (poll_ids, serve_ids)
        # the swap span nested under the poll inherits the id too
        assert _span_trace_ids(doc_replica, "fleet/replica_swap") == poll_ids
        # a poll drives several transport requests (latest + artifact
        # fetch at minimum) — all joined under the one trace
        trainer_spans = [ev for ev in doc_trainer["traceEvents"]
                         if ev.get("ph") == "X"
                         and ev["name"] == "serve/fleet_request"
                         and ev.get("args", {}).get("trace_id")
                         in shared]
        assert len(trainer_spans) >= 2

        # two processes, two identities: distinct pids, distinct
        # process_name metas a merged Perfetto load keeps apart
        pids = {ev["pid"] for ev in trainer_spans}
        pids |= {ev["pid"] for ev in doc_replica["traceEvents"]
                 if ev.get("ph") == "X"}
        assert len(pids) == 2
        assert _process_meta(doc_trainer) == \
            "lightgbm-tpu [trainer trainer-parent]"
        assert _process_meta(doc_replica) == \
            "lightgbm-tpu [replica replica-child]"
        # pid-salted ids: the shared id encodes the CHILD's pid
        child_pid = (set(pids) - {os.getpid()}).pop()
        assert (next(iter(shared)) >> 40) == (child_pid & 0x3FFFFF)

        # federation rode along: the child's heartbeat POST landed
        assert [h["node"] for h in store.heartbeats()] == ["replica-child"]
    finally:
        server.close()


def test_predict_echoes_trace_id_header(tmp_path):
    server = PredictServer(_train(), port=0, warmup=False)
    _start_server(server)
    host, port = server.address
    url = "http://%s:%d/predict" % (host, port)
    X, _ = _data(4, seed=9)
    try:
        # tracing OFF: the echo still works (header-only correlation for
        # external clients) and records zero spans on the hot path
        assert not tracer.serve_on
        started0 = tracer.spans_started
        code, headers, body = _request(
            url, {"rows": X.tolist()}, headers={TRACE_HEADER: "424242"})
        assert code == 200 and len(body["predictions"]) == 4
        assert headers[TRACE_HEADER] == "424242"
        # no header sent: the server mints one and still echoes it
        code, headers, _ = _request(url, {"rows": X.tolist()})
        assert code == 200
        minted = int(headers[TRACE_HEADER])
        assert (minted >> 40) == (os.getpid() & 0x3FFFFF)
        assert tracer.spans_started == started0

        # tracing ON: the client's id is adopted by the request spans
        tracer.configure("serve_only")
        tracer.clear()
        code, headers, _ = _request(
            url, {"rows": X.tolist()}, headers={TRACE_HEADER: "7777"})
        assert code == 200 and headers[TRACE_HEADER] == "7777"
        assert any(sp.trace_id == 7777 for sp in tracer.events()), \
            [(sp.name, sp.trace_id) for sp in tracer.events()]
        # bad rows: the error response carries the echo too
        code, headers, body = _request(
            url, {"rows": [["oops"]]}, headers={TRACE_HEADER: "31337"})
        assert code == 400 and headers[TRACE_HEADER] == "31337"
    finally:
        server.close()


# -------------------------------------------------- /healthz adoption state

def test_healthz_surfaces_replica_adoption_state(tmp_path):
    bst_serving = _train(seed=1)
    store = FleetStore(str(tmp_path), "default")
    store.publish(_train().model_to_string(), event="boot")
    server = PredictServer(bst_serving, port=0, warmup=False)
    server.fleet_watcher = ReplicaWatcher(bst_serving, store,
                                          node_id="hz-replica", start=False)
    _start_server(server)
    host, port = server.address
    try:
        assert server.fleet_watcher.poll_once()
        code, _, doc = _request("http://%s:%d/healthz" % (host, port))
        assert code == 200
        fl = doc["fleet"]
        assert fl["node"] == "hz-replica" and fl["role"] == "replica"
        assert fl["applied_version"] == 1 and fl["head_version"] == 1
        assert fl["version_skew"] == 0
        assert fl["last_adopt_lag_ms"] is not None
        assert fl["last_adopt_lag_ms"] >= 0.0
        assert fl["consec_poll_errors"] == 0
        assert fl["poll_backoff_s"] == 0.0
        assert fl["heartbeats"] == {"interval_s": 0.0, "sent": 0,
                                    "errors": 0}
    finally:
        server.close()


@pytest.mark.slow
def test_watcher_convergence_metrics(tmp_path):
    """The lag histogram and skew gauge feed off real publish
    timestamps; consecutive-error tracking resets on success."""
    store = FleetStore(str(tmp_path), "default")
    bst = lgb.Booster(model_str=_train(seed=2).model_to_string())
    w = ReplicaWatcher(bst, store, node_id="m-replica", start=False)
    polls0 = telemetry.counter("fleet/replica_polls")
    store.publish(_train().model_to_string(), event="boot")
    store.publish(_train(seed=3, rounds=8).model_to_string())
    assert w.poll_once()                       # jumps straight to head v2
    assert telemetry.counter("fleet/replica_polls") == polls0 + 1
    snap = telemetry.snapshot()
    assert snap["gauges"]["fleet/version_skew"] == 0
    hist = telemetry.histogram("fleet/publish_adopt_lag_ms")
    assert hist is not None and hist["count"] >= 1
    doc = w.heartbeat_doc()
    assert doc["version"] == 2 and doc["skew"] == 0
    assert doc["lag_ms"]["p50"] is not None
    assert doc["lag_ms"]["p99"] >= doc["lag_ms"]["p50"] >= 0.0


# --------------------------------------------------- heartbeat substrate

def test_heartbeats_never_grow_the_event_log(tmp_path):
    store = FleetStore(str(tmp_path), "m")
    store.publish("model-one", event="boot")
    log_bytes = os.path.getsize(store.events_path)
    for i in range(50):
        assert store.record_heartbeat({"node": "n-a", "seq": i})
    assert store.record_heartbeat({"node": "n-b"})
    # latest-wins sidecars: O(nodes) files, the event log untouched
    assert os.path.getsize(store.events_path) == log_bytes
    assert store.state()["events_log_bytes"] == log_bytes
    hbs = store.heartbeats()
    assert [h["node"] for h in hbs] == ["n-a", "n-b"]
    assert hbs[0]["seq"] == 49                 # only the newest beat kept
    assert all("ts" in h for h in hbs)
    assert store.state()["heartbeat_nodes"] == 2
    # replay sees exactly the published events, none of the heartbeats
    fresh = FleetStore(str(tmp_path), "m")
    assert [e["kind"] for e in fresh.events()] == ["publish"]

    # age filtering drops nodes that stopped reporting
    time.sleep(0.05)
    assert store.heartbeats(max_age_s=0.01) == []
    assert len(store.heartbeats(max_age_s=60.0)) == 2

    # a node id is required; junk ids are sanitized into a filename
    assert not store.record_heartbeat({"role": "replica"})
    assert store.record_heartbeat({"node": "../../../evil node"})
    hb_dir = os.path.join(str(tmp_path), "m", "heartbeats")
    names = os.listdir(hb_dir)
    assert all("/" not in n and " " not in n for n in names)

    # a torn sidecar (crash mid-beat) is skipped, not fatal
    torn = os.path.join(hb_dir, "torn.json")
    with open(torn, "w", encoding="utf-8") as f:
        f.write('{"node": "to')
    assert [h["node"] for h in store.heartbeats(max_age_s=60.0)
            if h["node"] == "torn"] == []


def test_read_only_replica_store_can_heartbeat(tmp_path):
    FleetStore(str(tmp_path), "m").publish("model-one")
    ro = FleetStore(str(tmp_path), "m", read_only=True)
    # publishing is fenced off for replica-role opens...
    from lightgbm_tpu.utils.log import LightGBMError
    with pytest.raises(LightGBMError):
        ro.publish("nope")
    # ...but heartbeats are observability, not replicated state
    assert ro.record_heartbeat({"node": "ro-replica", "version": 1})
    assert [h["node"] for h in ro.heartbeats()] == ["ro-replica"]


# ----------------------------------------------------------- ledger rollup

def test_ledger_serve_entries_carry_fleet_identity(tmp_path, capsys):
    from lightgbm_tpu import obs_ledger
    from lightgbm_tpu.config import Config
    path = str(tmp_path / "ledger.jsonl")
    cfg = Config.from_params({"objective": "binary", "verbosity": -1,
                              "obs_ledger": True, "obs_ledger_path": path})
    extra = {"fleet": {"role": "standby", "holder": "host-a:123",
                       "lease_epoch": 7}}
    entry = obs_ledger.record_run(cfg, "serve", 0, 0, extra=extra)
    assert entry is not None and entry["extra"]["fleet"]["role"] == "standby"
    obs_ledger.record_run(cfg, "serve", 0, 0)      # a fleet-less serve run

    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import ledger as ledger_cli
    finally:
        sys.path.pop(0)
    assert ledger_cli.main(["list", "--path", path]) == 0
    out = capsys.readouterr().out
    # the list view distinguishes trainer/standby/replica runs
    assert "standby@7 host-a:123" in out
    assert "fleet" in out.lower()                  # column header
