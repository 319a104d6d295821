"""Stdlib-HTTP JSON prediction endpoint (``task=serve`` in the CLI).

    POST /predict              {"rows": [[f0, f1, ...], ...]}
                               -> {"predictions": [...], "rows": n,
                                   "model_version": v}
    POST /predict/<model_id>   same, routed to one registry entry
                               (also: {"model": "<id>"} in the body)
    POST /ingest[/<model_id>]  {"rows": [[...]], "labels": [...]}
                               feed labeled traffic to the model's
                               OnlineTrainer (409 if online training is
                               off for that model)
    GET  /healthz              liveness + per-model version/queue/online
                               state, registry size, uptime
    GET  /models               registered model ids
    GET  /telemetry            full obs.Telemetry snapshot
    GET  /metrics              Prometheus text exposition format
    GET  /fleet/latest         newest fleet publish event (trainer mode)
    GET  /fleet/publishes      all valid publish events oldest-first
    GET  /fleet/artifact/<v>   raw whole-model artifact bytes
    GET  /fleet/status         federated rollup: head version, lease,
                               every node's latest heartbeat with skew
    GET  /fleet/events         the whole event log (remote replay)
    GET  /fleet/snapshot/<id>  raw snapshot blob (remote cold bootstrap)
    POST /fleet/heartbeat      remote nodes report their heartbeat docs
    POST /fleet/lease          remote lease acquire/renew/release/state
    POST /fleet/publish        sha256-verified model upload, fenced by
                               (holder, lease_epoch); zombie epoch: 409
    POST /fleet/ingest         append one labeled chunk to the store log
    POST /fleet/gate           append one promotion-gate record
    POST /fleet/compact        run log compaction (snapshot mode incl.)

The /fleet routes exist when the CLI attaches a local ``FleetStore``
(``server.fleet_store``). The GETs are the network transport remote
replicas (:class:`~lightgbm_tpu.fleet.transport.RemoteStore`) converge
through; the POSTs are the control plane's write surface
(:class:`~lightgbm_tpu.fleet.control.RemoteWriteStore`) — fencing is
enforced server-side under the store lock, so a remote zombie's stale
epoch is rejected 409 (with a ``leader_hint``) exactly like a local
one. Both carry the ``transport/serve`` chaos point (slow/torn/dropped
responses for the failover tests). The write routes answer during a
drain: a draining store host must keep serving lease renewals or a
healthy remote trainer would demote for no reason.

Multi-tenant: the server fronts a
:class:`~lightgbm_tpu.online.registry.ModelRegistry`; the single-model
constructor registers its booster under id ``"default"``. Admission
control: an over-limit submit under the shed policy returns **429**;
during graceful shutdown (:meth:`PredictServer.begin_shutdown`, wired to
SIGTERM by the CLI) every new request gets **503** while already-queued
work drains to completion.

With span tracing on (``trace_spans=on|serve_only``), each POST opens a
``serve/http_request`` span carrying a fresh trace id that the batcher
threads through queue_wait -> coalesce -> batch -> session_dispatch ->
slice_back, so one request yields a full chain in the flight recorder.

``ThreadingHTTPServer`` gives one handler thread per connection, so
concurrent POSTs land in the MicroBatcher together and coalesce into one
device dispatch. No dependencies beyond the standard library.
"""
from __future__ import annotations

import hashlib
import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional, Sequence

import numpy as np

from .. import obs
from ..obs import telemetry
from ..obs_trace import TRACE_HEADER, format_trace_id, parse_trace_id, tracer
from ..utils.log import LightGBMError, Log
from .batcher import QueueFullError


class PredictServer:
    """ModelRegistry (PredictSessions + MicroBatchers) behind a stdlib
    HTTP server.

    Single-model: ``PredictServer(booster, ...)`` (registered as
    ``"default"``; ``server.session``/``server.batcher`` keep pointing at
    it). Multi-tenant: build a
    :class:`~lightgbm_tpu.online.registry.ModelRegistry` yourself and
    pass ``registry=``. ``online`` (an OnlineTrainer or its kwargs dict)
    attaches continual training to the single-model constructor's entry.

    ``port=0`` binds an ephemeral port (tests); read it back from
    ``server.address``. ``serve_forever()`` blocks; call ``close()`` (any
    thread) to stop the server and the batcher workers, or
    ``begin_shutdown()`` for the draining path (refuse new work with 503,
    let queued requests finish, then unblock serve_forever).
    """

    def __init__(self, model=None, *, registry=None,
                 host: str = "127.0.0.1", port: int = 8080,
                 max_batch_rows: int = 8192, max_wait_ms: float = 2.0,
                 buckets: Optional[Sequence[int]] = None,
                 raw_score: bool = False, warmup: bool = True,
                 request_timeout_s: float = 30.0,
                 max_queue_rows: int = 0, overload: str = "shed",
                 tenant_quota_rows: int = 0, tenant_weights=None,
                 dispatch_mode: str = "continuous",
                 online=None) -> None:
        from ..online.registry import ModelRegistry

        if registry is None:
            if model is None:
                raise LightGBMError(
                    "PredictServer needs a model or a registry")
            registry = ModelRegistry()
            registry.register("default", model, buckets=buckets,
                              max_batch_rows=max_batch_rows,
                              max_wait_ms=max_wait_ms,
                              max_queue_rows=max_queue_rows,
                              overload=overload,
                              tenant_quota_rows=tenant_quota_rows,
                              tenant_weights=tenant_weights,
                              raw_score=raw_score,
                              dispatch_mode=dispatch_mode,
                              warmup=warmup, online=online)
        elif model is not None or online is not None:
            raise LightGBMError(
                "pass either model/online or a pre-built registry, "
                "not both")
        self.registry = registry
        self.request_timeout_s = float(request_timeout_s)
        # fleet replica mode: the CLI attaches the ReplicaWatcher here so
        # /healthz reports applied version/swaps and close() stops it
        self.fleet_watcher = None
        # fleet trainer mode: a local FleetStore attached here turns on
        # the /fleet/* transport routes + the /healthz store section
        self.fleet_store = None
        # remote-replica mode: the RemoteStore, for /healthz retry stats
        self.fleet_transport = None
        # control plane: an IngestForwarder attached here relays labeled
        # traffic hitting this node to the current lease holder instead
        # of 409ing it on the floor
        self.ingest_forwarder = None
        self._started_at = obs.monotonic()
        # guards the draining flag: flipped by begin_shutdown (signal
        # helper thread) and read on every handler thread
        self._lock = threading.Lock()
        self._draining = False
        server = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):  # default writes to stderr
                Log.debug("serve: " + fmt % args)

            def _json(self, code: int, obj, headers=None) -> None:
                body = json.dumps(obj).encode("utf-8")
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for key, value in (headers or {}).items():
                    self.send_header(key, value)
                self.end_headers()
                self.wfile.write(body)

            def _raw(self, code: int, body: bytes, ctype: str) -> None:
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/healthz":
                    self._json(200, server.healthz())
                elif self.path == "/models":
                    self._json(200, {"models": server.registry.ids()})
                elif self.path == "/telemetry":
                    self._json(200, telemetry.snapshot())
                elif self.path == "/metrics":
                    self._raw(200, obs.prometheus_text().encode("utf-8"),
                              "text/plain; version=0.0.4")
                elif self.path.startswith("/fleet/"):
                    self._fleet()
                else:
                    self._json(404, {"error": "unknown path %s" % self.path})

            def _fleet(self) -> None:
                """The replica-facing transport routes, serving the
                attached local store's publish feed + artifacts. A torn
                chaos action truncates the response body (Content-Length
                included, so the client's checksum — not a short-read
                error — must catch it); a raise action answers 500.

                When serve tracing is on, an ``X-Trace-Id`` sent by the
                remote replica's transport joins this handler's span to
                the replica's poll trace — the trainer half of the
                cross-process adoption trace."""
                if not tracer.serve_on:
                    self._fleet_impl()
                    return
                tid = parse_trace_id(self.headers.get(TRACE_HEADER))
                with tracer.span("serve/fleet_request", domain="serve",
                                 trace_id=tid, path=self.path):
                    self._fleet_impl()

            def _fleet_impl(self) -> None:
                store = server.fleet_store
                if store is None:
                    self._json(404, {"error": "no fleet store attached"})
                    return
                from ..fleet import chaos
                try:
                    act = chaos.hit("transport/serve")
                except Exception as exc:
                    self._json(500, {"error": "%s: %s"
                                     % (type(exc).__name__, exc)})
                    return
                torn = float(act[1]) if act is not None \
                    and act[0] == "torn" else None

                def send(body: bytes, ctype: str) -> None:
                    if torn is not None:
                        body = body[:int(len(body) * torn)]
                    self._raw(200, body, ctype)

                seg = [s for s in self.path.split("/") if s]
                if seg == ["fleet", "status"]:
                    send(json.dumps(server.fleet_status())
                         .encode("utf-8"), "application/json")
                elif seg == ["fleet", "events"]:
                    # remote standby cold-boot replay: the whole event
                    # log in one response (with snapshot compaction on,
                    # this is a compact record + publishes + tail)
                    send(json.dumps({"events": list(store.events())})
                         .encode("utf-8"), "application/json")
                elif seg[:2] == ["fleet", "snapshot"] and len(seg) == 3:
                    try:
                        sid = int(seg[2])
                    except ValueError:
                        self._json(404, {"error": "bad snapshot id %r"
                                         % seg[2]})
                        return
                    try:
                        with open(store.snapshot_path(sid), "rb") as f:
                            data = f.read()
                    except OSError:
                        self._json(404, {"error": "no snapshot s%06d"
                                         % sid})
                        return
                    send(data, "application/json")
                elif seg == ["fleet", "latest"]:
                    latest = store.latest_publish()
                    if latest is None:
                        self._json(404, {"error": "nothing published yet"})
                        return
                    send(json.dumps(latest).encode("utf-8"),
                         "application/json")
                elif seg == ["fleet", "publishes"]:
                    send(json.dumps({"publishes": store.publishes()})
                         .encode("utf-8"), "application/json")
                elif seg[:2] == ["fleet", "artifact"] and len(seg) == 3:
                    try:
                        version = int(seg[2])
                    except ValueError:
                        self._json(404, {"error": "bad version %r" % seg[2]})
                        return
                    try:
                        with open(store.artifact_path(version), "rb") as f:
                            data = f.read()
                    except OSError:
                        self._json(404, {"error": "no artifact v%d"
                                         % version})
                        return
                    send(data, "text/plain; charset=utf-8")
                else:
                    self._json(404, {"error": "unknown path %s" % self.path})

            def do_POST(self):
                try:
                    length = int(self.headers.get("Content-Length") or 0)
                    payload = json.loads(self.rfile.read(length) or b"{}")
                except Exception as exc:
                    self._json(400, {"error": "bad request body: %s" % exc})
                    return
                if self.path == "/fleet/heartbeat":
                    # federation intake: remote nodes POST their
                    # heartbeats here; observability stays up while the
                    # serve plane drains, so this precedes the 503 gate
                    self._fleet_heartbeat(payload)
                    return
                if self.path.startswith("/fleet/"):
                    # the control plane's write surface (remote lease,
                    # fenced publish, ingest/gate appends, compaction).
                    # Like heartbeats it precedes the drain gate: a
                    # draining store host must keep answering lease
                    # renewals and fence checks or a healthy remote
                    # trainer demotes for no reason
                    self._fleet_post(payload)
                    return
                if server.draining():
                    telemetry.count("serve/drain_rejected")
                    self._json(503, {"error": "server is draining"})
                    return
                seg = [s for s in self.path.split("/") if s]
                route = seg[0] if seg else ""
                if route not in ("predict", "ingest") or len(seg) > 2:
                    self._json(404, {"error": "unknown path %s" % self.path})
                    return
                model_id = seg[1] if len(seg) == 2 \
                    else payload.get("model")
                try:
                    entry = server.registry.get(model_id)
                except KeyError as exc:
                    self._json(404, {"error": str(exc)})
                    return
                if route == "predict":
                    self._predict(entry, payload)
                else:
                    self._ingest(entry, payload)

            def _fleet_post(self, payload) -> None:
                """``POST /fleet/{lease,publish,ingest,gate,compact}`` —
                the store host's half of the remote write surface.
                Every route needs the attached local store; fencing is
                enforced HERE, under the store's own lock, so a remote
                zombie's stale epoch dies exactly like a local one
                (409, with a ``leader_hint`` naming who holds the lease
                now). Chaos ``transport/serve`` actions apply as on the
                GET side: raise answers 500, torn truncates the body
                under an intact Content-Length."""
                store = server.fleet_store
                if store is None:
                    self._json(404, {"error": "no fleet store attached"})
                    return
                if not isinstance(payload, dict):
                    self._json(400, {"error": "body must be a JSON "
                                     "object"})
                    return
                from ..fleet import chaos
                from ..fleet.store import StaleLeaseError
                try:
                    act = chaos.hit("transport/serve")
                except Exception as exc:
                    self._json(500, {"error": "%s: %s"
                                     % (type(exc).__name__, exc)})
                    return
                torn = float(act[1]) if act is not None \
                    and act[0] == "torn" else None

                def send(code: int, obj) -> None:
                    body = json.dumps(obj).encode("utf-8")
                    if torn is not None:
                        body = body[:int(len(body) * torn)]
                        self._raw(code, body, "application/json")
                        return
                    self._json(code, obj)

                seg = [s for s in self.path.split("/") if s]
                route = seg[1] if len(seg) == 2 else ""
                try:
                    if route == "lease":
                        self._fleet_lease(store, payload, send)
                    elif route == "publish":
                        self._fleet_publish(store, payload, send)
                    elif route == "ingest":
                        store.append_ingest(payload["rows"],
                                            payload["labels"])
                        rows = payload.get("labels") or []
                        send(200, {"ok": True, "rows": len(rows)})
                    elif route == "gate":
                        store.append_gate(
                            payload["result"], int(payload["wins"]),
                            int(payload["consumed_rows"]),
                            payload.get("losses"))
                        send(200, {"ok": True})
                    elif route == "compact":
                        send(200, store.compact(
                            watermark=int(payload["watermark"]),
                            wins=int(payload["wins"]),
                            keep_rows=int(payload["keep_rows"]),
                            keep_artifacts=int(
                                payload.get("keep_artifacts", 0)),
                            snapshot_rows=int(
                                payload.get("snapshot_rows", 0))))
                    else:
                        self._json(404, {"error": "unknown path %s"
                                         % self.path})
                except StaleLeaseError as exc:
                    doc = {"error": str(exc)}
                    hint = server._leader_hint()
                    if hint:
                        doc["leader_hint"] = hint
                    send(409, doc)
                except (KeyError, TypeError, ValueError,
                        LightGBMError) as exc:
                    send(400, {"error": "%s: %s"
                               % (type(exc).__name__, exc)})

            def _fleet_lease(self, store, payload, send) -> None:
                op = payload.get("op")
                holder = payload.get("holder")
                url = payload.get("url") or None
                if op == "acquire":
                    epoch = store.acquire_lease(
                        str(holder), float(payload["ttl_s"]), url=url)
                    send(200, {"epoch": epoch,
                               "lease": store.lease_state()})
                elif op == "renew":
                    ok = store.renew_lease(
                        str(holder), int(payload["epoch"]),
                        float(payload["ttl_s"]), url=url)
                    send(200, {"ok": ok})
                elif op == "release":
                    ok = store.release_lease(str(holder),
                                             int(payload["epoch"]))
                    send(200, {"ok": ok})
                elif op == "state":
                    send(200, {"lease": store.lease_state()})
                else:
                    send(400, {"error": "unknown lease op %r" % op})

            def _fleet_publish(self, store, payload, send) -> None:
                model = payload.get("model")
                if not isinstance(model, str) or not model:
                    send(400, {"error": "publish needs a non-empty "
                               "model string"})
                    return
                data = model.encode("utf-8")
                want_sha = payload.get("sha256")
                want_bytes = int(payload.get("bytes", -1))
                got_sha = hashlib.sha256(data).hexdigest()
                if (want_bytes >= 0 and want_bytes != len(data)) \
                        or (want_sha and want_sha != got_sha):
                    # verify the UPLOAD before the fence: a torn body
                    # must never become an artifact, fenced or not
                    telemetry.count("fleet/upload_checksum_failures")
                    send(400, {"error": "model upload failed its "
                               "checksum (%d bytes, sha %s...)"
                               % (len(data), got_sha[:12])})
                    return
                fence = (str(payload.get("holder")),
                         int(payload.get("lease_epoch", 0)))
                version = store.publish(
                    model, str(payload.get("event", "promotion")),
                    payload.get("meta"), fence=fence)
                send(200, {"version": version})

            def _fleet_heartbeat(self, payload) -> None:
                store = server.fleet_store
                if store is None:
                    self._json(404, {"error": "no fleet store attached"})
                    return
                try:
                    ok = store.record_heartbeat(
                        payload if isinstance(payload, dict) else {})
                except Exception as exc:
                    self._json(500, {"error": "%s: %s"
                                     % (type(exc).__name__, exc)})
                    return
                if not ok:
                    self._json(400, {"error": "heartbeat needs a node id"})
                    return
                self._json(200, {"ok": True})

            def _predict(self, entry, payload) -> None:
                # trace correlation: adopt the client's X-Trace-Id when
                # sent, mint one otherwise, and echo it back on EVERY
                # response so external clients can correlate against
                # flight-recorder dumps (echoed even with tracing off —
                # minting is one counter increment, no span records)
                tid = parse_trace_id(self.headers.get(TRACE_HEADER)) \
                    or tracer.new_trace_id()
                echo = {TRACE_HEADER: format_trace_id(tid)}
                span_tid = tid if tracer.serve_on else None
                try:
                    X = np.asarray(payload["rows"], np.float64)
                    if X.ndim == 1:
                        X = X[None, :]
                    # tenant for fair queuing + per-tenant admission:
                    # header wins (proxies inject it), body is the
                    # curl-friendly fallback, absent means "default"
                    tenant = self.headers.get("X-Tenant") \
                        or payload.get("tenant")
                    with tracer.span("serve/http_request", domain="serve",
                                     trace_id=span_tid, rows=int(X.shape[0]),
                                     model=entry.model_id):
                        fut = entry.batcher.submit(X, trace_id=span_tid,
                                                   tenant=tenant)
                        out = fut.result(timeout=server.request_timeout_s)
                    self._json(200, {"predictions": out.tolist(),
                                     "rows": int(X.shape[0]),
                                     "model_version":
                                         entry.booster.inner.model_version},
                               echo)
                except QueueFullError as exc:
                    # admission control shed: fast 429 beats unbounded
                    # queueing; clients back off or retry elsewhere
                    self._json(429, {"error": "overloaded: %s" % exc}, echo)
                except Exception as exc:
                    self._json(400, {"error": "%s: %s"
                                     % (type(exc).__name__, exc)}, echo)

            def _ingest(self, entry, payload) -> None:
                if entry.online is None:
                    fwd = server.ingest_forwarder
                    hops = int(self.headers.get("X-Fleet-Hops") or 0)
                    if fwd is not None:
                        # this node cannot train on the rows, but the
                        # control plane knows who can: relay to the
                        # lease holder instead of dropping the chunk
                        try:
                            doc = fwd.forward(entry.model_id,
                                              payload.get("rows"),
                                              payload.get("labels"),
                                              hops=hops)
                        except Exception as exc:
                            self._json(503, {"error": "ingest forward "
                                             "failed: %s" % exc})
                            return
                        self._json(200, doc)
                        return
                    doc = {"error": "online training is not enabled "
                           "for model %r" % entry.model_id}
                    hint = server._leader_hint()
                    if hint:
                        # no forwarder here, but tell the client who IS
                        # the leader so it can re-aim itself
                        doc["leader_hint"] = hint
                    self._json(409, doc)
                    return
                try:
                    rows = np.asarray(payload["rows"], np.float64)
                    labels = np.asarray(payload["labels"], np.float64)
                    buffered = entry.online.ingest(rows, labels)
                    self._json(200, {"buffered_rows": int(buffered),
                                     "rows": int(len(labels.ravel()))})
                except Exception as exc:
                    self._json(400, {"error": "%s: %s"
                                     % (type(exc).__name__, exc)})

        self.httpd = ThreadingHTTPServer((host, int(port)), Handler)

    # ---------------------------------------------------------- back-compat
    @property
    def session(self):
        """Default entry's PredictSession (single-model callers)."""
        return self.registry.get().session

    @property
    def batcher(self):
        """Default entry's MicroBatcher (single-model callers)."""
        return self.registry.get().batcher

    @property
    def online(self):
        """Default entry's OnlineTrainer (None when online is off)."""
        return self.registry.get().online

    # --------------------------------------------------------------- status
    @property
    def address(self):
        """(host, port) actually bound — resolves port=0 ephemeral binds."""
        return self.httpd.server_address[:2]

    def draining(self) -> bool:
        with self._lock:
            return self._draining

    def healthz(self) -> dict:
        """The /healthz document: substance, not a static OK — model
        versions, registry size, queue depth, per-tenant queue/shed
        counts, uptime, online-trainer state per model (including
        last-promotion/rollback timestamps) and — in fleet replica mode
        — the watcher's applied version."""
        models = self.registry.info()
        # fleet ops view: per-tenant depth/sheds merged across models,
        # and each model's promotion/rollback timestamps hoisted out of
        # the nested online state
        tenants: dict = {}
        for m in models.values():
            for t, st in (m.get("tenants") or {}).items():
                agg = tenants.setdefault(
                    t, {"queue_rows": 0, "shed": 0, "shed_rows": 0})
                agg["queue_rows"] += st.get("queue_rows", 0)
                agg["shed"] += st.get("shed", 0)
                agg["shed_rows"] += st.get("shed_rows", 0)
        promotions = {
            mid: {"last_promotion_ts": m["online"]["last_promotion_ts"],
                  "last_rollback_ts": m["online"]["last_rollback_ts"]}
            for mid, m in models.items()
            if m.get("online") and "last_promotion_ts" in m["online"]}
        doc = {
            "status": "draining" if self.draining() else "ok",
            "uptime_s": round(obs.monotonic() - self._started_at, 3),
            "model_count": len(self.registry),
            "models": models,
            "queue_rows": sum(m["queue_rows"] for m in models.values()),
            "tenants": tenants,
            "requests": telemetry.counter("serve/requests"),
        }
        if promotions:
            doc["promotions"] = promotions
        if self.fleet_watcher is not None:
            doc["fleet"] = self.fleet_watcher.state()
        if self.fleet_store is not None:
            # lease holder/epoch/expiry, log size, last compaction
            doc["fleet_store"] = self.fleet_store.state()
        if self.fleet_transport is not None:
            # remote replica: request/retry/checksum-failure counts
            doc["fleet_transport"] = self.fleet_transport.state()
        if self.ingest_forwarder is not None:
            # control plane: relayed-chunk counts + cached leader
            doc["ingest_forwarder"] = self.ingest_forwarder.state()
        try:
            from .. import obs_device
            # compact device-cost view: HBM watermark + capture totals
            # (full per-jit detail stays on /telemetry and /metrics)
            doc["device_cost"] = obs_device.summary()
        except Exception:  # pragma: no cover - health must never fail
            pass
        try:
            default = self.registry.get()
            # single-model back-compat: the old flat fields stay
            doc["model_version"] = default.booster.inner.model_version
            doc["buckets"] = list(default.session.buckets)
        except KeyError:
            pass
        return doc

    def _leader_hint(self) -> Optional[str]:
        """The current lease holder's advertised serving URL (from the
        attached local store's lease record), or None — stamped into
        409 bodies so a rejected writer learns where to go."""
        store = self.fleet_store
        if store is None:
            return None
        try:
            lease = store.lease_state()
        except Exception:
            return None
        if lease.get("held") and lease.get("url"):
            return str(lease["url"])
        return None

    def fleet_status(self) -> dict:
        """The ``GET /fleet/status`` rollup: one document describing the
        whole fleet from the trainer's vantage — store head version +
        lease + log size, and every node's latest heartbeat (local
        replicas and standbys write them straight to the store; remote
        replicas POST them to ``/fleet/heartbeat``), each stamped with
        server-side version skew and heartbeat age."""
        store = self.fleet_store
        if store is None:
            return {"nodes": []}
        st = store.state()
        head = int(st["last_published_version"])
        now = time.time()  # graftlint: disable=naked-timer -- epoch timestamp, not a duration
        nodes = []
        for hb in store.heartbeats():
            node = dict(hb)
            node["skew"] = max(0, head - int(node.get("version", 0) or 0))
            node["age_s"] = round(max(0.0, now - float(node.get("ts", now))),
                                  3)
            nodes.append(node)
        return {
            "model_id": st["model_id"],
            "head_version": head,
            "lease": st["lease"],
            "log_bytes": st["events_log_bytes"],
            "compactions": st["compactions"],
            "nodes": nodes,
        }

    # ------------------------------------------------------------ lifecycle
    def serve_forever(self) -> None:
        self.httpd.serve_forever()

    def shutdown(self) -> None:
        """Unblock serve_forever() (callable from any thread)."""
        self.httpd.shutdown()

    def begin_shutdown(self, drain_timeout_s: float = 30.0) -> None:
        """Graceful drain (the SIGTERM path): flip /predict//ingest to
        503, keep the accept loop alive until the batcher queues are
        empty (new requests are answered 503 during the drain window,
        queued ones finish normally), then stop the accept loop. Call
        :meth:`close` afterwards to join the workers. Safe from any
        thread EXCEPT the one inside serve_forever (httpd.shutdown would
        deadlock there — the CLI's signal handler hops to a helper
        thread for exactly that reason)."""
        with self._lock:
            already = self._draining
            self._draining = True
        if already:
            return
        telemetry.count("serve/drain_begin")
        Log.info("serve: draining (refusing new requests)")
        deadline = obs.monotonic() + drain_timeout_s
        while obs.monotonic() < deadline:
            if all(e.batcher.queue_rows() == 0
                   for e in self.registry.entries()):
                break
            time.sleep(0.01)
        self.httpd.shutdown()

    def close(self) -> None:
        try:
            self.httpd.server_close()
        finally:
            if self.fleet_watcher is not None:
                self.fleet_watcher.close()
            self.registry.close()
