"""Command-line application: train / predict / convert_model / refit /
save_binary.

Equivalent of the reference CLI (reference: src/main.cpp:11,
src/application/application.h:29 Application, application.cpp:52
LoadParameters). Usage mirrors the reference:

    python -m lightgbm_tpu config=train.conf [key=value ...]
"""
from __future__ import annotations

import os
import sys
from typing import Any, Dict, List, Optional

import numpy as np

from .basic import Booster, Dataset
from .config import Config, resolve_aliases
from .engine import train as _train
from .io import load_config_file, load_text_file
from .utils.log import Log, verbosity_to_level


def parse_args(argv: List[str]) -> Dict[str, Any]:
    """``config=file`` + ``key=value`` overrides
    (reference: application.cpp:52-85 — config file first, CLI wins).
    Two flag-style extras on top of the reference grammar:
    ``--dump-telemetry PATH`` (or ``--dump-telemetry=PATH``) maps to the
    ``dump_telemetry`` parameter, ``--dump-trace PATH`` to ``dump_trace``
    (Chrome trace-event JSON from the span flight recorder)."""
    flags = {"--dump-telemetry": "dump_telemetry",
             "--dump-trace": "dump_trace"}
    cli: Dict[str, str] = {}
    argv = list(argv)
    i = 0
    while i < len(argv):
        a = argv[i]
        if a in flags and i + 1 < len(argv):
            cli[flags[a]] = argv[i + 1].strip()
            i += 2
            continue
        if "=" in a and a.split("=", 1)[0] in flags:
            cli[flags[a.split("=", 1)[0]]] = a.split("=", 1)[1].strip()
            i += 1
            continue
        if "=" not in a:
            Log.warning("Unknown argument: %s", a)
            i += 1
            continue
        k, v = a.split("=", 1)
        cli[k.strip()] = v.strip()
        i += 1
    params: Dict[str, Any] = {}
    if "config" in cli or "config_file" in cli:
        params.update(load_config_file(cli.get("config") or cli["config_file"]))
    params.update(cli)
    params.pop("config", None)
    params.pop("config_file", None)
    return params


class Application:
    """(reference: application.h:29)"""

    def __init__(self, params: Dict[str, Any]) -> None:
        self.raw_params = resolve_aliases(params)
        self.config = Config.from_params(params)
        Log.reset_log_level(verbosity_to_level(self.config.verbosity))
        # the CLI process owns the span tracer: apply the (validated)
        # trace_spans mode up front so every task records consistently
        from .obs_trace import tracer
        tracer.configure(self.config.trace_spans,
                         self.config.trace_buffer_events)
        # same contract for the device-cost capture flag
        from . import obs_device
        obs_device.configure(cost_enabled=self.config.obs_device_cost)

    def run(self) -> None:
        task = self.config.task
        if task == "train":
            self.train()
        elif task in ("predict", "prediction", "test"):
            self.predict()
        elif task == "convert_model":
            self.convert_model()
        elif task == "refit":
            self.refit()
        elif task == "save_binary":
            self.save_binary()
        elif task == "serve":
            self.serve()
        else:
            Log.fatal("Unknown task: %s", task)

    def _load_train_data(self) -> Dataset:
        cfg = self.config
        if cfg.two_round:
            from .io import load_dataset_two_round
            binned = load_dataset_two_round(cfg.data, cfg)
            if binned is not None:
                ds = Dataset(None, params=dict(self.raw_params))
                ds._constructed = binned
                return ds
        X, label, weight, group, names = load_text_file(cfg.data, cfg)
        return Dataset(X, label=label, weight=weight, group=group,
                       feature_name=names or "auto",
                       params=dict(self.raw_params))

    def train(self) -> None:
        cfg = self.config
        train_set = self._load_train_data()
        valid_sets, valid_names = [], []
        for i, vf in enumerate(cfg.valid):
            Xv, lv, wv, gv, _ = load_text_file(vf, cfg)
            valid_sets.append(train_set.create_valid(Xv, label=lv, weight=wv,
                                                     group=gv))
            valid_names.append("valid_%d" % (i + 1) if len(cfg.valid) > 1
                               else "valid_1")
        params = dict(self.raw_params)
        params.setdefault("is_provide_training_metric",
                          cfg.is_provide_training_metric)
        if cfg.is_provide_training_metric:
            valid_sets.insert(0, train_set)
            valid_names.insert(0, "training")
        init_model = cfg.input_model or None
        bst = _train(params, train_set, num_boost_round=cfg.num_iterations,
                     valid_sets=valid_sets, valid_names=valid_names,
                     init_model=init_model)
        bst.save_model(cfg.output_model)
        Log.info("Finished training; model saved to %s", cfg.output_model)

    def predict(self) -> None:
        cfg = self.config
        if not cfg.input_model:
            Log.fatal("task=predict requires input_model")
        bst = Booster(model_file=cfg.input_model)
        X, _, _, _, _ = load_text_file(cfg.data, cfg)
        pred = bst.predict(
            X, raw_score=cfg.predict_raw_score,
            start_iteration=cfg.start_iteration_predict,
            num_iteration=(cfg.num_iteration_predict
                           if cfg.num_iteration_predict > 0 else None),
            pred_leaf=cfg.predict_leaf_index, pred_contrib=cfg.predict_contrib)
        pred2d = pred if pred.ndim > 1 else pred.reshape(-1, 1)
        with open(cfg.output_result, "w") as f:
            for row in pred2d:
                f.write("\t".join("%g" % v for v in row) + "\n")
        Log.info("Finished prediction; results saved to %s", cfg.output_result)

    def convert_model(self) -> None:
        """reference: task=convert_model (gbdt_model_text.cpp ModelToIfElse
        for convert_model_language=cpp; JSON dump otherwise)."""
        cfg = self.config
        if not cfg.input_model:
            Log.fatal("task=convert_model requires input_model")
        bst = Booster(model_file=cfg.input_model)
        out = cfg.convert_model or "gbdt_prediction.cpp"
        if cfg.convert_model_language == "cpp":
            with open(out, "w") as f:
                f.write(bst.inner.to_if_else_cpp())
            Log.info("Model converted to C++ source at %s", out)
        else:
            with open(out, "w") as f:
                f.write(bst.inner.dump_json())
            Log.info("Model dumped to %s", out)

    def refit(self) -> None:
        cfg = self.config
        if not cfg.input_model:
            Log.fatal("task=refit requires input_model")
        bst = Booster(model_file=cfg.input_model)
        X, label, _, _, _ = load_text_file(cfg.data, cfg)
        new_bst = bst.refit(X, label, decay_rate=cfg.refit_decay_rate)
        new_bst.save_model(cfg.output_model)
        Log.info("Refit model saved to %s", cfg.output_model)

    def save_binary(self) -> None:
        cfg = self.config
        ds = self._load_train_data()
        ds.save_binary(cfg.data + ".bin")
        Log.info("Saved binary dataset to %s.bin", cfg.data)

    def serve(self) -> None:
        """task=serve: stdlib-HTTP JSON prediction endpoint over loaded
        model(s) (POST /predict[/<id>], /ingest[/<id>]; GET /healthz,
        /models, /telemetry, /metrics). Device-resident pack +
        bucket-ladder compiled predict + request micro-batching with
        admission control — see lightgbm_tpu/serve/. ``serve_models=
        id=path,...`` hosts extra models next to input_model
        ("default"); ``online_train=true`` attaches an OnlineTrainer per
        model (POST /ingest feeds it) — see lightgbm_tpu/online/.
        SIGTERM drains gracefully: new requests get 503, queued work
        finishes, telemetry/trace dumps fire, exit 0.

        Fleet mode (``fleet_dir=...``): ``fleet_role=trainer`` persists
        ingest/gate/publish events in the durable store, replays them on
        boot, and publishes every promotion/rollback as a version-tokened
        artifact; ``fleet_role=replica`` serves without training, watching
        the store and hot-swapping each published version through the
        adopt path — see lightgbm_tpu/fleet/.

        Fleet hardening: ``fleet_lease_ttl_s>0`` makes the trainer
        lease-gated (boots in standby, trains only while holding the
        store lease, epoch-fenced publishes — run two trainer processes
        on one store and the survivor takes over);
        ``fleet_compact_bytes``/``fleet_keep_artifacts`` bound the store;
        ``fleet_url=http://trainer:port`` points a replica at a remote
        trainer's /fleet endpoints instead of a shared filesystem."""
        cfg = self.config
        entries = []
        if cfg.input_model:
            entries.append(("default", cfg.input_model))
        for spec in cfg.serve_models:
            mid, path = spec.split("=", 1)
            entries.append((mid.strip(), path.strip()))
        if not entries and not cfg.fleet_dir and not cfg.fleet_url \
                and not cfg.fleet_urls:
            Log.fatal("task=serve requires input_model or serve_models")
        fleet_on = bool(cfg.fleet_dir) or bool(cfg.fleet_url) \
            or bool(cfg.fleet_urls)
        fleet_trainer = fleet_on and cfg.fleet_role == "trainer"
        fleet_replica = fleet_on and cfg.fleet_role == "replica"
        import socket
        holder = "%s:%d" % (socket.gethostname(), os.getpid())
        if fleet_on:
            # stamp this process's fleet identity into the span tracer so
            # merged multi-process Perfetto loads keep nodes apart
            from .obs_trace import tracer
            tracer.set_identity(role=cfg.fleet_role, holder=holder)
        if fleet_trainer and not cfg.online_train:
            Log.fatal("fleet_role=trainer requires online_train=true (the "
                      "trainer is the process that publishes promotions)")
        if fleet_replica and cfg.online_train:
            Log.fatal("fleet_role=replica is serve-only (replicas apply "
                      "published models, they never train); drop "
                      "online_train or use fleet_role=trainer")
        if fleet_on and len(entries) > 1:
            Log.fatal("fleet mode serves one model per store; drop "
                      "serve_models or run one process per model")
        if fleet_replica and not entries:
            entries = [("default", "")]   # bootstrap purely from the store
        online_cfg = None
        if cfg.online_train:
            online_cfg = dict(
                mode=cfg.online_mode,
                trigger_rows=cfg.online_trigger_rows,
                trigger_interval_s=cfg.online_trigger_interval_s,
                buffer_rows=cfg.online_buffer_rows,
                shadow_rows=cfg.online_shadow_rows,
                promote_threshold=cfg.online_promote_threshold,
                min_rows=cfg.online_min_rows,
                continue_rounds=cfg.online_continue_rounds,
                decay_rate=cfg.refit_decay_rate,
                shadow_decay=cfg.online_shadow_decay,
                promote_patience=cfg.online_promote_patience,
                rollback_threshold=cfg.online_rollback_threshold,
                rollback_min_rows=cfg.online_rollback_min_rows)
        tenant_weights = {}
        for spec in cfg.serve_tenant_weights:
            name, _, w = spec.partition("=")
            tenant_weights[name.strip()] = float(w)
        from .online import ModelRegistry
        from .serve.http import PredictServer
        registry = ModelRegistry()
        watcher = None
        for mid, path in entries:
            booster, applied = None, 0
            store = None
            if cfg.fleet_dir:
                from .fleet import FleetStore, bootstrap_model
                # a replica over a shared filesystem is a pure reader:
                # it must not run the open-time torn-tail repair or
                # orphan reaping against a live trainer's files
                store = FleetStore(cfg.fleet_dir, mid,
                                   read_only=fleet_replica)
                booster, applied = bootstrap_model(store)
            elif cfg.fleet_url or cfg.fleet_urls:
                from .fleet import (MultiEndpointStore, RemoteStore,
                                    RemoteWriteStore, bootstrap_model)
                if fleet_trainer:
                    # remote trainer: the full write surface (lease,
                    # fenced publish, ingest/gate appends, compaction)
                    # over HTTP against the store host — no shared
                    # filesystem anywhere in the path
                    store = RemoteWriteStore(
                        cfg.fleet_urls[0],
                        timeout_s=cfg.fleet_timeout_s,
                        backoff_max_s=cfg.fleet_backoff_max_s)
                elif len(cfg.fleet_urls) > 1:
                    # multi-endpoint replica: liveness-ranked failover
                    store = MultiEndpointStore(
                        cfg.fleet_urls,
                        timeout_s=cfg.fleet_timeout_s,
                        backoff_max_s=cfg.fleet_backoff_max_s)
                    store.probe()
                else:
                    store = RemoteStore(
                        cfg.fleet_url or cfg.fleet_urls[0],
                        timeout_s=cfg.fleet_timeout_s,
                        backoff_max_s=cfg.fleet_backoff_max_s)
                try:
                    booster, applied = bootstrap_model(store)
                except Exception as exc:
                    # the remote trainer may simply not be up yet; the
                    # watcher keeps retrying with backoff
                    Log.warning("fleet: remote bootstrap failed (%s: "
                                "%s); watching %s for the first publish",
                                type(exc).__name__, exc,
                                cfg.fleet_url
                                or ",".join(cfg.fleet_urls))
            if booster is not None:
                Log.info("fleet: %s booted from published v%d",
                         mid, applied)
            if booster is None:
                if not path:
                    Log.fatal("fleet: store %s has no published model yet "
                              "and no input_model to seed from",
                              cfg.fleet_dir or cfg.fleet_url)
                booster = Booster(model_file=path)
                if fleet_trainer and store.latest_publish() is None:
                    # seed the store so replicas can boot before the
                    # first promotion
                    store.publish(booster.model_to_string(), event="boot")
            model_online = None
            if online_cfg is not None:
                model_online = dict(online_cfg)
                if fleet_trainer:
                    model_online.update(
                        store=store, replay=cfg.fleet_replay,
                        lease_ttl_s=cfg.fleet_lease_ttl_s,
                        holder_id=holder,
                        compact_bytes=cfg.fleet_compact_bytes,
                        keep_artifacts=cfg.fleet_keep_artifacts,
                        snapshot_rows=cfg.fleet_snapshot_rows,
                        heartbeat_interval_s=cfg.fleet_heartbeat_interval_s)
            entry = registry.register(
                mid, booster,
                buckets=cfg.serve_buckets or None,
                max_batch_rows=cfg.serve_max_batch_rows,
                max_wait_ms=cfg.serve_max_wait_ms,
                max_queue_rows=cfg.serve_max_queue_rows,
                overload=cfg.serve_overload,
                tenant_quota_rows=cfg.serve_tenant_quota_rows,
                tenant_weights=tenant_weights or None,
                raw_score=cfg.predict_raw_score,
                warmup=cfg.serve_warmup,
                dispatch_mode=cfg.serve_dispatch,
                online=model_online)
            if fleet_replica:
                from .fleet import ReplicaWatcher
                watcher = ReplicaWatcher(
                    entry.booster, store,
                    poll_interval_s=cfg.fleet_poll_interval_s,
                    applied_version=applied,
                    backoff_max_s=cfg.fleet_backoff_max_s,
                    heartbeat_interval_s=cfg.fleet_heartbeat_interval_s,
                    node_id=holder)
        server = PredictServer(registry=registry, host=cfg.serve_host,
                               port=cfg.serve_port)
        server.fleet_watcher = watcher
        if cfg.fleet_dir and store is not None:
            # local store: serve the /fleet transport routes (remote
            # replicas converge through them) + /healthz lease/log state
            server.fleet_store = store
        elif (cfg.fleet_url or cfg.fleet_urls) and store is not None:
            # remote store: surface transport retry/backoff on /healthz
            server.fleet_transport = store
        host, port = server.address
        if fleet_trainer:
            # advertise this trainer's serving endpoint in the lease
            # record (acquire/renew both write it): the leader_hint
            # ingest forwarding resolves. The bound port is only known
            # HERE, after the trainer exists — the next lease touch
            # carries it (set mutable advertise_url, per-call url= for
            # stores created before the bind)
            adv_host = host if host not in ("0.0.0.0", "::") \
                else __import__("socket").gethostname()
            advertise = "http://%s:%d" % (adv_host, port)
            try:
                ent = registry.get()
                if ent.online is not None:
                    ent.online.advertise_url = advertise
            except KeyError:
                pass
        if cfg.fleet_forward_ingest and store is not None:
            # relay labeled traffic hitting this node to the lease
            # holder instead of 409ing it (replicas and standbys have
            # no online trainer to buffer it)
            from .fleet import IngestForwarder
            server.ingest_forwarder = IngestForwarder(
                store=store if cfg.fleet_dir else None,
                urls=(cfg.fleet_urls or
                      ([cfg.fleet_url] if cfg.fleet_url else ())),
                timeout_s=cfg.fleet_timeout_s)
        Log.info("Serving %s on http://%s:%d (POST /predict, /ingest; GET "
                 "/healthz, /models, /telemetry, /metrics)%s",
                 ", ".join("%s=%s" % e for e in entries), host, port,
                 " [fleet %s @ %s]" % (cfg.fleet_role,
                                       cfg.fleet_dir or cfg.fleet_url
                                       or ",".join(cfg.fleet_urls))
                 if fleet_on else "")
        stop_dump = None
        if cfg.dump_telemetry and cfg.telemetry_dump_interval_s > 0:
            # a wedged server still leaves fresh counters on disk
            from .obs_trace import start_periodic_telemetry_dump
            stop_dump = start_periodic_telemetry_dump(
                cfg.dump_telemetry, cfg.telemetry_dump_interval_s)
        stop_hbm = None
        if cfg.obs_hbm_sample_interval_s > 0:
            # live-HBM watermark under load (hbm/* gauges on /metrics;
            # counted no-op on backends without memory stats)
            from . import obs_device
            stop_hbm = obs_device.start_hbm_sampler(
                cfg.obs_hbm_sample_interval_s)
        import signal
        import threading

        def _on_sigterm(signum, frame):
            # begin_shutdown calls httpd.shutdown(), which would deadlock
            # on the thread stuck inside serve_forever (this one) — hop
            # to a helper thread and let serve_forever return
            threading.Thread(target=server.begin_shutdown,
                             name="lgbtpu-serve-drain",
                             daemon=True).start()

        try:
            old_term = signal.signal(signal.SIGTERM, _on_sigterm)
        except ValueError:        # not the main thread (embedded use)
            old_term = None
        try:
            server.serve_forever()
        except KeyboardInterrupt:
            # return normally so main() still honors --dump-telemetry —
            # serving counters must survive the process
            Log.info("serve: interrupted, shutting down")
        finally:
            if stop_dump is not None:
                stop_dump.set()
            if stop_hbm is not None:
                stop_hbm.set()
            # drains the batchers: requests admitted before the drain
            # flag flipped still get their answers
            server.close()
            if old_term is not None:
                signal.signal(signal.SIGTERM, old_term)
            if cfg.obs_ledger:
                # one serve entry per process lifetime: the serving
                # latency histograms + device-cost section at drain time
                from . import obs_ledger
                extra = None
                if fleet_on:
                    # record what this process actually WAS (a standby
                    # that never won the lease ledgers as standby, not
                    # trainer) so `ledger list` tells fleet runs apart
                    role, epoch = cfg.fleet_role, 0
                    try:
                        ent = registry.get()
                        if ent.online is not None:
                            st = ent.online.state()
                            role = st.get("role", role)
                            epoch = int(st.get("lease_epoch", 0))
                    except Exception:
                        pass
                    extra = {"fleet": {"role": role, "holder": holder,
                                       "lease_epoch": epoch}}
                obs_ledger.record_run(cfg, "serve", 0, 0, extra=extra)
        Log.info("serve: drained and closed")


def main(argv: Optional[List[str]] = None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    if not argv:
        print(__doc__)
        return
    app = Application(parse_args(argv))
    cfg = app.config
    if cfg.dump_telemetry or cfg.dump_trace:
        # SIGUSR1 -> telemetry snapshot, SIGUSR2 -> trace dump, live —
        # a hung run can be inspected without killing it
        from .obs_trace import install_signal_handlers
        try:
            install_signal_handlers(
                telemetry_path=cfg.dump_telemetry or None,
                trace_path=cfg.dump_trace or None)
        except ValueError:    # not the main thread (embedded use)
            pass
    app.run()
    if cfg.dump_telemetry:
        import json
        from .obs import telemetry
        with open(cfg.dump_telemetry, "w") as f:
            json.dump(telemetry.snapshot(), f, indent=2)
        Log.info("Dumped telemetry to %s", cfg.dump_telemetry)
    if cfg.dump_trace:
        from .obs_trace import tracer
        n = tracer.dump(cfg.dump_trace)
        Log.info("Dumped %d trace events to %s", n, cfg.dump_trace)


if __name__ == "__main__":
    main()
