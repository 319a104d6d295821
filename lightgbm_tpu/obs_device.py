"""Device-cost observability: compiled-executable accounting, live HBM
sampling, and the training health watchdog.

PR 3/7 observability is host-blind to the device: counters and spans say
*when* phases run, not what they *cost* the accelerator. This module adds
the device side, with zero runtime device ops on the measurement paths:

1. **Compile-time cost capture** — every :func:`obs.track_jit` entry point
   reports cache growth here (:func:`on_compile`); the capture re-lowers
   the just-compiled signature through the AOT API and records
   ``Compiled.cost_analysis()`` (FLOPs, bytes accessed) and
   ``Compiled.memory_analysis()`` (argument/output/temp/generated-code
   bytes — the executable's HBM footprint). Lowering after a call hits
   jax's jaxpr cache (sub-ms); the AOT backend compile is the cost, paid
   once per (entry point, signature), and its duration is recorded
   honestly under ``device_cost/capture_s``. The AOT compile's own
   backend event is suppressed so ``jit/backend_compiles`` keeps counting
   only the program's compiles (the compile-budget tests pin that).
2. **Live HBM sampling** — :func:`sample_hbm` reads
   ``device.memory_stats()`` (bytes in use / limit / allocator peak) into
   gauges and keeps a process-wide peak watermark. CPU backends return no
   stats; the sampler degrades to a counted no-op. ``serve`` can run it
   periodically (:func:`start_hbm_sampler`).
3. **Training health watchdog** — :func:`check_finite`
   (``obs_check_finite=off|warn|raise``): one fused device-side
   ``isfinite`` reduction over the grads/scores of a block, fetched as a
   single scalar. ``off`` (the default) never builds a single jnp op —
   the mode check happens in the callers before any array is touched.

Everything lands in the process-global :data:`obs.telemetry` registry, so
it surfaces through ``Booster.telemetry()`` (the ``device_cost`` section
:func:`section` contributes to every snapshot), ``GET /metrics``
Prometheus families (``lgbtpu_device_cost_*``, ``lgbtpu_hbm_*``,
``lgbtpu_obs_nonfinite_*``) and the bench JSON.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, Iterable, Optional

from .obs import suppress_backend_compiles, telemetry, track_jit
from .utils.log import LightGBMError, Log

#: memory_analysis attributes recorded per captured executable
_MEM_FIELDS = (
    ("argument_size_in_bytes", "argument_bytes"),
    ("output_size_in_bytes", "output_bytes"),
    ("temp_size_in_bytes", "temp_bytes"),
    ("alias_size_in_bytes", "alias_bytes"),
    ("generated_code_size_in_bytes", "generated_code_bytes"),
)


class _State:
    """Process-global device-cost aggregates (mirrors the Telemetry
    pattern: one lock, plain dicts, host-only mutation)."""

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.cost_enabled = True     # flipped by configure(obs_device_cost)
        # per tracked-jit name: running sums (flops/bytes accumulate over
        # signatures; HBM fields keep the max — footprints don't add, the
        # executables are not resident simultaneously)
        self.jits: Dict[str, Dict[str, float]] = {}
        self.hbm_peak = 0
        self.hbm_samples = 0
        self.hbm_supported: Optional[bool] = None   # unknown until sampled
        self.hbm_last: Dict[str, int] = {}


_state = _State()  # graftlint: disable=module-mutable-state -- process-global registry, guarded by _state.lock


def configure(cost_enabled: Optional[bool] = None) -> None:
    """Apply config knobs (process-global, last writer wins — same
    contract as obs_trace.tracer.configure)."""
    if cost_enabled is not None:
        with _state.lock:
            _state.cost_enabled = bool(cost_enabled)


def cost_capture_enabled() -> bool:
    with _state.lock:
        return _state.cost_enabled


def reset() -> None:
    """Clear the aggregates (tests, fresh benches). Does not touch the
    enabled flag — reset() between two trains must not change behavior."""
    with _state.lock:
        _state.jits.clear()
        _state.hbm_peak = 0
        _state.hbm_samples = 0
        _state.hbm_supported = None
        _state.hbm_last.clear()


def on_compile(name: str, fn, args, kwargs) -> None:
    """Record the device cost of a freshly compiled tracked-jit signature.

    Called by obs._TrackedJit right after it observed cache growth; the
    call's concrete ``args``/``kwargs`` pin the signature, so
    ``fn.lower(*args).compile()`` reproduces the executable that was just
    built. Donated-buffer entry points (the inputs are already consumed)
    and backends without analysis support degrade to a counted error —
    capture must never break training.
    """
    if not cost_capture_enabled():
        return
    t0 = time.perf_counter()   # graftlint: disable=naked-timer -- times a HOST compile, no device work to sync
    try:
        with suppress_backend_compiles():
            compiled = fn.lower(*args, **kwargs).compile()
        cost = compiled.cost_analysis() or {}
        entry = {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed",
                                             cost.get("bytes_accessed", 0.0))),
        }
        try:
            mem = compiled.memory_analysis()
        except Exception:
            mem = None
        for attr, key in _MEM_FIELDS:
            entry[key] = float(getattr(mem, attr, 0) or 0) if mem is not None \
                else 0.0
    except Exception as exc:
        telemetry.count("device_cost/capture_errors")
        Log.debug("device-cost capture failed for %s: %s: %s",
                  name, type(exc).__name__, exc)
        return
    finally:
        telemetry.add_time("device_cost/capture_s",
                           time.perf_counter() - t0)   # graftlint: disable=naked-timer -- host-compile duration
    with _state.lock:
        agg = _state.jits.setdefault(name, {
            "compiles": 0, "flops": 0.0, "bytes_accessed": 0.0,
            "argument_bytes": 0.0, "output_bytes": 0.0, "temp_bytes": 0.0,
            "alias_bytes": 0.0, "generated_code_bytes": 0.0})
        agg["compiles"] += 1
        agg["flops"] += entry["flops"]
        agg["bytes_accessed"] += entry["bytes_accessed"]
        for _, key in _MEM_FIELDS:
            agg[key] = max(agg[key], entry[key])
    telemetry.count("device_cost/captures")
    # Prometheus families: per-jit FLOPs/bytes as counters (accumulate
    # over signatures), HBM footprint as gauges (max over signatures)
    telemetry.count("device_cost/flops/" + name, int(entry["flops"]))
    telemetry.count("device_cost/bytes_accessed/" + name,
                    int(entry["bytes_accessed"]))
    telemetry.gauge("device_cost/temp_hbm_bytes/" + name,
                    int(entry["temp_bytes"]))
    telemetry.gauge("device_cost/argument_hbm_bytes/" + name,
                    int(entry["argument_bytes"]))
    telemetry.gauge("device_cost/output_hbm_bytes/" + name,
                    int(entry["output_bytes"]))
    telemetry.gauge("device_cost/generated_code_bytes/" + name,
                    int(entry["generated_code_bytes"]))
    telemetry.record("device_cost_capture", jit=name, **entry)


# ---------------------------------------------------------------------------
# Live HBM sampling
# ---------------------------------------------------------------------------

def sample_hbm() -> Optional[Dict[str, int]]:
    """One ``device.memory_stats()`` sample into gauges + the peak
    watermark. Returns the sample dict, or None on backends without
    memory stats (CPU jax returns None — graceful, counted no-op).
    Host-only: reads allocator state, never touches device queues."""
    stats = None
    try:
        import jax
        devs = jax.local_devices()
        if devs:
            stats = devs[0].memory_stats()
    except Exception:
        stats = None
    if not stats:
        with _state.lock:
            _state.hbm_supported = False
        telemetry.count("obs_device/hbm_sample_noop")
        return None
    in_use = int(stats.get("bytes_in_use", 0))
    limit = int(stats.get("bytes_limit",
                          stats.get("bytes_reservable_limit", 0)))
    alloc_peak = int(stats.get("peak_bytes_in_use", in_use))
    with _state.lock:
        _state.hbm_supported = True
        _state.hbm_samples += 1
        _state.hbm_peak = max(_state.hbm_peak, alloc_peak, in_use)
        peak = _state.hbm_peak
        _state.hbm_last = {"bytes_in_use": in_use, "bytes_limit": limit}
    telemetry.count("obs_device/hbm_samples")
    telemetry.gauge("hbm/bytes_in_use", in_use)
    telemetry.gauge("hbm/peak_bytes", peak)
    if limit:
        telemetry.gauge("hbm/bytes_limit", limit)
    return {"bytes_in_use": in_use, "peak_bytes": peak,
            "bytes_limit": limit}


def maybe_sample_hbm() -> Optional[Dict[str, int]]:
    """Boundary sampler for hot paths (fused block finalize): one stats
    read per call, but once a backend has answered "no memory stats"
    every further call is a single lock-check — the per-block noop
    counter must not grow unbounded on CPU."""
    with _state.lock:
        if _state.hbm_supported is False:
            return None
    return sample_hbm()


def start_hbm_sampler(interval_s: float) -> threading.Event:
    """Sample HBM every ``interval_s`` seconds from a named daemon thread
    until the returned Event is set (``task=serve`` wires this to
    ``obs_hbm_sample_interval_s``). A no-stats backend keeps the thread
    cheap: one failed stats read per tick."""
    stop = threading.Event()

    def _loop():
        while not stop.wait(interval_s):
            sample_hbm()

    t = threading.Thread(target=_loop, name="lgbtpu-hbm-sampler",
                         daemon=True)
    t.start()
    return stop


# ---------------------------------------------------------------------------
# Snapshot section
# ---------------------------------------------------------------------------

def section() -> Dict[str, Any]:
    """The ``device_cost`` section of :meth:`obs.Telemetry.snapshot`:
    per-jit FLOPs/bytes/HBM aggregates plus the HBM watermark. Always
    present (empty ``jits`` when capture is off or nothing compiled) so
    snapshot consumers need no feature detection."""
    with _state.lock:
        jits = {k: dict(v) for k, v in _state.jits.items()}
        hbm: Dict[str, Any] = {
            "supported": _state.hbm_supported,
            "samples": _state.hbm_samples,
            "peak_bytes": _state.hbm_peak,
        }
        hbm.update(_state.hbm_last)
        enabled = _state.cost_enabled
    return {"enabled": enabled, "jits": jits, "hbm": hbm}


def summary() -> Dict[str, Any]:
    """Compact view for ``/healthz``: watermark + totals, no per-jit
    detail (that lives on ``/telemetry`` and ``/metrics``)."""
    with _state.lock:
        return {
            "hbm_supported": _state.hbm_supported,
            "hbm_peak_bytes": _state.hbm_peak,
            "hbm_samples": _state.hbm_samples,
            "captured_jits": len(_state.jits),
            "total_flops": sum(j["flops"] for j in _state.jits.values()),
        }


# ---------------------------------------------------------------------------
# Training health watchdog (obs_check_finite)
# ---------------------------------------------------------------------------

_finite_fn = None  # graftlint: disable=module-mutable-state -- lazily built jit, guarded by _finite_lock
_finite_lock = threading.Lock()


def _nonfinite_counter():
    """The fused device-side reduction: one jitted scalar over all float
    leaves. Built lazily so ``obs_check_finite=off`` never imports a
    kernel, tracked so its compiles are visible in the budget telemetry."""
    global _finite_fn
    with _finite_lock:
        if _finite_fn is None:
            import jax
            import jax.numpy as jnp

            @jax.jit
            def nonfinite(arrays):
                total = jnp.zeros((), jnp.int32)
                for a in arrays:
                    if jnp.issubdtype(a.dtype, jnp.floating):
                        total = total + jnp.sum(~jnp.isfinite(a),
                                                dtype=jnp.int32)
                return total

            _finite_fn = track_jit("obs/check_finite", nonfinite)
        return _finite_fn


def check_finite(kind: str, arrays: Iterable, mode: str) -> int:
    """Count non-finite elements across ``arrays`` on device; count them
    into ``obs/nonfinite_<kind>`` and warn/raise per ``mode``.

    The scalar fetch is an intentional host sync — the watchdog trades
    one 4-byte transfer per block for catching a NaN blow-up at the block
    it happened instead of N iterations later. Callers gate on
    ``mode != "off"`` BEFORE building the argument tuple, so off-mode
    adds zero device ops (pinned by tests/test_obs_device.py against the
    compile-budget harness)."""
    if mode == "off":
        return 0
    arrays = tuple(arrays)
    if not arrays:
        return 0
    n = int(_nonfinite_counter()(arrays))
    telemetry.count("obs/finite_checks")
    if n:
        telemetry.count("obs/nonfinite_" + kind, n)
        msg = ("non-finite values in %s: %d elements (objective blow-up "
               "or bad input; see obs/nonfinite_%s)" % (kind, n, kind))
        if mode == "raise":
            raise LightGBMError(msg)
        Log.warning(msg)
    return n
