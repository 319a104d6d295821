"""Telemetry: trusted timers, phase tracing, structured run counters.

Codifies PERF.md's measurement discipline as a library instead of a
per-script convention. The primitives are deliberately conservative; each
rule below was adopted before this round of work on a differently attached
device, and ROADMAP S2 re-tests them on a directly attached v5e
(``chip_smoke.py`` already reports ``block_until_ready`` against
:func:`sync` for one fused block):

- wall clocks around a completed transfer are the ground truth for Pallas
  kernels; profiler custom-call costs were not trusted;
- every trusted wall ends in :func:`sync`, a real 1-element transfer
  (``device_get``), rather than in ``block_until_ready`` alone;
- A/B loops thread a CHANGING carry so no two executions are bit-identical
  (:func:`ab_interleaved` documents and enforces the protocol shape);
- only same-process interleaved comparisons are trusted.

Three layers:

1. **Trusted timing** — :func:`sync`, :func:`wall`, :func:`timed_sync`,
   :func:`ab_interleaved`. The ``scripts/*_bisect.py`` /
   ``scripts/profile_wall.py`` harnesses build on these; the yardstick
   (``benchmark/``) reads the clock itself and takes from here only what
   the program recorded (layer 3).
2. **Phase tracing** — :func:`trace_phase`, the one primitive every named
   region goes through; :data:`PHASES` lists every ``lgbtpu/<phase>`` name
   a site may use. One ``with`` feeds up to four readers:

   - the compiled program's ``op_name`` metadata (``jax.named_scope``),
     when the region is traced into a jit: ``benchmark/trace_reduce.py``
     books each device op to its OUTERMOST ``lgbtpu/<phase>``;
   - the profiler's host timeline (``jax.profiler.TraceAnnotation``), when
     the region runs on the host: on the device trace's clock, so idle
     device time is attributed to the span that held it;
   - a **timer** (``timer=`` name): accumulated seconds and call count in
     :data:`telemetry`, read as window deltas by the benchmark's
     ``timer_delta``;
   - the **flight recorder** (``obs_trace.tracer``) when ``trace_spans=on``.

   Scope and annotation are metadata: they never change computed values.
3. **Structured run counters** — the process-global :data:`telemetry`
   registry (counters / gauges / timers / record lists) instrumenting the
   dataset device caches, the fused pipeline, per-tree growth stats, every
   ``auto`` knob resolution, one ``job_start`` (:class:`JobStart`) and one
   ``dataset_construct`` record per job, and the job's timeline: one
   ``package_import`` and one ``runtime_start`` record a process
   (:func:`record_package_import`, ``runtime.py``) and one ``fused_block``
   record a finalized block (``fused.FusedTrainer``), every stamp a
   ``time.perf_counter()`` second. ``Booster.telemetry()``,
   ``CallbackEnv.telemetry``, ``cli --dump-telemetry`` and the benchmark's
   readers all read :meth:`Telemetry.snapshot` / :meth:`Telemetry.records`.

All counter updates run on HOST, outside traced code, and never add a
device sync: telemetry keeps bit-parity with an uninstrumented run.
"""
from __future__ import annotations

import contextlib
import re
import threading
import time
from bisect import bisect_left
from collections import defaultdict
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple


# ---------------------------------------------------------------------------
# Trusted timing primitives (PERF.md measurement discipline v2)
# ---------------------------------------------------------------------------

def sync(x) -> Optional[Any]:
    """Force a REAL 1-element device->host transfer dependent on ``x``.

    A completed transfer cannot return before its producers ran, whatever
    the runtime does with ``block_until_ready``. The first
    jax.Array leaf of ``x`` (any pytree) is reduced to one element ON
    DEVICE and ``device_get`` pulled — completing it forces every producer
    of that leaf to have run. Returns the fetched 1-element array, or None
    when ``x`` holds no device arrays (host values need no sync).
    """
    import jax
    for leaf in jax.tree.leaves(x):
        if isinstance(leaf, jax.Array):
            return jax.device_get(leaf.ravel()[:1])
    return None


def monotonic() -> float:
    """Monotonic timestamp (``perf_counter``) for spans that cannot be a
    ``with`` block — e.g. the serve MicroBatcher measures submit->delivery
    latency across threads, so the start and end of the span live in
    different frames. Pure host clock read; callers pair two of these and
    feed the difference to :meth:`Telemetry.add_time`."""
    return time.perf_counter()


class WallTimer:
    """Result handle yielded by :func:`wall`; ``seconds`` is set on exit."""

    __slots__ = ("name", "seconds")

    def __init__(self, name: str) -> None:
        self.name = name
        self.seconds = 0.0


@contextlib.contextmanager
def wall(name: str, record: bool = True) -> Iterator[WallTimer]:
    """Monotonic (``perf_counter``) wall timer around a block.

    Callers timing device work must end the block with ``obs.sync(result)``
    — the timer cannot know what to sync on. The elapsed time lands on the
    yielded handle's ``.seconds`` and (when ``record``) in the global
    telemetry registry under ``wall/<name>``.
    """
    w = WallTimer(name)
    t0 = time.perf_counter()
    try:
        yield w
    finally:
        w.seconds = time.perf_counter() - t0
        if record:
            telemetry.add_time("wall/" + name, w.seconds)


def timed_sync(fn: Callable[[], Any]) -> float:
    """Trusted wall of one call of ``fn``: warm (compile) once, then time a
    second call ended by a forced 1-element transfer of its result."""
    import jax
    r = fn()
    jax.block_until_ready(r)       # warm/compiled; the real sync is below
    t0 = time.perf_counter()
    sync(fn())
    return time.perf_counter() - t0


def ab_interleaved(fns: Sequence[Tuple[str, Callable[[int], Callable[[], Any]]]],
                   reps: int = 5, k: int = 4) -> Dict[str, float]:
    """Interleaved A/B per-op timing under discipline v2.

    ``fns`` is ``[(name, make)]`` where ``make(j)`` returns a zero-arg
    thunk running a j-chained computation (e.g. a ``lax.scan`` of length j)
    whose body threads a CHANGING carry, so no link repeats a bit-identical
    execution a runtime could elide. Per-op time = (t_k - t_1) / (k - 1), which cancels the
    dispatch + sync overhead shared by both chain lengths; trials are
    interleaved A, B, A, B per rep (run-to-run drift hits both sides)
    and the best of ``reps`` is kept. Everything is compiled before the
    first timed trial. Returns ``{name: per_op_seconds}``.
    """
    if k < 2:
        raise ValueError("ab_interleaved needs chain length k >= 2")
    pairs = {name: (make(1), make(k)) for name, make in fns}
    for f1, fk in pairs.values():          # compile everything first
        timed_sync(f1), timed_sync(fk)
    best = {name: float("inf") for name, _ in fns}
    for _ in range(reps):
        for name, (f1, fk) in pairs.items():   # A, B, A, B ... per rep
            best[name] = min(best[name],
                             (timed_sync(fk) - timed_sync(f1)) / (k - 1))
    return best


# ---------------------------------------------------------------------------
# Phase tracing
# ---------------------------------------------------------------------------

# Every ``lgbtpu/<phase>`` a trace_phase site may name: (kind, layer, timer).
# ``device`` phases are traced into jitted programs and must be SIBLINGS:
# the benchmark books a device op to the outermost phase of its op_name, so
# one wrapped around another would swallow it. ``host`` phases run on the
# host around dispatches and waits; ``timer`` is the telemetry timer the
# site accumulates into. The layer is PERF.md's (section 3). tests/test_obs.py
# holds every site and every compiled block program to this table.
PHASES: Dict[str, Tuple[str, str, Optional[str]]] = {
    # -- device: one iteration of the block program, in program order
    "lgbtpu/block_setup": ("device", "booster", None),
    "lgbtpu/objective": ("device", "kernels", None),
    "lgbtpu/rank_gather": ("device", "kernels", None),
    "lgbtpu/rank_sort": ("device", "kernels", None),
    "lgbtpu/rank_pairs": ("device", "kernels", None),
    "lgbtpu/rank_scatter": ("device", "kernels", None),
    "lgbtpu/sample": ("device", "booster", None),
    "lgbtpu/pack": ("device", "tree learner", None),
    "lgbtpu/root_hist": ("device", "tree learner", None),
    "lgbtpu/tree_state": ("device", "tree learner", None),
    # bundled histogram -> per-feature view, feature-bin routing table ->
    # bundle-bin one; holds no op where nothing is bundled
    "lgbtpu/efb_view": ("device", "tree learner", None),
    # the view's own slots by an index a (feature, bin): the ``gather`` form
    # of a table whose selection matrix would pass VIEW_SEL_MAX_BYTES; a
    # sibling of efb_view, which keeps the rest of the view
    "lgbtpu/efb_gather": ("device", "tree learner", None),
    "lgbtpu/split_scan": ("device", "tree learner", None),
    # the categorical half of a node search (one-vs-rest gains, sort keys,
    # the bins' order by count or by sort, the sorted histogram, prefix sums,
    # the winner's table), named inside ops/split.py beside split_scan; holds
    # no op without categorical columns
    "lgbtpu/cat_scan": ("device", "tree learner", None),
    "lgbtpu/partition": ("device", "kernels", None),
    "lgbtpu/histogram": ("device", "kernels", None),
    "lgbtpu/route": ("device", "kernels", None),
    "lgbtpu/score_update": ("device", "tree learner", None),
    "lgbtpu/tree_log": ("device", "booster", None),
    # nested labels of single ops (lgbtpu/ops/<kernel>), never outermost
    "lgbtpu/ops": ("device", "kernels", None),
    # -- host: before the loop
    "lgbtpu/runtime_start": ("host", "runtime", "runtime/start"),
    "lgbtpu/construct": ("host", "host data", "construct/total"),
    "lgbtpu/construct_copy": ("host", "host data", "construct/copy"),
    "lgbtpu/construct_find_bins": ("host", "host data", "construct/find_bins"),
    "lgbtpu/construct_bundle": ("host", "host data", "construct/bundle"),
    "lgbtpu/construct_bin_rows": ("host", "host data", "construct/bin_rows"),
    "lgbtpu/train": ("host", "booster", "train/total"),
    "lgbtpu/booster_init": ("host", "booster", "train/booster_init"),
    "lgbtpu/objective_init": ("host", "booster", "train/objective_init"),
    "lgbtpu/learner_init": ("host", "booster", "train/learner_init"),
    # -- host: the loop
    "lgbtpu/train_block": ("host", "booster", "train/block"),
    "lgbtpu/train_iter": ("host", "booster", "train/iter"),
    "lgbtpu/metric_eval": ("host", "booster", "train/metric_eval"),
    "lgbtpu/fused_block_fn": ("host", "booster", "fused/block_fn"),
    "lgbtpu/fused_args": ("host", "booster", "fused/args"),
    "lgbtpu/fused_dispatch": ("host", "booster", "fused/dispatch"),
    "lgbtpu/fused_after_call": ("host", "booster", "fused/after_call"),
    "lgbtpu/fused_device_wait": ("host", "booster", "fused/device_wait"),
    "lgbtpu/fused_flush": ("host", "booster", "fused/logs_transfer"),
    "lgbtpu/fused_host_trees": ("host", "booster", "fused/host_trees"),
    "lgbtpu/fused_commit": ("host", "booster", "fused/commit"),
}


def host_phase(name: str):
    """``trace_phase`` of a host phase of :data:`PHASES`, with its timer."""
    return trace_phase(name, timer=PHASES[name][2])


@contextlib.contextmanager
def trace_phase(name: str, timer: Optional[str] = None) -> Iterator[None]:
    """Name a region for the compiled program, the profiler, a timer and —
    when span tracing is on — the host-side flight recorder.

    Inside a jit trace, ``jax.named_scope`` stamps the phase name onto the
    emitted HLO ops; on host, ``jax.profiler.TraceAnnotation`` marks the
    span on the profiler timeline. Both are metadata-only — no runtime
    effect on the computed values, so phase-traced trees stay bit-identical
    (tests/test_obs.py rides the existing parity shapes).

    ``timer`` (host regions only: inside a jit trace it would time the
    trace) adds the region's wall seconds and one call to that telemetry
    timer.

    With ``trace_spans=on`` (obs_trace.tracer), host-side executions of
    the region additionally record a span into the flight recorder.
    ``phase_begin`` refuses to record inside a jit trace (that would
    measure trace time once per compile, not runtime) and is a single
    attribute read when tracing is off.
    """
    import jax
    from . import obs_trace
    sp = obs_trace.tracer.phase_begin(name)
    try:
        ann = jax.profiler.TraceAnnotation(name)
    except Exception:  # pragma: no cover - profiler backend unavailable
        ann = contextlib.nullcontext()
    t0 = time.perf_counter()
    try:
        with jax.named_scope(name), ann:
            yield
    finally:
        if timer is not None:
            telemetry.add_time(timer, time.perf_counter() - t0)
        if sp is not None:
            obs_trace.tracer.end(sp)


# ---------------------------------------------------------------------------
# Retrace / compile-budget detection
# ---------------------------------------------------------------------------
#
# Every jit entry point of the training path is wrapped in track_jit(), so
# each (re)trace shows up as a named counter in the telemetry registry:
# ``jit/compiles/<name>``. A retrace explosion (the round-5 "dispatch soup"
# failure class) then reads directly off ``Booster.telemetry()`` /
# ``bench.py`` JSON instead of being inferred from wall-clock, and
# tests/test_retrace.py pins a per-train compile budget.

_JIT_COMPILES_PREFIX = "jit/compiles/"
_BACKEND_COMPILES = "jit/backend_compiles"
_compile_listener_installed = False
# Thread-local mute for the backend-compile listener. obs_device's AOT
# cost capture re-compiles a signature the program ALREADY paid for; its
# backend event would double-count in ``jit/backend_compiles`` (which the
# compile-budget tests pin as "the program's own compiles").
_suppress = threading.local()


@contextlib.contextmanager
def suppress_backend_compiles() -> Iterator[None]:
    """Mute ``jit/backend_compiles`` and the ``jit/*_s`` timers for work
    issued by the current thread inside the block (used by
    obs_device.on_compile around its AOT re-lowering and re-compile). The
    duration still lands in ``device_cost/capture_s``,
    so the capture cost stays visible — just not conflated with the
    training path's compile count."""
    prev = getattr(_suppress, "on", False)
    _suppress.on = True
    try:
        yield
    finally:
        _suppress.on = prev
# jax.monitoring listeners cannot be unregistered, so the "already
# installed" marker must outlive THIS module object: a reloaded obs (or a
# second copy imported under a different package path) re-running
# install would otherwise stack a second listener and double every
# backend-compile count. The sentinel lives on jax.monitoring itself.
_LISTENER_SENTINEL = "_lightgbm_tpu_compile_listener"


def _exclusive_trace_s(duration: float) -> float:
    """Seconds of one jaxpr-trace event that no earlier event already holds.

    A jitted callee traced inside its caller's trace fires its own event
    first, and the caller's duration includes it; summing both would count
    the callee twice. Events arrive in order of their ends, so the ones a
    new event encloses are the newest on this thread's list, which holds
    the finished children of the traces still open."""
    from jax.core import trace_ctx
    start = time.perf_counter() - duration
    seen = getattr(_suppress, "traces", None)
    if seen is None:
        seen = _suppress.traces = []
    inner = 0.0
    while seen and seen[-1][0] >= start:
        inner += seen.pop()[1]
    if trace_ctx.is_top_level():
        seen.clear()              # an outermost trace: nothing can hold it
    else:
        seen.append((start, duration))
    return max(duration - inner, 0.0)


def install_compile_listener() -> None:
    """Count every XLA backend compile into ``jit/backend_compiles`` and its
    seconds into ``jit/backend_compile_s`` (compile, or load from the
    persistent cache); the seconds of jaxpr tracing into ``jit/trace_s`` and
    of lowering to MLIR into ``jit/lower_s``.

    Uses jax.monitoring's duration listener (fires once per event,
    including jits we did not wrap). Idempotent
    across repeated calls, repeated Boosters, and module re-imports (the
    installed marker is a sentinel attribute on ``jax.monitoring``, not
    only a module global — see tests/test_obs.py)."""
    global _compile_listener_installed
    if _compile_listener_installed:
        return
    _compile_listener_installed = True
    from jax import monitoring
    if getattr(monitoring, _LISTENER_SENTINEL, None) is not None:
        return

    def _on_event(event: str, duration: float, **kw) -> None:
        if getattr(_suppress, "on", False):
            return
        if "backend_compile" in event:
            telemetry.count(_BACKEND_COMPILES)
            telemetry.add_time("jit/backend_compile_s", duration)
        elif event.endswith("/jaxpr_trace_duration"):
            telemetry.add_time("jit/trace_s", _exclusive_trace_s(duration))
        elif event.endswith("/jaxpr_to_mlir_module_duration"):
            telemetry.add_time("jit/lower_s", duration)

    monitoring.register_event_duration_secs_listener(_on_event)
    setattr(monitoring, _LISTENER_SENTINEL, _on_event)


class _TrackedJit:
    """Transparent wrapper over a jitted callable that turns compiled-cache
    growth into telemetry counts.

    ``fn._cache_size()`` (PjitFunction) counts cached executables — one per
    traced signature — so a positive delta across a call means that call
    paid a trace+compile. Attribute access (``.lower()``, ``.trace()``,
    static-argname metadata) delegates to the wrapped function."""

    __slots__ = ("_fn", "_name", "_seen")

    def __init__(self, name: str, fn: Callable[..., Any]) -> None:
        self._fn = fn
        self._name = name
        self._seen = self._size() or 0

    def _size(self) -> Optional[int]:
        try:
            return self._fn._cache_size()
        except Exception:  # pragma: no cover - non-pjit callable
            return None

    def __call__(self, *args, **kwargs):
        out = self._fn(*args, **kwargs)
        self.after_call(args, kwargs)
        return out

    def dispatch(self, *args, **kwargs):
        """The wrapped call alone; the caller owes :meth:`after_call` with
        the same arguments (``FusedTrainer.run`` reads the job's clock
        between the two, so the cost capture is not booked to job start)."""
        return self._fn(*args, **kwargs)

    def after_call(self, args, kwargs) -> None:
        """Count a (re)trace that the call just made and hand its signature
        to the device-cost capture."""
        size = self._size()
        if size is not None:
            if size > self._seen:
                telemetry.count(_JIT_COMPILES_PREFIX + self._name,
                                size - self._seen)
                # this exact signature just compiled: hand it to the
                # device-cost capture (AOT cost/memory analysis). Lazy
                # import breaks the obs <-> obs_device cycle; any capture
                # failure is counted there, never raised into training.
                try:
                    from . import obs_device
                    if obs_device.cost_capture_enabled():
                        obs_device.on_compile(self._name, self._fn,
                                              args, kwargs)
                except Exception:  # pragma: no cover - capture is best-effort
                    telemetry.count("device_cost/capture_errors")
            self._seen = size  # shrink = cache cleared; re-arm

    def __getattr__(self, name: str):
        return getattr(self._fn, name)


def track_jit(name: str, fn: Callable[..., Any]) -> Callable[..., Any]:
    """Wrap a jitted callable so its (re)traces count into
    ``jit/compiles/<name>``. Installs the global backend-compile listener
    on first use. Wrapping an already-tracked callable re-labels it."""
    install_compile_listener()
    if isinstance(fn, _TrackedJit):
        fn = fn._fn
    return _TrackedJit(name, fn)


def jit_compiles() -> Dict[str, int]:
    """Per-entry-point compile counts seen so far (name -> count)."""
    with telemetry._lock:
        return {k[len(_JIT_COMPILES_PREFIX):]: v
                for k, v in telemetry._counters.items()
                if k.startswith(_JIT_COMPILES_PREFIX)}


# ---------------------------------------------------------------------------
# Structured run counters
# ---------------------------------------------------------------------------

def _jsonable(v):
    """Coerce numpy scalars / arrays so snapshot() survives json.dumps."""
    if isinstance(v, (str, bool, int, float)) or v is None:
        return v
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    if hasattr(v, "item"):       # numpy / jax scalar
        try:
            return v.item()
        except Exception:
            pass
    if hasattr(v, "tolist"):
        return v.tolist()
    return repr(v)


def _log_bounds(lo: float = 2.0 ** -10, hi: float = 2.0 ** 20,
                factor: float = 2.0) -> Tuple[float, ...]:
    """Geometric bucket upper bounds lo, lo*f, ..., >= hi."""
    bounds = []
    b = float(lo)
    while b <= hi * (1 + 1e-12):
        bounds.append(b)
        b *= factor
    return tuple(bounds)


# powers of two from ~0.001 to ~1M: one ladder covers latencies in ms
# (10us..17min) and batch sizes in rows (1..1M) at ~2x resolution
DEFAULT_HIST_BOUNDS = _log_bounds()

_PCTS = ((0.50, "p50"), (0.90, "p90"), (0.99, "p99"), (0.999, "p999"))


class Histogram:
    """Log-bucketed histogram: exact counts per geometric bucket, with
    percentiles derived by linear interpolation inside the bucket.

    Replaces the serve latency deque: bounded memory regardless of
    request count, mergeable across processes, and exportable both as
    JSON (``snapshot``) and Prometheus ``_bucket{le=...}`` series
    (:func:`prometheus_text`). NOT internally locked — registry
    instances are guarded by the Telemetry lock; standalone users (the
    MicroBatcher window) bring their own.
    """

    __slots__ = ("bounds", "counts", "count", "sum")

    def __init__(self, bounds: Optional[Sequence[float]] = None) -> None:
        self.bounds = tuple(bounds) if bounds else DEFAULT_HIST_BOUNDS
        self.counts = [0] * (len(self.bounds) + 1)   # last = +Inf overflow
        self.count = 0
        self.sum = 0.0

    def observe(self, value: float) -> None:
        v = float(value)
        self.count += 1   # graftlint: guarded-by=_lock -- caller holds it
        self.sum += v     # graftlint: guarded-by=_lock -- caller holds it
        self.counts[bisect_left(self.bounds, v)] += 1   # le-inclusive

    def percentile(self, q: float) -> float:
        """q in [0, 1]; linear interpolation within the hit bucket
        (Prometheus histogram_quantile semantics)."""
        if self.count == 0:   # graftlint: guarded-by=_lock
            return 0.0
        target = q * self.count   # graftlint: guarded-by=_lock
        cum, lo = 0, 0.0
        for i, hi in enumerate(self.bounds):
            c = self.counts[i]
            if c > 0 and cum + c >= target:
                return lo + (hi - lo) * ((target - cum) / c)
            cum += c
            lo = hi
        return self.bounds[-1]   # overflow bucket: clamp to top bound

    def cumulative(self) -> List[Tuple[Any, int]]:
        """Prometheus-style cumulative buckets: [(le, count<=le), ...,
        ("+Inf", total)]."""
        out = []
        cum = 0
        for i, b in enumerate(self.bounds):
            cum += self.counts[i]
            out.append((b, cum))
        out.append(("+Inf", self.count))   # graftlint: guarded-by=_lock
        return out

    def snapshot(self) -> Dict[str, Any]:
        snap: Dict[str, Any] = {
            "count": self.count,        # graftlint: guarded-by=_lock
            "sum": round(self.sum, 6),  # graftlint: guarded-by=_lock
            "buckets": [[le, c] for le, c in self.cumulative()],
        }
        for q, label in _PCTS:
            snap[label] = round(self.percentile(q), 6)
        return snap


class Telemetry:
    """Process-global registry of counters, gauges, timers and records.

    Thread-safe (the mesh learners and user callbacks may touch it from
    worker threads) and cheap: every mutation is a dict update under one
    lock, on host, never inside traced code. ``snapshot()`` returns a
    plain JSON-serializable dict.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: Dict[str, int] = defaultdict(int)
        self._gauges: Dict[str, Any] = {}
        self._timers: Dict[str, float] = defaultdict(float)
        self._timer_calls: Dict[str, int] = defaultdict(int)
        self._records: Dict[str, List[dict]] = defaultdict(list)
        self._hists: Dict[str, Histogram] = {}

    # -- mutation --
    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counters[name] += int(n)

    def gauge(self, name: str, value) -> None:
        with self._lock:
            self._gauges[name] = _jsonable(value)

    def add_time(self, name: str, seconds: float) -> None:
        with self._lock:
            self._timers[name] += float(seconds)
            self._timer_calls[name] += 1

    def observe(self, name: str, value: float,
                bounds: Optional[Sequence[float]] = None) -> None:
        """Add one sample to the log-bucketed histogram ``name``
        (created on first use; ``bounds`` only applies then)."""
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = Histogram(bounds)
            h.observe(value)

    @contextlib.contextmanager
    def timed(self, name: str) -> Iterator[None]:
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.add_time(name, time.perf_counter() - t0)

    @contextlib.contextmanager
    def timed_observe(self, name: str) -> Iterator[None]:
        """Observe the block's wall time in MILLISECONDS into histogram
        ``name`` — for events whose distribution matters (online train
        cycles, promotion swaps), where ``timed`` would collapse them
        into a single running total."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.observe(name, (time.perf_counter() - t0) * 1e3)

    def record(self, name: str, dedupe_key=None, keep: Optional[int] = None,
               **payload) -> None:
        """Append a structured event to the ``name`` list. With
        ``dedupe_key``, an event carrying the same key is appended at most
        once (auto-knob resolutions re-run per build_kwargs call but the
        registry keeps one record per distinct resolution). With ``keep``,
        the list holds at most that many: its first event and the newest
        ``keep - 1`` (one event a block of a job of any length)."""
        with self._lock:
            lst = self._records[name]
            if keep is not None and len(lst) >= keep:
                del lst[1]
            if dedupe_key is not None:
                key = _jsonable(dedupe_key)
                if any(r.get("_key") == key for r in lst):
                    return
                payload = dict(payload, _key=key)
            lst.append(_jsonable(payload))

    # -- read --
    def counter(self, name: str) -> int:
        with self._lock:
            return self._counters.get(name, 0)

    def records(self, name: str) -> List[dict]:
        with self._lock:
            return list(self._records.get(name, []))

    def clear_records(self, name: str) -> None:
        """Start the ``name`` list anew (``engine.train`` does for the
        job's ``fused_block`` records)."""
        with self._lock:
            self._records.pop(name, None)

    def histogram(self, name: str) -> Optional[Dict[str, Any]]:
        """Snapshot of one histogram (buckets + p50/p90/p99/p999), or
        None when nothing was observed under ``name``."""
        with self._lock:
            h = self._hists.get(name)
            return h.snapshot() if h is not None else None

    def snapshot(self) -> Dict[str, Any]:
        """JSON-serializable view of everything recorded so far."""
        with self._lock:
            timers = {k: round(v, 6) for k, v in self._timers.items()}
            calls = dict(self._timer_calls)
            per_fn = {k[len(_JIT_COMPILES_PREFIX):]: v
                      for k, v in self._counters.items()
                      if k.startswith(_JIT_COMPILES_PREFIX)}
            snap = {
                "counters": dict(self._counters),
                "gauges": dict(self._gauges),
                "timers": timers,
                "timer_calls": calls,
                "jit_compiles": {
                    "per_function": per_fn,
                    "total": sum(per_fn.values()),
                    "backend_compiles":
                        self._counters.get(_BACKEND_COMPILES, 0),
                },
                "records": {k: [dict(r) for r in v]
                            for k, v in self._records.items()},
                "histograms": {k: h.snapshot()
                               for k, h in self._hists.items()},
            }
        for lst in snap["records"].values():
            for r in lst:
                r.pop("_key", None)
        try:   # outside self._lock: obs_device has its own lock
            from . import obs_device
            snap["device_cost"] = obs_device.section()
        except Exception:  # pragma: no cover - snapshot must never fail
            snap["device_cost"] = {"enabled": False, "jits": {}, "hbm": {}}
        return snap

    def reset(self) -> None:
        """Clear every counter/gauge/timer/record (tests, fresh benches)
        but :data:`PROCESS_RECORDS`, which are written once a process and
        could not be written again."""
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._timers.clear()
            self._timer_calls.clear()
            kept = {k: self._records[k] for k in PROCESS_RECORDS
                    if k in self._records}
            self._records.clear()
            self._records.update(kept)
            self._hists.clear()


# records of the process, not of a job: written once, kept by reset()
PROCESS_RECORDS = ("package_import", "runtime_start")

telemetry = Telemetry()


def record_package_import(marks: Sequence[Tuple[str, float]],
                          jax_preimported: bool) -> None:
    """The ``package_import`` record, written once at the end of
    ``lightgbm_tpu/__init__.py``. ``marks`` are its clock reads, ``("entry",
    t)`` before the first import and ``(group, t)`` after each group of
    imports; ``elapsed_s`` runs from the first to the last. Where the
    ``runtime_start`` record's stretch lies inside a group (an import that
    brings the XLA backend up: ``runtime.start``), its seconds are
    ``runtime_start_s`` here too and are taken off that group, so that
    ``import_s`` = ``elapsed_s`` - ``runtime_start_s`` is Python's importing
    alone and the ``<group>_s`` (``core_s``, ``serve_online_s``,
    ``plotting_s``, ``sklearn_s``) are disjoint and sum to it.
    ``jax_preimported``: jax was in ``sys.modules`` at entry, its own import
    is then outside this record. ``process_age_s``: seconds from the OS's
    start of the process to the entry (``/proc/self/stat`` against
    ``CLOCK_BOOTTIME``; ``None`` where the OS gives neither)."""
    entry, last = marks[0][1], marks[-1][1]
    started = telemetry.records("runtime_start")
    asked = started[0]["asked_s"] if started else None
    inside = 0.0
    parts: Dict[str, float] = defaultdict(float)
    for (_, before), (group, t) in zip(marks, marks[1:]):
        parts[group + "_s"] += t - before
        if asked is not None and before <= asked < t:
            inside = started[0]["runtime_start_s"]
            parts[group + "_s"] -= inside
    age = _process_age_s()
    telemetry.record(
        "package_import", import_s=last - entry - inside,
        elapsed_s=last - entry, runtime_start_s=inside, entry_s=entry,
        jax_preimported=bool(jax_preimported),
        process_age_s=None if age is None
        else age - (time.perf_counter() - entry), **parts)


def _process_age_s() -> Optional[float]:
    """Seconds since the OS started this process, or None."""
    try:
        import os
        with open("/proc/self/stat") as f:
            # the 22nd field, counted from after the command's ")"
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        return time.clock_gettime(time.CLOCK_BOOTTIME) \
            - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, AttributeError, ValueError, IndexError):
        return None


def count_trees(trees, rows: int) -> Dict[str, int]:
    """Growth of finished host trees, counted into ``tree/*`` and returned:
    the one place both loops count (``FusedTrainer._finalize`` a block at a
    time, ``GBDT.train_one_iter`` a tree). Of the trees that split,
    ``inbag_rows`` sums the rows at the root (the bag's, where the job bags
    its rows) and ``rows`` the training set's ``rows``."""
    grown = [t for t in trees if t.num_leaves > 1]
    sums = {"trees": len(trees),
            "splits": sum(t.num_leaves - 1 for t in trees),
            "splits_categorical": sum(t.num_cat for t in trees),
            "splits_default_left": sum(t.num_default_left for t in trees),
            "leaves": sum(t.num_leaves for t in trees),
            "inbag_rows": sum(int(t.internal_count[0]) for t in grown),
            "rows": len(grown) * int(rows)}
    for name, n in sums.items():
        telemetry.count("tree/" + name, n)
    return sums


def report_timers() -> None:
    """Log the registry's timers, longest first: at info level when
    ``LIGHTGBM_TPU_TIMETAG=1`` (the reference's USE_TIMETAG report), else at
    debug level."""
    import os
    from .utils.log import Log
    say = Log.info if os.environ.get("LIGHTGBM_TPU_TIMETAG") == "1" \
        else Log.debug
    with telemetry._lock:
        timers = dict(telemetry._timers)
        calls = dict(telemetry._timer_calls)
    say("LightGBM-TPU phase timers:")
    for name in sorted(timers, key=timers.get, reverse=True):
        say("  %-40s %10.4f s  (%d calls)", name, timers[name], calls[name])


class TimerMark:
    """The readings of some telemetry timers at one moment; :meth:`grown`
    gives what each accumulated since, under the caller's own names
    (``{part: timer name}``). How a record gets its parts from the timers
    that :func:`trace_phase` sites already feed."""

    def __init__(self, parts: Dict[str, str]) -> None:
        self._parts = dict(parts)
        self._then = self._read()

    def _read(self) -> Dict[str, float]:
        with telemetry._lock:
            return {t: telemetry._timers.get(t, 0.0)
                    for t in self._parts.values()}

    def grown(self) -> Dict[str, float]:
        now = self._read()
        return {part: now[t] - self._then[t]
                for part, t in self._parts.items()}


class JobStart:
    """What one ``lgb.train`` call spends before its first dispatch, written
    by the program as ONE ``job_start`` record (and one ``Log.info`` line
    unless the job asked for silence).

    ``engine.train`` makes it at entry and calls :meth:`init_done` when the
    booster exists; the first dispatch — the first block program called in
    ``FusedTrainer.run`` (``path="fused"``), else the first
    ``train_one_iter`` returned (``"eager"``) — calls :meth:`dispatched`.
    No device sync: dispatch is asynchronous and the clock is read after it
    returns. The parts are growths of process-global timers since entry, so
    a second job training on another thread at the same time blurs them.

    ``entry_to_first_dispatch_s`` is the whole; ``booster_init_s`` (seconds
    of ``lgbtpu/booster_init`` less the jit and capture seconds inside it),
    ``block_fn_s``, ``trace_s``, ``lower_s``, ``compile_or_load_s`` (all
    jit work since entry), ``cost_capture_s`` (``obs_device.on_compile``;
    0 on the fused path, whose capture runs behind the dispatch) and
    ``other_s`` are disjoint and sum to it. ``objective_init_s`` and
    ``learner_init_s`` are spans inside the booster's init, jit included.
    """

    # work that may also run inside the booster's init
    _NESTED = {"trace_s": "jit/trace_s", "lower_s": "jit/lower_s",
            "compile_or_load_s": "jit/backend_compile_s",
            "cost_capture_s": "device_cost/capture_s"}
    _HOST = {"booster_init_s": "train/booster_init",
             "objective_init_s": "train/objective_init",
             "learner_init_s": "train/learner_init",
             "block_fn_s": "fused/block_fn"}

    def __init__(self) -> None:
        install_compile_listener()
        self._t0 = time.perf_counter()
        self._mark = TimerMark({**self._NESTED, **self._HOST})
        self._nested_in_init = 0.0
        self._done = False
        self.verbose = True     # engine.train: the job's verbosity > 0

    def init_done(self) -> None:
        grown = self._mark.grown()
        self._nested_in_init = sum(grown[p] for p in self._NESTED)

    def dispatched(self, path: str) -> None:
        if self._done:
            return
        self._done = True
        whole = time.perf_counter() - self._t0
        rec = self._mark.grown()
        rec["booster_init_s"] -= self._nested_in_init
        rec["other_s"] = whole - sum(
            v for p, v in rec.items()
            if p not in ("objective_init_s", "learner_init_s"))
        telemetry.record("job_start", entry_to_first_dispatch_s=whole,
                         path=path, **rec)
        if not self.verbose:
            return
        from .utils.log import Log
        Log.info("job start (%s): %.3f s to the first dispatch = init %.3f "
                 "(objective %.3f, learner %.3f) + block_fn %.3f + trace "
                 "%.3f + lower %.3f + compile or load %.3f + cost capture "
                 "%.3f + other %.3f", path, whole, rec["booster_init_s"],
                 rec["objective_init_s"], rec["learner_init_s"],
                 rec["block_fn_s"], rec["trace_s"], rec["lower_s"],
                 rec["compile_or_load_s"], rec["cost_capture_s"],
                 rec["other_s"])


def safe_metric_part(part: str, max_len: int = 48) -> str:
    """Untrusted id (e.g. an HTTP tenant name) -> safe registry-key
    segment: alnum/dash/underscore only, bounded length, never empty.
    Keeps caller-controlled strings from exploding the flat metric
    namespace or smuggling separators into Prometheus names."""
    s = re.sub(r"[^a-zA-Z0-9_\-]", "_", str(part))[:max_len]
    return s or "_"


# ---------------------------------------------------------------------------
# Prometheus text exposition
# ---------------------------------------------------------------------------

def _prom_name(name: str) -> str:
    """Registry key -> legal Prometheus metric name (lgbtpu_ namespace)."""
    return "lgbtpu_" + re.sub(r"[^a-zA-Z0-9_:]", "_", name)


def _prom_num(v) -> str:
    if isinstance(v, bool):
        v = int(v)
    if isinstance(v, int):
        return str(v)
    return repr(float(v))


def prometheus_text(registry: Optional[Telemetry] = None) -> str:
    """The registry rendered in Prometheus text exposition format
    (version 0.0.4): counters as ``_total``, numeric gauges as gauges,
    timers as ``_seconds_total`` + ``_calls_total`` pairs, histograms as
    cumulative ``_bucket{le="..."}`` / ``_sum`` / ``_count`` series.
    Non-numeric gauges (layout strings, auto-knob records) are skipped —
    they stay on ``/telemetry``. Served by ``GET /metrics`` on
    :class:`serve.http.PredictServer`."""
    reg = telemetry if registry is None else registry
    with reg._lock:
        counters = dict(reg._counters)
        gauges = dict(reg._gauges)
        timers = dict(reg._timers)
        calls = dict(reg._timer_calls)
        hists = {k: h.snapshot() for k, h in reg._hists.items()}
    out: List[str] = []
    seen = set()

    def emit(name: str, typ: str, lines: List[str]) -> List[str]:
        if name in seen:   # sanitization collisions: first family wins
            return []
        seen.add(name)
        return ["# TYPE %s %s" % (name, typ)] + lines

    for k in sorted(counters):
        n = _prom_name(k) + "_total"
        out += emit(n, "counter", ["%s %s" % (n, _prom_num(counters[k]))])
    for k in sorted(gauges):
        v = gauges[k]
        if not isinstance(v, (bool, int, float)):
            continue
        n = _prom_name(k)
        out += emit(n, "gauge", ["%s %s" % (n, _prom_num(v))])
    for k in sorted(timers):
        n = _prom_name(k) + "_seconds_total"
        out += emit(n, "counter", ["%s %s" % (n, _prom_num(timers[k]))])
        c = _prom_name(k) + "_calls_total"
        out += emit(c, "counter", ["%s %s" % (c, _prom_num(calls.get(k, 0)))])
    for k in sorted(hists):
        h = hists[k]
        n = _prom_name(k)
        lines = []
        for le, cum in h["buckets"]:
            le_s = le if isinstance(le, str) else "%g" % le
            lines.append('%s_bucket{le="%s"} %d' % (n, le_s, cum))
        lines.append("%s_sum %s" % (n, _prom_num(h["sum"])))
        lines.append("%s_count %d" % (n, h["count"]))
        out += emit(n, "histogram", lines)
    return "\n".join(out) + "\n"
