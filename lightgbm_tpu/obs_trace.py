"""Host-side span tracing and flight recorder.

The telemetry registry (obs.py) answers "how many / how long in total";
this module answers "where did the wall-clock of THIS request / THIS
training block go".  It provides:

- ``SpanTracer``: nested spans with monotonic start + duration, recorded
  per-thread and optionally carrying a request ``trace_id`` so the serve
  chain (http -> batcher queue/coalesce -> session dispatch -> slice)
  can be stitched back together across threads.
- a bounded **flight recorder**: completed spans land in a ring buffer
  (newest-wins) that can be dumped on demand (``tracer.dump(path)``,
  ``Booster.dump_trace``), at exit (``cli --dump-trace``), or on
  ``SIGUSR2`` (``install_signal_handlers``).
- Chrome trace-event JSON export (``chrome_trace``): load the dump in
  Perfetto (https://ui.perfetto.dev) or chrome://tracing.

Tracing is zero-cost-when-off: the mode flag (``off|on|serve_only``,
config ``trace_spans``) is checked as a plain attribute read before any
allocation, ``span()`` returns a shared no-op context manager, and
``tests/test_trace.py`` pins the off-path overhead compile-budget style.

Spans are HOST-side: inside a jit trace ``phase_begin`` refuses to
record (via ``jax.core.trace_ctx.is_top_level``), so ``trace_phase`` sites
that live in traced code cost nothing at runtime and do not pollute the
recorder with trace-time measurements.

Who feeds what. This module is the serving chain's flight recorder: the
http / batcher / session / online / fleet spans (and the linear-tree fit)
come straight through ``tracer.span`` / ``tracer.record`` and reach nothing
else (plus the ``span_ms/<name>`` histogram in the registry on every span
end). The boosting loop does not call this module: its named regions go
through
``obs.trace_phase`` (table: ``obs.PHASES``), which feeds, in one ``with``,
the device program's ``op_name`` scope, the profiler's host annotation,
a telemetry timer where the site names one, and — only with
``trace_spans=on`` — a span here, through ``phase_begin``. So a training
span in a dump (``lgbtpu/train_block`` > ``lgbtpu/fused_dispatch``,
``fused_device_wait``, ``fused_flush``, ``fused_host_trees``,
``fused_commit``) is the same region the benchmark reads from the
profiler's clock, and the timers (``fused/*``, ``train/*``) hold the same
seconds with tracing off. ``lgbtpu/fused_device_wait`` (an ``obs.sync``
completion barrier: device execution as the host waits for it) precedes
``lgbtpu/fused_flush`` (the result transfer).

Import-time this module is pure stdlib; jax is resolved lazily when
tracing is first switched on.
"""
import itertools
import json
import os
import threading
import time
from collections import deque

from .obs import telemetry

monotonic = time.perf_counter

DEFAULT_CAPACITY = 65536
MODES = ("off", "on", "serve_only")

# HTTP header carrying a trace id across process boundaries (client ->
# /predict, replica transport -> trainer /fleet endpoints).  The value
# is the decimal trace id; foreign ids (non-numeric) are carried opaque.
TRACE_HEADER = "X-Trace-Id"


def format_trace_id(trace_id):
    """Trace id -> header value."""
    return str(trace_id)


def parse_trace_id(value):
    """Header value -> trace id (int when it parses, else the raw string
    bounded to 128 chars so a hostile header cannot bloat spans), or
    None for absent/blank values."""
    if not value:
        return None
    value = value.strip()
    if not value:
        return None
    try:
        return int(value, 10)
    except ValueError:
        return value[:128]

# histogram family for per-phase timings, fed on every span end while
# tracing is on (per-phase train timings / serve stage timings)
_SPAN_HIST_PREFIX = "span_ms/"


class Span(object):
    """One completed (or in-flight) span. Times are perf_counter floats."""

    __slots__ = ("name", "t0", "dur", "tid", "thread", "trace_id", "args")

    def __init__(self, name, t0, trace_id=None, args=None):
        self.name = name
        self.t0 = t0
        self.dur = 0.0
        self.tid = threading.get_native_id()
        self.thread = threading.current_thread().name
        self.trace_id = trace_id
        self.args = args


class _NullSpan(object):
    """Shared no-op context manager returned when tracing is off.

    A single module-level instance (identity-checkable in tests) so the
    off path allocates nothing per call."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


class _SpanCtx(object):
    __slots__ = ("_tracer", "span")

    def __init__(self, tracer, span):
        self._tracer = tracer
        self.span = span

    def __enter__(self):
        return self.span

    def __exit__(self, *exc):
        self._tracer.end(self.span)
        return False


class SpanTracer(object):
    """Thread-aware span tracer with a bounded flight-recorder ring.

    Mode gates which domains record (``train_on`` / ``serve_on`` are
    plain attributes so hot paths pay one attribute read when off):

    - ``off``:        nothing records (default)
    - ``on``:         train phases + serve chain
    - ``serve_only``: only the serve chain (http/batcher/session)
    """

    def __init__(self, capacity=DEFAULT_CAPACITY):
        self.mode = "off"
        self.train_on = False
        self.serve_on = False
        self.spans_started = 0        # monotone; pins off-path overhead
        self._lock = threading.Lock()
        self._ring = deque(maxlen=capacity)
        self._epoch = monotonic()
        self._ids = itertools.count(1)
        self._tls = threading.local()
        self._at_top_level = None   # bound by configure() when on
        # fleet process identity: stamped into chrome_trace process_name
        # so merged multi-process exports keep nodes distinguishable
        self.identity_role = None
        self.identity_holder = None

    # ------------------------------------------------------------- setup
    def configure(self, mode, capacity=None):
        """Set the tracing mode (and optionally resize the ring)."""
        if mode not in MODES:
            raise ValueError("trace_spans must be one of %s, got %r"
                             % ("|".join(MODES), mode))
        if capacity is not None and capacity != self._ring.maxlen:
            with self._lock:
                self._ring = deque(self._ring, maxlen=max(1, int(capacity)))
        self.mode = mode
        self.train_on = mode == "on"
        self.serve_on = mode in ("on", "serve_only")
        if self.serve_on or self.train_on:
            # host spans must not record while jax is tracing a function:
            # that would measure trace time once per compile, not runtime.
            # Imported here so a tracer that stays off never imports jax;
            # a jax without it fails loudly instead of recording in traces.
            from jax.core import trace_ctx
            self._at_top_level = trace_ctx.is_top_level
        return self

    def new_trace_id(self):
        # pid-salted so ids minted by different fleet processes never
        # collide when their traces are merged into one Perfetto load;
        # getpid() is read per call so forked children stay distinct
        return ((os.getpid() & 0x3FFFFF) << 40) | next(self._ids)

    def set_identity(self, role=None, holder=None):
        """Label this process for multi-process trace merges (fleet
        role + holder id; cli serve sets this when fleet mode is on)."""
        with self._lock:
            self.identity_role = role
            self.identity_holder = holder

    def identity(self):
        """JSON-serializable process identity (pid always present)."""
        with self._lock:
            role, holder = self.identity_role, self.identity_holder
        doc = {"pid": os.getpid()}
        if role:
            doc["role"] = role
        if holder:
            doc["holder"] = holder
        return doc

    def current_trace_id(self):
        """Trace id of the innermost open span on this thread (None when
        no span is open) — lets the fleet transport propagate the active
        request's id over HTTP without threading it through every call."""
        stack = getattr(self._tls, "stack", None)
        return stack[-1].trace_id if stack else None

    # ----------------------------------------------------------- spanning
    def _stack(self):
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def begin(self, name, trace_id=None, args=None):
        """Open a span on the current thread; returns it for end()."""
        stack = self._stack()
        if trace_id is None and stack:
            trace_id = stack[-1].trace_id
        sp = Span(name, monotonic(), trace_id, args)
        stack.append(sp)
        with self._lock:
            self.spans_started += 1
        return sp

    def end(self, sp):
        """Close a span: fix duration, pop the stack, hit the recorder."""
        sp.dur = monotonic() - sp.t0
        stack = self._stack()
        if stack and stack[-1] is sp:
            stack.pop()
        elif sp in stack:            # tolerate out-of-order ends
            stack.remove(sp)
        with self._lock:
            self._ring.append(sp)
        telemetry.observe(_SPAN_HIST_PREFIX + sp.name, sp.dur * 1e3)

    def span(self, name, domain="train", trace_id=None, **args):
        """Context-manager span; shared no-op when the domain is off.
        The ``online`` domain (continual-refit trainer: train cycles,
        shadow scoring, promotion swaps) records whenever the serve chain
        does — promotions are part of the serving story, and serve_only
        deployments must still see them."""
        on = self.serve_on if domain in ("serve", "online") else self.train_on
        if not on:
            return NULL_SPAN
        return _SpanCtx(self, self.begin(name, trace_id, args or None))

    def phase_begin(self, name):
        """Hot-path hook for obs.trace_phase: no kwargs, no allocation
        when train tracing is off or a jit trace is in flight."""
        if not self.train_on:
            return None
        if not self._at_top_level():
            return None
        return self.begin(name)

    def record(self, name, t0, t1, trace_id=None, args=None):
        """Record a retroactive span from explicit timestamps (e.g. the
        batcher marking a request's queue wait after dequeue)."""
        sp = Span(name, t0, trace_id, args)
        sp.dur = max(0.0, t1 - t0)
        with self._lock:
            self.spans_started += 1
            self._ring.append(sp)
        telemetry.observe(_SPAN_HIST_PREFIX + name, sp.dur * 1e3)
        return sp

    # ------------------------------------------------------------- export
    def events(self):
        """Completed spans currently in the flight recorder (oldest
        first; bounded by the ring capacity)."""
        with self._lock:
            return list(self._ring)

    def clear(self):
        with self._lock:
            self._ring.clear()
            self._epoch = monotonic()

    def chrome_trace(self):
        """Flight recorder as a Chrome trace-event JSON object
        (Perfetto / chrome://tracing loadable)."""
        with self._lock:
            spans = list(self._ring)
            epoch = self._epoch
            id_role, id_holder = self.identity_role, self.identity_holder
        pid = os.getpid()
        threads = {}
        events = []
        for sp in spans:
            threads.setdefault(sp.tid, sp.thread)
            ev = {"name": sp.name, "ph": "X", "pid": pid, "tid": sp.tid,
                  "ts": round((sp.t0 - epoch) * 1e6, 3),
                  "dur": round(sp.dur * 1e6, 3)}
            args = dict(sp.args) if sp.args else {}
            if sp.trace_id is not None:
                args["trace_id"] = sp.trace_id
            if args:
                ev["args"] = args
            events.append(ev)
        pname = "lightgbm-tpu"
        if id_role or id_holder:
            pname += " [%s]" % " ".join(
                str(x) for x in (id_role, id_holder) if x)
        meta = [{"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
                 "args": {"name": pname}}]
        for tid in sorted(threads):
            meta.append({"name": "thread_name", "ph": "M", "pid": pid,
                         "tid": tid, "args": {"name": threads[tid]}})
        return {"traceEvents": meta + events, "displayTimeUnit": "ms"}

    def dump(self, path):
        """Write the Chrome trace JSON atomically; returns event count."""
        doc = self.chrome_trace()
        _atomic_write_json(path, doc)
        return len(doc["traceEvents"])


tracer = SpanTracer()


def _atomic_write_json(path, obj):
    tmp = "%s.tmp.%d" % (path, os.getpid())
    with open(tmp, "w") as f:
        json.dump(obj, f, indent=1)
    os.replace(tmp, path)


# --------------------------------------------------------------- dumping
def dump_telemetry(path):
    """Write the telemetry registry snapshot (atomic replace, so a
    reader never sees a torn file even mid-dump)."""
    _atomic_write_json(path, telemetry.snapshot())


def install_signal_handlers(telemetry_path=None, trace_path=None):
    """SIGUSR1 -> telemetry snapshot, SIGUSR2 -> trace dump.

    Lets a hung/live server be inspected from outside:
    ``kill -USR1 <pid>``.  Main-thread only (signal module constraint);
    silently a no-op on platforms without SIGUSR1/2. Returns the list of
    signals installed."""
    import signal
    installed = []
    if telemetry_path and hasattr(signal, "SIGUSR1"):
        def _usr1(signum, frame):
            dump_telemetry(telemetry_path)
        signal.signal(signal.SIGUSR1, _usr1)
        installed.append("SIGUSR1")
    if trace_path and hasattr(signal, "SIGUSR2"):
        def _usr2(signum, frame):
            tracer.dump(trace_path)
        signal.signal(signal.SIGUSR2, _usr2)
        installed.append("SIGUSR2")
    return installed


def start_periodic_telemetry_dump(path, interval_s):
    """Dump telemetry to `path` every `interval_s` seconds from a named
    daemon thread until the returned Event is set (cli serve uses this
    so a wedged server still leaves fresh counters on disk)."""
    stop = threading.Event()

    def _loop():
        while not stop.wait(interval_s):
            try:
                dump_telemetry(path)
            except OSError:
                pass

    t = threading.Thread(target=_loop, name="lgbtpu-telemetry-dump",
                         daemon=True)
    t.start()
    return stop
