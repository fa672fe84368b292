"""Profiler.

Reference parity: paddle/fluid/platform/profiler.h:127 RecordEvent /
:213 EnableProfiler + python/paddle/fluid/profiler.py:314. TPU-native:
jax.profiler (XPlane) captures real device timelines viewable in
TensorBoard / Perfetto; RecordEvent lowers to jax.profiler.TraceAnnotation
+ jax.named_scope so op metadata reaches the XLA trace, the analogue of
the reference's NVTX/CUPTI annotations.

record_scope is the framework's single instrumentation point with
THREE sinks (see paddle_tpu.observability): the XPlane annotation
above, the bounded host-span ring buffer (chrome://tracing dump), and
the process metrics registry (per-scope seconds/calls, Prometheus
text) — so a scope placed once in the serving engine or the hapi
training loop shows up in the device timeline, the host timeline, and
the dashboard. host_scope is the same three sinks for a section under
which nothing is ever staged (the serving step loop, the DataLoader,
a compiled to_static call): it leaves out the jax.named_scope push,
which only names ops traced inside it.
"""
import contextlib
import threading
import time

import jax

from ..observability import registry as _obs_registry
from ..observability import tracing as _obs_tracing

# framework-wide per-scope accrual (the "dashboard" sink): seconds and
# call count per scope name, in the process-global registry
_span_seconds = _obs_registry.default_registry().counter(
    "host_span_seconds_total",
    "wall seconds accrued per record_scope name", labelnames=("span",))
_span_calls = _obs_registry.default_registry().counter(
    "host_span_calls_total",
    "record_scope completions per scope name", labelnames=("span",))


class RecordEvent:
    """RAII scope annotation (reference: profiler.h:127)."""

    def __init__(self, name, event_type=None):
        self.name = name
        self._cm = None

    def __enter__(self):
        self._cm = contextlib.ExitStack()
        self._cm.enter_context(jax.profiler.TraceAnnotation(self.name))
        self._cm.enter_context(device_scope(self.name))
        return self

    def __exit__(self, *exc):
        self._cm.close()
        return False

    def begin(self):
        self.__enter__()

    def end(self):
        self.__exit__()


class host_scope:
    """One host-only scope, three sinks: a TraceAnnotation on the
    profiler's clock while it is open; at exit the span goes into the
    host-span ring (``self.span`` is the HostSpan it left, so a caller
    that learns a fact only afterwards can still attach ``args`` to it)
    and seconds + a call accrue in the process registry. An optional
    ``sink(name, dt)`` receives the same elapsed seconds — the hook
    the serving metrics hang their per-engine accounting on.

    ``drop()`` inside the scope leaves none of that behind (ring,
    registry, sink): for a probe that turns out to have done nothing,
    such as the ``next()`` that finds an epoch over. ``t0`` (a
    perf_counter stamp) backdates the recorded span to a wait the
    caller timed before it knew the scope would open; the annotation
    cannot be backdated and opens on entry.

    For code that dispatches already-compiled programs or does plain
    host work. A section that may run under staging (jit tracing) wants
    record_scope, which also names the ops traced inside it."""

    __slots__ = ("name", "sink", "span", "_ann", "_t0")

    def __init__(self, name, sink=None, t0=None):
        self.name = name
        self.sink = sink
        self.span = None
        self._t0 = t0

    def __enter__(self):
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        if self._t0 is None:
            self._t0 = time.perf_counter()
        return self

    def drop(self):
        self.name = None

    def __exit__(self, *exc):
        t0 = self._t0
        dt = time.perf_counter() - t0
        self._ann.__exit__(*exc)
        if self.name is not None:
            self.span = record_span(self.name, t0, dt, self.sink)
        return False


def record_span(name, t0, dt, sink=None):
    """The three sinks of host_scope for a section timed by hand
    (``t0`` on perf_counter, ``dt`` seconds): ring, registry, ``sink``;
    returns the HostSpan. No TraceAnnotation: one cannot be opened in
    the past. For a wait that is only worth a span once it is over
    (the gateway driver's, if the iteration then steps) or a span that
    sums several pieces (the callbacks of one harvest)."""
    span = _recorder.record(name, t0, dt)
    kids = _span_kids.get(name)
    if kids is None:
        kids = _span_kids[name] = (_span_seconds.labels(name),
                                   _span_calls.labels(name))
    kids[0].inc(dt)
    kids[1].inc()
    if sink is not None:
        sink(name, dt)
    return span


_recorder = _obs_tracing.default_recorder()
# name -> (seconds child, calls child): .labels() takes the registry's
# lock and rebuilds the label tuple on every call, and a scope name is
# one of a few dozen literals
_span_kids = {}


_device_scopes = threading.local()


def current_scopes():
    """The names of the ``device_scope``s open on this thread, outermost
    first, as one tuple (every push makes a new one, so whatever is
    recorded inside one scope shares it). The tape keeps it on a
    ``GradNode`` recorded while a step is staged and runs the node's
    backward under ``bwd`` + these names."""
    return getattr(_device_scopes, "stack", ())


class device_scope:
    """``jax.named_scope(name)`` that also keeps ``name`` on the
    framework's own stack (``current_scopes``): the one way a model or
    a serving program names the layer its staged ops belong to. Names
    only what is traced inside it (``op_name`` metadata of the compiled
    program, read back through ``observability.watchdog.
    program_scopes``); no span, no clock, nothing at run time."""

    __slots__ = ("name", "_prev", "_ns")

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self._prev = current_scopes()
        _device_scopes.stack = self._prev + (self.name,)
        self._ns = jax.named_scope(self.name)
        self._ns.__enter__()
        return self

    def __exit__(self, *exc):
        _device_scopes.stack = self._prev
        return self._ns.__exit__(*exc)


@contextlib.contextmanager
def record_scope(name, sink=None):
    """One scope, three sinks. Entering annotates the XLA trace
    (TraceAnnotation + named_scope, visible in a live XPlane capture);
    exiting records the span into the bounded host-span ring buffer
    (observability.default_recorder(), dumpable as a chrome://tracing
    timeline) and accrues seconds + a call count into the process
    metrics registry (observability.default_registry(), scrapeable as
    Prometheus text). An optional ``sink(name, dt)`` callback receives
    the same elapsed seconds. host_scope plus the device_scope that
    names the ops staged inside the section."""
    with host_scope(name, sink), device_scope(name):
        yield


class ProfilerState:
    """Reference: paddle.profiler.ProfilerState."""
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget:
    """Reference: paddle.profiler.ProfilerTarget (CPU/GPU); device
    timelines here come from the XPlane capture, which covers both."""
    CPU = 0
    GPU = 1
    TPU = 2


def make_scheduler(closed=0, ready=0, record=1000000, repeat=0,
                   skip_first=0):
    """Reference: paddle.profiler.make_scheduler — step-state schedule
    [skip_first][closed][ready][record]... repeated."""
    period = closed + ready + record

    def schedule(step):
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat > 0 and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return schedule


def export_chrome_tracing(dir_name, worker_name=None):
    """Reference: paddle.profiler.export_chrome_tracing. The XPlane
    capture already contains a Perfetto/chrome-compatible trace; the
    callback carries the target dir so the Profiler redirects its
    capture there BEFORE the first trace starts (assigning at
    trace-ready time would be too late — the file is already written)."""
    def on_ready(prof):
        return dir_name
    on_ready._export_dir = dir_name
    return on_ready


class Profiler:
    """paddle.profiler.Profiler-style API over jax.profiler traces
    (reference: python/paddle/profiler/profiler.py). start/stop (or the
    scheduler) capture an XPlane trace under log_dir — the TPU-native
    analogue of the reference's CUPTI DeviceTracer timeline
    (platform/device_tracer.h:43) — viewable in TensorBoard/Perfetto."""

    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 log_dir="./profiler_log", timer_only=False):
        self.log_dir = log_dir
        self.timer_only = timer_only
        if isinstance(scheduler, tuple):
            start, stop = scheduler
            scheduler = make_scheduler(closed=start, ready=0,
                                       record=stop - start, repeat=1)
        self.scheduler = scheduler
        self.on_trace_ready = on_trace_ready
        export_dir = getattr(on_trace_ready, "_export_dir", None)
        if export_dir is not None:
            self.log_dir = export_dir
        self._started = False
        self._tracing = False
        self._step_num = 0
        self._step_times = []
        self._t0 = None

    def _state(self):
        if self.scheduler is None:
            return ProfilerState.RECORD
        return self.scheduler(self._step_num)

    def _sync_trace(self):
        want = (not self.timer_only
                and self._state() in (ProfilerState.RECORD,
                                      ProfilerState.RECORD_AND_RETURN))
        if want and not self._tracing:
            jax.profiler.start_trace(self.log_dir)
            self._tracing = True
        elif not want and self._tracing:
            jax.profiler.stop_trace()
            self._tracing = False
            if self.on_trace_ready is not None:
                self.on_trace_ready(self)

    def start(self):
        self._started = True
        self._sync_trace()
        self._t0 = time.perf_counter()

    def stop(self):
        if self._tracing:
            jax.profiler.stop_trace()
            self._tracing = False
            if self.on_trace_ready is not None:
                self.on_trace_ready(self)
        self._started = False

    def step(self):
        now = time.perf_counter()
        if self._t0 is not None:
            self._step_times.append(now - self._t0)
        self._t0 = now
        self._step_num += 1
        if self._started:
            self._sync_trace()

    def step_info(self, unit=None):
        """Step-time summary string; ``unit`` selects milliseconds
        ("ms", default) or seconds ("s")."""
        unit = "ms" if unit is None else str(unit).lower()
        if unit not in ("ms", "s"):
            raise ValueError(f"unit must be 'ms' or 's', got {unit!r}")
        if not self._step_times:
            return "no steps recorded"
        import numpy as np
        arr = np.asarray(self._step_times[1:] or self._step_times)
        scale = 1000.0 if unit == "ms" else 1.0
        return (f"avg step {arr.mean() * scale:.3f} {unit}, "
                f"min {arr.min() * scale:.3f} {unit}, "
                f"max {arr.max() * scale:.3f} {unit}")

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    def summary(self, **kwargs):
        return self.step_info()


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path=None):
    """Legacy fluid.profiler.profiler context (reference:
    python/paddle/fluid/profiler.py:314)."""
    p = Profiler(log_dir=profile_path or "./profiler_log")
    p.start()
    try:
        yield p
    finally:
        p.stop()


def start_profiler(state="All", tracer_option=None):
    jax.profiler.start_trace("./profiler_log")


def stop_profiler(sorted_key=None, profile_path=None):
    jax.profiler.stop_trace()
