"""paddle.profiler: tracing/profiling.

Reference parity: platform/profiler.h (RecordEvent :127,
Enable/DisableProfiler :209,:212, chrome-trace dump via profiler.proto) and
Python fluid/profiler.py:255; GPU-side CUPTI DeviceTracer (device_tracer.h:43);
the 2.x ``paddle.profiler.Profiler`` scheduler
(CLOSED/READY/RECORD/RECORD_AND_RETURN phases, ``make_scheduler``,
``on_trace_ready`` handlers, ``export_chrome_tracing``).

TPU-first: device-side timing comes from jax.profiler (XPlane → TensorBoard /
Perfetto — the CUPTI analogue is built into PJRT), activated per record
window; host-side RecordEvent spans are a lightweight aggregator with the
reference's summary table, and export_chrome_tracing writes the standard
chrome://tracing JSON.  The runtime's hot paths (static Executor, @to_static
dispatch, TrainStep, device.synchronize, the serving workers and the slot
loop's driver thread) are instrumented with ``span(...)``: always a
``jax.profiler.TraceAnnotation`` (on the device trace's clock; a no-op
while no capture runs), plus a RecordEvent while a Profiler window is
recording or FLAGS_enable_profiler / PADDLE_TPU_PROFILE is set.  Recompile
accounting lives in ``profiler.ledger`` and is always on.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from collections import defaultdict, deque
from typing import Optional

import jax

from ..framework import flags as _flags

_state = threading.local()

# active record windows (Profiler phases / start_profiler sessions); spans
# are collected iff this is non-zero or FLAGS_enable_profiler is set
_active = [0]


def _events():
    if not hasattr(_state, "events"):
        # bounded: a flag-enabled long run without a scheduler must not
        # grow host memory without bound (windows managed by a Profiler
        # are cleared at every window start anyway)
        _state.events = deque(maxlen=1 << 20)
        _state.stack = []
    return _state.events


def profiling_enabled() -> bool:
    """One-branch gate for the instrumented runtime paths."""
    return _active[0] > 0 or bool(_flags.flag("enable_profiler"))


class RecordEvent:
    """platform/profiler.h:127 parity (context manager / begin-end)."""

    def __init__(self, name, event_type=None):
        self.name = name
        self._t0 = None

    def begin(self):
        self._t0 = time.perf_counter_ns()

    def end(self):
        if self._t0 is not None:
            _events().append((self.name, self._t0,
                              time.perf_counter_ns() - self._t0))
            self._t0 = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()


class _Span:
    """What ``span()`` returns: a ``jax.profiler.TraceAnnotation`` (the
    host span on the device trace's clock; a no-op while no capture
    runs) and, while profiling is enabled, the ``RecordEvent`` of the
    same name."""
    __slots__ = ("_ann", "_rec")

    def __init__(self, name):
        self._ann = jax.profiler.TraceAnnotation(name)
        self._rec = RecordEvent(name) if profiling_enabled() else None

    def begin(self):
        self._ann.__enter__()
        if self._rec is not None:
            self._rec.begin()

    def end(self):
        if self._rec is not None:
            self._rec.end()
        self._ann.__exit__(None, None, None)

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


def span(name):
    """The span primitive of the runtime's instrumentation points.  It
    always enters a ``TraceAnnotation``, so every site appears on its
    thread's line of any ``.xplane.pb`` beside the device's own events,
    and records a RecordEvent too while a Profiler window records (or
    FLAGS_enable_profiler is set)."""
    return _Span(name)


class ProfilerTarget:
    CPU = 0
    GPU = 1
    TPU = 2
    CUSTOM_DEVICE = 3


class ProfilerState:
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


_REC_STATES = (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN)


def make_scheduler(*, closed=0, ready=0, record=1, repeat=0, skip_first=0):
    """paddle.profiler.make_scheduler parity: step -> ProfilerState.

    Phases cycle ``[closed (wait) | ready (warmup) | record (active)]``;
    the last record step of each cycle returns RECORD_AND_RETURN (the
    window is finalized and on_trace_ready fires there); the first
    ``skip_first`` steps are CLOSED; ``repeat=0`` cycles forever,
    ``repeat=k`` goes CLOSED after k windows."""
    if record < 1:
        raise ValueError("record span must be >= 1")
    if closed < 0 or ready < 0 or skip_first < 0 or repeat < 0:
        raise ValueError("scheduler phase lengths must be non-negative")
    span_len = closed + ready + record

    def scheduler(step):
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s // span_len >= repeat:
            return ProfilerState.CLOSED
        pos = s % span_len
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == span_len - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD
    return scheduler


def _range_scheduler(start, stop):
    """paddle's tuple scheduler: record in [start, stop)."""
    def scheduler(step):
        if start <= step < stop:
            return (ProfilerState.RECORD_AND_RETURN if step == stop - 1
                    else ProfilerState.RECORD)
        return ProfilerState.CLOSED
    return scheduler


class Profiler:
    """paddle.profiler.Profiler parity with real scheduler semantics.

    ``scheduler`` is a callable step->ProfilerState (see make_scheduler),
    a (start, stop) tuple recording in [start, stop), or None (record
    every step from start() to stop()).  ``on_trace_ready`` receives the
    profiler at the end of every record window.  While a window records,
    host spans collect (profiling_enabled() is true) and — unless
    ``timer_only`` — jax.profiler captures device-side XPlane data into
    ``profiler_result_dir``, and ``program_scopes.json`` is written beside
    it when the window ends (``ledger.program_scopes()``: which part of
    the model asked for each instruction of the compiled programs), so
    that the capture can be read by program and by named scope after the
    process is gone (docs/METRICS.md).
    """

    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, record_shapes=False, profile_memory=False,
                 with_flops=False):
        if scheduler is None:
            self._scheduler = lambda step: ProfilerState.RECORD
        elif isinstance(scheduler, (tuple, list)):
            self._scheduler = _range_scheduler(int(scheduler[0]),
                                               int(scheduler[1]))
        else:
            self._scheduler = scheduler
        self._dir = None
        self._on_ready = on_trace_ready
        self._timer_only = timer_only
        self._jax_started = False
        self._step = 0
        self.current_state = ProfilerState.CLOSED
        self._recording = False
        self._step_t0 = None
        self.round_count = 0          # completed record windows

    # -- window management ---------------------------------------------------
    def _begin_window(self):
        _events().clear()
        _active[0] += 1
        self._recording = True
        self._step_t0 = time.perf_counter_ns()
        if not self._timer_only:
            import tempfile
            self._dir = tempfile.mkdtemp(prefix="paddle_tpu_prof_")
            try:
                jax.profiler.start_trace(self._dir)
                self._jax_started = True
            except Exception:
                self._jax_started = False

    def _end_window(self):
        # fence pending device work so the window's device trace and the
        # final step span are honest (device.synchronize is the
        # framework's fence)
        try:
            from .. import device as _device
            _device.synchronize()
        except Exception:
            pass
        if self._jax_started:
            try:
                jax.profiler.stop_trace()
                from . import ledger
                with open(os.path.join(self._dir, "program_scopes.json"),
                          "w") as f:
                    json.dump(ledger.program_scopes(), f)
            except Exception:
                pass
            self._jax_started = False
        self._recording = False
        _active[0] = max(0, _active[0] - 1)
        self.round_count += 1
        if self._on_ready is not None:
            self._on_ready(self)

    # -- lifecycle -----------------------------------------------------------
    def start(self):
        self._step = 0
        self.current_state = self._scheduler(0)
        if self.current_state in _REC_STATES:
            self._begin_window()

    def step(self, num_samples=None):
        prev = self.current_state
        if self._recording:
            now = time.perf_counter_ns()
            _events().append((f"ProfileStep#{self._step}", self._step_t0,
                              now - self._step_t0))
            self._step_t0 = now
        self._step += 1
        self.current_state = self._scheduler(self._step)
        if self._recording and (prev == ProfilerState.RECORD_AND_RETURN
                                or self.current_state not in _REC_STATES):
            self._end_window()
        if not self._recording and self.current_state in _REC_STATES:
            self._begin_window()

    def stop(self):
        if self._recording:
            now = time.perf_counter_ns()
            _events().append((f"ProfileStep#{self._step}", self._step_t0,
                              now - self._step_t0))
            self._end_window()

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        s = summary_string()
        print(s)
        return s

    @property
    def profiler_result_dir(self):
        return self._dir


def summary_string():
    """Event summary table (profiler.cc report parity: calls/total/avg/max)."""
    agg = defaultdict(lambda: [0, 0, 0])  # name -> [calls, total_ns, max_ns]
    for name, _, dur in _events():
        a = agg[name]
        a[0] += 1
        a[1] += dur
        a[2] = max(a[2], dur)
    lines = [f"{'Event':<40}{'Calls':>8}{'Total(ms)':>12}{'Avg(ms)':>12}"
             f"{'Max(ms)':>12}", "-" * 84]
    for name, (calls, total, mx) in sorted(agg.items(),
                                           key=lambda kv: -kv[1][1]):
        lines.append(f"{name:<40}{calls:>8}{total / 1e6:>12.3f}"
                     f"{total / calls / 1e6:>12.3f}{mx / 1e6:>12.3f}")
    return "\n".join(lines)


def export_chrome_tracing(dir_name, worker_name=None):
    """Write host events as chrome://tracing JSON (profiler.proto dump
    parity); returns an on_trace_ready callback.  With a worker_name,
    repeat windows write one file per round; the default filename keeps
    the historical ``paddle_tpu_trace.json`` (overwritten per window)."""
    import os

    def handler(prof):
        os.makedirs(dir_name, exist_ok=True)
        trace = [{"name": name, "ph": "X", "ts": t0 / 1000,
                  "dur": dur / 1000, "pid": 0, "tid": 0, "cat": "host"}
                 for name, t0, dur in _events()]
        # merge finished request spans (profiler.tracing) into the same
        # timeline: pid 0 = host RecordEvents, pid 1 = request traces
        from .tracing import chrome_trace_events
        trace += chrome_trace_events()
        if worker_name:
            rnd = getattr(prof, "round_count", 0) or 1
            fname = f"{worker_name}_r{rnd}.json"
        else:
            fname = "paddle_tpu_trace.json"
        with open(os.path.join(dir_name, fname), "w") as f:
            json.dump({"traceEvents": trace}, f)
    return handler


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path=None):
    """fluid.profiler.profiler (fluid/profiler.py:255) parity."""
    p = Profiler(timer_only=True)
    p.start()
    try:
        yield
    finally:
        p.stop()
        print(summary_string())


def start_profiler(state="All"):
    _events().clear()
    _active[0] += 1


def stop_profiler(sorted_key=None, profile_path=None):
    _active[0] = max(0, _active[0] - 1)
    print(summary_string())


# recompile ledger (always-on compile accounting; see ledger.py)
from . import ledger  # noqa: E402,F401
from .ledger import compile_events, set_ledger_dir  # noqa: E402,F401

# typed metrics plane + serving instruments (see metrics.py)
from . import metrics  # noqa: E402,F401
from .metrics import (Counter, Gauge, Histogram,  # noqa: E402,F401
                      LatencyWindow, MetricsRegistry, RateMeter,
                      default_registry, serve_metrics, write_textfile)

# request-scoped span tracing (FLAGS_trace; see tracing.py)
from . import tracing  # noqa: E402,F401
from .tracing import Span, export_chrome_trace, set_trace_dir  # noqa: E402,F401

# device-side: direct jax.profiler bridges
start_trace = jax.profiler.start_trace
stop_trace = jax.profiler.stop_trace
TraceAnnotation = jax.profiler.TraceAnnotation
