"""Request-scoped span tracing: where did THIS request spend its time.

The reference runtime's platform/monitor.h + profiler stack can say how
many requests completed (int64 gauges) and where the process spends time
in aggregate (RecordEvent summary table); neither answers the production
question "why was request X slow".  This module is the Dapper-style
answer built TPU-native:

  * a **trace** is one request's tree of **spans** (trace_id/span_id/
    parent_id), covering the whole serving path — ``Server.submit`` →
    RequestQueue wait → batcher pack (with bucket/padding attribution) →
    H2D → execute → D2H → reply — plus the train-step phase breakdown
    and ``generate()``'s prefill/decode scan boundary;
  * **XLA compile events are first-class annotations**: every recompile-
    ledger record lands as an event on the active span, so a steady-state
    recompile shows up inside the exact request that paid for it;
  * **the decode scan is one device program**: the host never observes
    token k in isolation, so the decode span carries ``steps`` and the
    measured ``per_token_ms`` of the fenced scan window and no per-token
    events; on the slot path the request's tree is cut from the
    ``SlotRequest`` stamps (``slot_queue``, ``slot_prefill``,
    ``slot_decode``, ``reply_hold`` — serving/slots.py);
  * gating is ``FLAGS_trace`` off|sample|full (PADDLE_TPU_TRACE).  Off
    is ONE Python branch per instrumentation point (the shared
    ``enabled()`` check); sample keeps every round(1/rate)-th root span
    via a deterministic stride, so no per-request RNG draw.

Durations use ``time.monotonic()`` exclusively (a wall-clock jump — NTP
step, leap smearing — must never produce a negative or inflated span);
``time.time()`` appears only as the ``wall`` timestamp annotation.

Export is dual: :func:`export_chrome_trace` writes chrome://tracing JSON
whose timeline merges with the PR-1 profiler's host spans (one pid per
source), and a LogWriter JSONL sink (``FLAGS_trace_dir`` /
PADDLE_TPU_TRACE_DIR, size-capped rotation via FLAGS_log_writer_max_mb)
that ``tools/obs_report.py`` joins with metrics snapshots into
per-request waterfalls and SLO reports.
"""
from __future__ import annotations

import contextlib
import contextvars
import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from ..framework import flags as _flags

__all__ = [
    "Span", "enabled", "mode", "should_sample", "start_span", "span",
    "child", "current_span", "use_span", "finish", "event",
    "attach_compile_event", "finished_spans", "clear",
    "enable_span_export", "disable_span_export", "drain_exported_spans",
    "set_trace_dir", "export_chrome_trace", "chrome_trace_events",
]

_lock = threading.Lock()
_ring: deque = deque(maxlen=1 << 16)      # finished span dicts, newest last
# span export (cluster trace shipping): a bounded drain-once buffer a
# replica hands to the Router's scrape poll.  None while disabled — the
# cost of the feature being off is one `is None` check inside finish().
_export_buf: Optional[deque] = None
_export_cap = 4096
_export_drops = 0
_ids = itertools.count(1)
_sample_tick = itertools.count()
_dir_override = [None]
_writer = [None, None]        # [dir the writer was opened for, LogWriter]

# ambient span for the current thread/context: children created via
# span() nest under it, and ledger compile events attach to it
_current: contextvars.ContextVar = contextvars.ContextVar(
    "paddle_tpu_trace_span", default=None)


def mode() -> str:
    """Current FLAGS_trace value: 'off' | 'sample' | 'full'."""
    return str(_flags.flag("trace")).lower()


def enabled() -> bool:
    """One-branch gate for instrumentation points."""
    return mode() != "off"


def should_sample() -> bool:
    """Root-span sampling decision: True in full mode; every
    round(1/FLAGS_trace_sample_rate)-th call in sample mode (deterministic
    stride — converges to the rate with zero RNG cost); False when off.
    Child spans never re-sample: an unsampled root prunes its subtree by
    returning None."""
    m = mode()
    if m == "full":
        return True
    if m == "sample":
        rate = float(_flags.flag("trace_sample_rate"))
        stride = max(1, int(round(1.0 / rate)))
        return next(_sample_tick) % stride == 0
    return False


class Span:
    """One timed operation in a trace.  ``t0``/``dur`` are monotonic
    seconds (duration math survives wall-clock jumps); ``wall`` is the
    time.time() start timestamp for humans and cross-process joins."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "t0",
                 "wall", "dur", "attrs", "events", "_finished")

    def __init__(self, name: str, trace_id: str, span_id: int,
                 parent_id: Optional[int], t0: Optional[float] = None,
                 attrs: Optional[dict] = None):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.t0 = time.monotonic() if t0 is None else float(t0)
        self.wall = time.time()
        self.dur = None
        self.attrs = dict(attrs) if attrs else {}
        self.events: List[dict] = []
        self._finished = False

    def set_attr(self, **kw) -> "Span":
        self.attrs.update(kw)
        return self

    def event(self, name: str, t: Optional[float] = None, **attrs) -> None:
        """Point-in-time annotation on this span (monotonic ``t``)."""
        ev = {"name": name, "t": time.monotonic() if t is None else t}
        if attrs:
            ev.update(attrs)
        self.events.append(ev)

    def to_dict(self) -> dict:
        return {"trace_id": self.trace_id, "span_id": self.span_id,
                "parent_id": self.parent_id, "name": self.name,
                "t0": self.t0, "dur_ms": round((self.dur or 0.0) * 1e3, 6),
                "wall": self.wall, "attrs": dict(self.attrs),
                "events": list(self.events)}


def _new_trace_id() -> str:
    return f"{os.getpid():x}-{next(_ids):x}"


def start_span(name: str, parent: Optional[Span] = None,
               trace_id: Optional[str] = None, t0: Optional[float] = None,
               **attrs) -> Optional[Span]:
    """Open a span, or return None when tracing is off / the root was not
    sampled.  With no ``parent`` and no ``trace_id`` this is a ROOT span
    and the sampling decision is made here; with a ``parent`` the child
    rides the parent's trace (a None parent from an unsampled root means
    the caller already got None and never reaches this)."""
    if not enabled():
        return None
    if parent is not None:
        return Span(name, parent.trace_id, next(_ids), parent.span_id,
                    t0=t0, attrs=attrs)
    if trace_id is not None:
        return Span(name, trace_id, next(_ids), None, t0=t0, attrs=attrs)
    if not should_sample():
        return None
    return Span(name, _new_trace_id(), next(_ids), None, t0=t0,
                attrs=attrs)


def finish(s: Optional[Span], end: Optional[float] = None) -> None:
    """Close a span: compute its monotonic duration and emit it to the
    in-memory ring and (when FLAGS_trace_dir is set) the JSONL sink.
    Idempotent; None is accepted so call sites stay one-branch."""
    if s is None or s._finished:
        return
    s._finished = True
    s.dur = max(0.0, (time.monotonic() if end is None else end) - s.t0)
    rec = s.to_dict()
    global _export_drops
    with _lock:
        _ring.append(rec)
        if _export_buf is not None:
            if len(_export_buf) >= _export_cap:
                _export_buf.popleft()
                _export_drops += 1
            _export_buf.append(rec)
        w = _get_writer()
    if w is not None:
        w.add_event("trace/span", rec)


def child(parent: Optional[Span], name: str, t0: float, t1: float,
          **attrs) -> Optional[Span]:
    """Create AND finish a child span from explicit monotonic stamps —
    the cross-thread form (queue wait, batch phases) where the timing was
    observed outside the span's own context manager."""
    if parent is None:
        return None
    s = start_span(name, parent=parent, t0=t0, **attrs)
    finish(s, end=t1)
    return s


@contextlib.contextmanager
def span(name: str, parent: Optional[Span] = None, **attrs):
    """Context-managed span nested under ``parent`` (default: the ambient
    current span, which it becomes for the duration).  Yields None when
    tracing is off or nothing upstream was sampled — call sites need no
    second branch."""
    if not enabled():
        yield None
        return
    p = parent if parent is not None else _current.get()
    s = start_span(name, parent=p, **attrs)
    if s is None:
        yield None
        return
    tok = _current.set(s)
    try:
        yield s
    finally:
        _current.reset(tok)
        finish(s)


def current_span() -> Optional[Span]:
    return _current.get()


@contextlib.contextmanager
def use_span(s: Optional[Span]):
    """Make ``s`` the ambient span WITHOUT owning its lifetime (the
    serving worker sets a request's root while executing its batch so
    ledger compile events attach to the right trace)."""
    if s is None:
        yield None
        return
    tok = _current.set(s)
    try:
        yield s
    finally:
        _current.reset(tok)


def event(name: str, **attrs) -> None:
    """Annotate the ambient span (no-op without one)."""
    s = _current.get()
    if s is not None:
        s.event(name, **attrs)


def attach_compile_event(ev: dict) -> None:
    """Recompile-ledger hook: pin a compile event to the active span so
    'why was this request slow' can answer 'an XLA compile ran inside
    it'.  One branch when no span is ambient."""
    s = _current.get()
    if s is None:
        return
    s.event("compile", site=ev.get("site"), kind=ev.get("kind"),
            ms=ev.get("ms"))


# -- sinks + export ----------------------------------------------------------

def set_trace_dir(path: Optional[str]) -> None:
    """Route finished spans to JSONL under ``path`` (None reverts to the
    ``trace_dir`` flag / env)."""
    with _lock:
        _dir_override[0] = path


def _get_writer():
    """Lazily (re)open the JSONL writer; call with _lock held."""
    d = _dir_override[0]
    if d is None:
        d = _flags.flag("trace_dir") or None
    if d != _writer[0]:
        if _writer[1] is not None:
            try:
                _writer[1].close()
            except Exception:
                pass
        from ..utils.monitor import LogWriter
        _writer[0] = d
        _writer[1] = LogWriter(logdir=d, filename_suffix=".trace") \
            if d else None
    return _writer[1]


def finished_spans(trace_id: Optional[str] = None) -> List[dict]:
    """Snapshot of the finished-span ring, oldest first."""
    with _lock:
        out = list(_ring)
    if trace_id is None:
        return out
    return [s for s in out if s["trace_id"] == trace_id]


def clear() -> None:
    """Drop ring state (tests)."""
    global _export_drops
    with _lock:
        _ring.clear()
        if _export_buf is not None:
            _export_buf.clear()
        _export_drops = 0


def enable_span_export(cap: int = 4096) -> None:
    """Start buffering finished spans for cross-process shipping.  The
    buffer is BOUNDED: past ``cap`` undrained spans the oldest are
    dropped and counted (``drain_exported_spans`` reports the running
    drop total) — a dead Router must never grow replica memory."""
    global _export_buf, _export_cap
    with _lock:
        _export_cap = max(1, int(cap))
        if _export_buf is None:
            _export_buf = deque()


def disable_span_export() -> None:
    global _export_buf, _export_drops
    with _lock:
        _export_buf = None
        _export_drops = 0


def drain_exported_spans(limit: Optional[int] = None):
    """Drain-once read of the export buffer -> (span dicts oldest first,
    cumulative drop count).  Each span is returned exactly once; drops
    are cumulative so the reader can publish a monotonic counter."""
    with _lock:
        if _export_buf is None:
            return [], _export_drops
        n = len(_export_buf) if limit is None \
            else min(int(limit), len(_export_buf))
        out = [_export_buf.popleft() for _ in range(n)]
        return out, _export_drops


def chrome_trace_events() -> List[dict]:
    """Finished spans as chrome://tracing complete events.  Timestamps
    are mapped onto the PR-1 profiler's perf_counter timeline (one
    offset sample — µs-accurate) so one merged JSON shows host
    RecordEvent spans (pid 0) and request traces (pid 1, one tid per
    trace) side by side."""
    off_us = time.perf_counter_ns() / 1e3 - time.monotonic() * 1e6
    out = []
    tids: Dict[str, int] = {}
    for s in finished_spans():
        tid = tids.setdefault(s["trace_id"], len(tids) + 1)
        ev = {"name": s["name"], "ph": "X",
              "ts": s["t0"] * 1e6 + off_us, "dur": s["dur_ms"] * 1e3,
              "pid": 1, "tid": tid, "cat": "trace",
              "args": {"trace_id": s["trace_id"], **s["attrs"]}}
        out.append(ev)
        for e in s["events"]:
            out.append({"name": f"{s['name']}::{e['name']}", "ph": "i",
                        "ts": e["t"] * 1e6 + off_us, "pid": 1,
                        "tid": tid, "s": "t", "cat": "trace",
                        "args": {k: v for k, v in e.items()
                                 if k not in ("name", "t")}})
    return out


def export_chrome_trace(path: str, include_profiler: bool = True) -> str:
    """Write finished spans (and, by default, the profiler's host
    RecordEvent buffer) as one chrome://tracing JSON file."""
    events = chrome_trace_events()
    if include_profiler:
        from . import _events as _prof_events
        events += [{"name": name, "ph": "X", "ts": t0 / 1000,
                    "dur": dur / 1000, "pid": 0, "tid": 0, "cat": "host"}
                   for name, t0, dur in _prof_events()]
    d = os.path.dirname(os.path.abspath(path))
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump({"traceEvents": events}, f)
    return path
