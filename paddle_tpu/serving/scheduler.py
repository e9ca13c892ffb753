"""Request queue + continuous batcher.

Reference seat: the reference serves via ``AnalysisPredictor::Clone`` and
leaves batching to the application; production TPU serving cannot — batch
shape is compile shape.  This scheduler is the Orca-style continuous
batching loop: requests of mixed row counts stream into per-model FIFO
queues, and whenever a worker can take work the scheduler packs the
oldest requests into one batch, padded to a ladder bucket.  While the
workers are busy, arrivals accumulate, so the next batch is bigger —
batch size adapts to load with no per-request recompiles and no fixed
batch-size knob.

Host-side, lock-and-condvar concurrency; nothing here touches the device.
"""
from __future__ import annotations

import threading
import time
from collections import OrderedDict, deque
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..framework import flags as _flags
from ..framework.enforce import UnavailableError
from ..profiler import tracing as _tracing
from ..profiler.metrics import default_registry as _registry
from ..utils.monitor import stat_set

# typed serving histograms (docs/METRICS.md inventory): where a request
# waits, how full the batches run, how much of each bucket is padding
_QUEUE_WAIT = _registry().histogram(
    "serving_queue_wait_seconds",
    "Time a request spends in the RequestQueue between submit() and the "
    "continuous batcher packing it (per request).")
_BATCH_ROWS = _registry().histogram(
    "serving_batch_occupancy_rows",
    "Real (un-padded) rows per scheduler-formed batch — how big the "
    "continuous batcher actually runs under load.",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256))
_PAD_EFFICIENCY = _registry().histogram(
    "serving_padding_efficiency_ratio",
    "rows / bucket per batch: 1.0 = the padded bucket was full, low "
    "values = the ladder is paying for zeros.",
    buckets=(0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0))

# token-level slot accounting for the iteration-level decode loop
# (serving/slots.py, FLAGS_decode_slots): batch-level queue depth says
# nothing about how full the step executable runs — these do.  Published
# through Server.signals() into the PR-16 ClusterSignals snapshot.
SLOT_OCCUPANCY = _registry().gauge(
    "decode_slot_occupancy_ratio",
    "Generating rows / total slots at the latest decode step of the "
    "slot loop — the token-level utilisation of the single-step decode "
    "executable (1.0 = every slot is emitting).",
    labels=("model",))
SLOTS_JOINED = _registry().counter(
    "decode_slots_joined_total",
    "Requests admitted into a decode slot at a token boundary (a join "
    "is a validity-window restart: no recompile, no cache copy).",
    labels=("model",))
SLOTS_RETIRED = _registry().counter(
    "decode_slots_retired_total",
    "Rows retired from the slot loop (eos or per-request token budget), "
    "counted when their last token is read: a row that ends by its "
    "budget left its slot when that step was dispatched, one that took "
    "the end token leaves it now.",
    labels=("model",))
SLOT_STEPS = _registry().counter(
    "decode_slot_steps_total",
    "Slot-steps of the slot loop by what the slot was doing at that "
    "decode step: emitting (a token came out), prefilling (admitted, "
    "its prompt chunks or its activation still ahead), drain_blocked "
    "(empty while the FIFO head waits for the ring session to drain "
    "and restart) or no_demand (empty, nothing pending).  The four "
    "sum to steps x slots.",
    labels=("model", "state"))
SLOT_SESSION_RESETS = _registry().counter(
    "decode_slot_session_resets_total",
    "Ring-session restarts of the slot loop: the FIFO head did not fit "
    "the ring's remaining columns, the loop drained and the position "
    "went back to 0.",
    labels=("model",))
# per-tenant admission (cluster lifecycle PR): quotas bound how much of
# the shared queue one tenant can hold, so a burst from tenant A fills
# A's allowance and then bounces with a retry_after hint instead of
# growing everyone's p99
TENANT_REJECTS = _registry().counter(
    "serving_tenant_rejections_total",
    "Requests rejected because the tenant was at its pending-quota "
    "(UnavailableError with a retry_after hint; the global queue still "
    "had room for other tenants).",
    labels=("tenant",))
TENANT_PENDING = _registry().gauge(
    "serving_tenant_pending",
    "Requests currently queued per tenant — the quantity the per-tenant "
    "quota caps.",
    labels=("tenant",))
SLOT_TTFT = _registry().histogram(
    "decode_slot_ttft_seconds",
    "Time from slot-loop submit to the request's first emitted token — "
    "the metric chunked prefill exists to keep flat under long-prompt "
    "head-of-line pressure.",
    labels=("model",),
    buckets=(0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
             2.5, 5.0))
SLOT_PHASE = _registry().histogram(
    "decode_slot_phase_seconds",
    "A replied slot request's life by phase, from one set of stamps: "
    "handoff (arrival at the Server -> row handed to the loop), "
    "admit_wait (the loop's FIFO, ring drains included), prefill "
    "(slot -> first token), decode (first token -> row retired), "
    "reply_hold (row retired -> the client's Future resolved; the "
    "worker's wait for the row's batch-mates); the five sum to total, "
    "and arrival_ttft is arrival -> first token.",
    labels=("model", "phase"),
    buckets=(0.001, 0.005, 0.025, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
             20.0, 40.0))


@dataclass
class Request:
    """One client request: ``rows`` examples for one model."""

    model: str
    inputs: Tuple[np.ndarray, ...]
    rows: int
    future: Future = field(default_factory=Future)
    t_enqueue: float = field(default_factory=time.perf_counter)
    # request-scoped tracing: the root span opened by Server.submit (None
    # when FLAGS_trace is off / the request was not sampled) plus the
    # monotonic enqueue stamp the queue-wait span/histogram is cut from
    trace: Optional[object] = None
    t_enqueue_mono: float = field(default_factory=time.monotonic)
    # admission class: which tenant's quota this request consumes, and
    # its priority (higher packs first; None = the tenant policy's
    # priority, default 1).  Resolved at put() time.
    tenant: str = "default"
    priority: Optional[int] = None


@dataclass
class Batch:
    """A scheduler-formed batch: FIFO requests totalling ``rows`` rows,
    to be padded up to ``bucket`` rows at execution."""

    model: str
    requests: List[Request]
    rows: int
    bucket: int


def pack_fifo(pending, max_rows: int) -> Tuple[List[Request], int]:
    """Pop requests FIFO while they fit in ``max_rows`` total rows.
    Always takes at least the head request (callers pre-validate that a
    single request fits the ladder).  Pure queue surgery — unit-testable
    without threads."""
    taken: List[Request] = []
    rows = 0
    while pending and (not taken or rows + pending[0].rows <= max_rows):
        r = pending.popleft()
        taken.append(r)
        rows += r.rows
    return taken, rows


class RequestQueue:
    """Bounded multi-model FIFO with condition-variable handoff.

    ``put`` applies backpressure (blocks up to its timeout, then raises
    UnavailableError); ``next_batch`` blocks until work exists, holds the
    batch open up to ``batch_timeout_s`` for more arrivals, then packs
    FIFO up to the model's bucket ceiling.

    Admission is per-tenant aware: ``set_tenant_policy`` caps how many
    pending requests one tenant may hold (default from
    ``FLAGS_serving_tenant_quota``; 0 = unlimited) and assigns a
    priority class — higher priority inserts ahead of lower within a
    model's queue (FIFO within a class), so a quota'd burst from one
    tenant bounces with a retry_after hint while everyone else's wait
    stays flat.
    """

    def __init__(self, capacity: int):
        self._capacity = int(capacity)
        self._cond = threading.Condition()
        self._pending: "OrderedDict[str, deque]" = OrderedDict()  # guarded-by: _cond
        self._depth = 0                                           # guarded-by: _cond
        self._closed = False                                      # guarded-by: _cond
        # drain-rate EWMA (requests/s popped by the batcher): the basis
        # of the machine-readable retry-after hint a backpressure
        # rejection carries — "one slot frees in about 1/rate seconds"
        self._drain_ewma = 0.0                                    # guarded-by: _cond
        self._last_pop_mono: Optional[float] = None
        # staleness epoch for the hint decay: the last instant the queue
        # made progress while work was pending (a pop, or the put that
        # took it from empty).  None until work first arrives.
        self._last_progress_mono: Optional[float] = None
        # per-tenant admission state
        self._tenant_pending: Dict[str, int] = {}                 # guarded-by: _cond
        self._tenant_policy: Dict[str, dict] = {}                 # guarded-by: _cond

    def set_tenant_policy(self, tenant: str,
                          max_pending: Optional[int] = None,
                          priority: Optional[int] = None) -> None:
        """Set a tenant's admission class: ``max_pending`` caps its queued
        requests (None = fall back to ``FLAGS_serving_tenant_quota``),
        ``priority`` orders its requests against other classes (higher
        packs first; default 1)."""
        with self._cond:
            pol = self._tenant_policy.setdefault(tenant, {})
            if max_pending is not None:
                pol["max_pending"] = int(max_pending)
            if priority is not None:
                pol["priority"] = int(priority)
            self._cond.notify_all()

    def _quota_of(self, tenant: str) -> Optional[int]:
        pol = self._tenant_policy.get(tenant)
        if pol and pol.get("max_pending") is not None:
            return pol["max_pending"]
        q = int(_flags.flag("serving_tenant_quota"))
        return q if q > 0 else None

    def _hint_locked(self) -> float:
        """The retry-after estimate (lock held).  Base: 1/drain-rate
        clamped to [10 ms, 5 s], 100 ms before any batch has drained.
        Decay: when work is pending but nothing has drained within
        ``FLAGS_router_stale_after_s``, the hint ramps linearly toward
        the 5 s clamp ceiling over one further stale window — a
        drain-hung replica stops advertising the optimistic cold-start
        default and the router backs off hard instead of hammering it."""
        rate = self._drain_ewma
        hint = 0.1 if rate <= 0 else min(5.0, max(0.01, 1.0 / rate))
        if self._depth > 0 and self._last_progress_mono is not None:
            stale = float(_flags.flag("router_stale_after_s"))
            elapsed = time.monotonic() - self._last_progress_mono
            if stale > 0 and elapsed > stale:
                frac = min(1.0, (elapsed - stale) / stale)
                hint = hint + frac * (5.0 - hint)
        return hint

    def suggest_retry_after(self) -> float:
        """Estimated seconds until a queue slot frees, from the observed
        drain rate (clamped to [10 ms, 5 s]; 100 ms before any batch has
        drained, decaying toward the ceiling once the queue is stuck —
        see ``_hint_locked``).  Callers attach this to UnavailableError
        rejections so a router backs off THIS replica instead of
        evicting it."""
        with self._cond:
            return self._hint_locked()

    # -- producer ------------------------------------------------------------
    def put(self, req: Request, timeout: Optional[float] = None) -> None:
        deadline = None if timeout is None else time.perf_counter() + timeout
        tenant = req.tenant or "default"
        with self._cond:
            quota = self._quota_of(tenant)
            while not self._closed and (
                    self._depth >= self._capacity
                    or (quota is not None
                        and self._tenant_pending.get(tenant, 0) >= quota)):
                remaining = None if deadline is None \
                    else deadline - time.perf_counter()
                if remaining is not None and remaining <= 0:
                    hint = self._hint_locked()
                    over_quota = quota is not None \
                        and self._tenant_pending.get(tenant, 0) >= quota \
                        and self._depth < self._capacity
                    if over_quota:
                        TENANT_REJECTS.labels(tenant).inc()
                        raise UnavailableError(
                            f"tenant {tenant!r} at pending-quota "
                            f"({quota}); backpressure timeout expired "
                            f"(retry after ~{hint:.3f}s)",
                            retry_after_s=hint)
                    raise UnavailableError(
                        f"serving queue full ({self._capacity} pending); "
                        "backpressure timeout expired "
                        f"(retry after ~{hint:.3f}s)",
                        retry_after_s=hint)
                self._cond.wait(remaining)
                quota = self._quota_of(tenant)
            if self._closed:
                # no hint: a closed queue is not coming back — callers
                # should fail over, not retry here
                raise UnavailableError("serving queue is closed")
            if req.priority is None:
                pol = self._tenant_policy.get(tenant)
                req.priority = int(pol.get("priority", 1)) if pol else 1
            if self._depth == 0:
                # fresh epoch: idle time before this arrival is not
                # drain staleness
                self._last_progress_mono = time.monotonic()
            dq = self._pending.setdefault(req.model, deque())
            if dq and req.priority > (dq[-1].priority or 1):
                # priority insert: ahead of the first strictly-lower
                # class, FIFO within its own (deques stay sorted by
                # priority descending, so one scan suffices)
                idx = len(dq)
                for i, r in enumerate(dq):
                    if (r.priority or 1) < req.priority:
                        idx = i
                        break
                dq.insert(idx, req)
            else:
                dq.append(req)
            self._depth += 1
            self._tenant_pending[tenant] = \
                self._tenant_pending.get(tenant, 0) + 1
            TENANT_PENDING.labels(tenant).set(
                self._tenant_pending[tenant])
            stat_set("serving_queue_depth", self._depth)
            self._cond.notify_all()

    # -- consumer (scheduler thread) -----------------------------------------
    def _oldest_model(self) -> Optional[str]:
        best, best_t = None, None
        for name, dq in self._pending.items():
            if dq and (best_t is None or dq[0].t_enqueue < best_t):
                best, best_t = name, dq[0].t_enqueue
        return best

    def next_batch(self, max_rows_of, bucket_of,
                   batch_timeout_s: float) -> Optional[Batch]:
        """Form the next batch, or None once closed and drained.

        ``max_rows_of(model)`` bounds the pack; ``bucket_of(model, rows)``
        maps packed rows to the ladder bucket.
        """
        with self._cond:
            while True:
                model = self._oldest_model()
                if model is not None:
                    break
                if self._closed:
                    return None
                self._cond.wait(0.1)
            # hold the batch open for stragglers: more arrivals within the
            # window ride this batch instead of paying their own dispatch
            dq = self._pending[model]
            limit = max_rows_of(model)
            if batch_timeout_s > 0:
                deadline = dq[0].t_enqueue + batch_timeout_s
                while (sum(r.rows for r in dq) < limit
                       and not self._closed):
                    remaining = deadline - time.perf_counter()
                    if remaining <= 0:
                        break
                    self._cond.wait(remaining)
                dq = self._pending[model]
            t_pack0 = time.monotonic()
            taken, rows = pack_fifo(dq, limit)
            self._depth -= len(taken)
            if taken and self._last_pop_mono is not None:
                inst = len(taken) / max(1e-6,
                                        t_pack0 - self._last_pop_mono)
                self._drain_ewma = inst if self._drain_ewma <= 0 \
                    else 0.8 * self._drain_ewma + 0.2 * inst
            if taken:
                self._last_pop_mono = t_pack0
                self._last_progress_mono = t_pack0
            for r in taken:
                t = r.tenant or "default"
                left = self._tenant_pending.get(t, 0) - 1
                if left > 0:
                    self._tenant_pending[t] = left
                else:
                    self._tenant_pending.pop(t, None)
                TENANT_PENDING.labels(t).set(max(0, left))
            stat_set("serving_queue_depth", self._depth)
            self._cond.notify_all()
        bucket = bucket_of(model, rows)
        t_pack1 = time.monotonic()
        _BATCH_ROWS.observe(rows)
        _PAD_EFFICIENCY.observe(rows / bucket if bucket else 0.0)
        for r in taken:
            _QUEUE_WAIT.observe(t_pack0 - r.t_enqueue_mono)
            if r.trace is not None:
                _tracing.child(r.trace, "queue_wait",
                               r.t_enqueue_mono, t_pack0)
                _tracing.child(r.trace, "pack", t_pack0, t_pack1,
                               bucket=bucket, batch_rows=rows,
                               padding_rows=bucket - rows)
        return Batch(model=model, requests=taken, rows=rows, bucket=bucket)

    # -- lifecycle -----------------------------------------------------------
    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()

    def depth(self) -> int:
        with self._cond:
            return self._depth

    def signals(self) -> dict:
        """The queue's autoscaling inputs in one locked read: current
        depth, the drain-rate EWMA (requests/s the batcher is actually
        popping), and the same retry-after estimate backpressure
        rejections carry — what cluster/obs.ClusterSignals publishes
        per replica."""
        with self._cond:
            depth, rate = self._depth, self._drain_ewma
            retry = self._hint_locked()
            tenants = {t: n for t, n in self._tenant_pending.items() if n}
        return {"queue_depth": depth,
                "drain_rate_rps": round(rate, 3),
                "retry_after_s": round(retry, 4),
                "tenant_pending": tenants}

    def drain(self) -> List[Request]:
        """Pop everything still pending (stop without serving them)."""
        with self._cond:
            out: List[Request] = []
            for dq in self._pending.values():
                out.extend(dq)
                dq.clear()
            self._depth = 0
            for t in list(self._tenant_pending):
                TENANT_PENDING.labels(t).set(0)
            self._tenant_pending.clear()
            stat_set("serving_queue_depth", 0)
            self._cond.notify_all()
            return out
