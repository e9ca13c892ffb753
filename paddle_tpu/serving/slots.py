"""Iteration-level continuous batching: the slot-based decode loop.

The run-to-completion decode path (serving/decode.py ``execute``) runs
every batch as ONE scanned program: a 5-token request waits on its
500-token neighbor and arrivals queue until the whole batch drains.
This module hoists the token loop onto the HOST — Orca-style iteration-
level scheduling — over TWO slot executables the Generator compiles per
(slot-count, cache-bucket):

  * the step: one greedy token step for all ``S`` slot rows (or one
    speculative propose/verify/accept step under a draft pair);
  * the chunk: one Sarathi-style prefill chunk — ``T`` prompt tokens of
    ONE joining row, interleaved between decode steps so long prompts
    never stall the running rows' token cadence.

They come as a pair (``Generator.slot_execs(S, T, C)``): both run
thousands of times over one copy of the weights, so the pair first
settles in which device layout each weight lies.  A third, small
program joins them (``Generator.put_logits_row_exec(S)``): it writes the
final chunk's logits into the joining row of the step's ``[S, V]``
logits, in place and on the device.  That plane and a row's activation
logits stay device arrays from the program that made them to the step
that reads them: the driver thread, the only one that feeds the device,
fetches neither, so it dispatches the next step BEHIND a final chunk
and not after it.

**One step in flight.**  Nothing a plain step needs from the host hangs
on the step before it: the tokens are chosen on the device from the
device's logits, ``finished`` goes from one step's output into the
next's input as the device array it is, the cache is donated through,
and ``pos`` is a host counter.  So an iteration of the plain loop is
admit -> chunks -> activate -> DISPATCH step k+1 -> only then READ step
k's tokens (and the counts of the chunks dispatched before step k), emit,
commit the counters, retire: the device holds its next programs while the
driver thread does its own work.  Which side the loop then waits on,
``counters["steps_read_ready"]`` says: a read that waits (the usual case
where a step outlasts an iteration's host work) means the device was busy
with step k while step k+1 lay queued behind it; a read that finds step
k's tokens already there means the driver was the slower side, and the
device had only the one queued step to live on.  The depth is one, always,
for every model the plain loop serves.  What follows from it:

  * **a row that reaches ``max_new`` leaves at dispatch** — every
    generating row emits exactly one token a plain step, so the host knows
    when it dispatches step k which rows end there.  It hands step k+1 a
    vacated row for them (``active`` false, ``start = C``) and frees the
    slot for ``_admit`` in the very next iteration, as a loop that read
    before it dispatched would; the row's record waits in ``_leaving`` for
    its last token (a session row's snapshot is pulled behind step k,
    before a new occupant can write the row), and its future resolves
    when step k is read;
  * **a row that takes the end token costs one masked slot-step** — the
    host learns of it a step late, so step k+1 still lists the row as
    ``active``; the step itself knows (``finished``, on the device) and
    passes the row by.  ``counters["slot_steps_retire_lag"]`` counts
    these; the slot is free one iteration later than it would be;
  * **whatever reads or rewrites loop state settles the step in flight
    first** (``_settle``): ``_drive`` when no slot is occupied, which is
    before it idles, ends (``close()``) or restarts the ring;
    ``_fast_forward`` (no row generates: nothing to dispatch behind it);
    and before ``_do_park`` parks sessions (outside the lock: the read
    commits and replies under it).  A step whose
    DISPATCH raises fails its rows only after the step before it has
    delivered its tokens; a step whose READ raises fails every row, the
    rows in ``_leaving`` too;
  * **the speculative loop does not run ahead** — its ``ncommit`` decides
    ``pos``, and ``pos`` decides which chunks and activations come before
    the next step, so the host has to read a step before it can dispatch
    another.  That is a property of the program the loop was built for
    (``self._spec``), not a setting.

The scheduling invariants that make slot reuse BIT-EXACT against a
per-request ``generate()`` of the same prompt:

  * **scalar lockstep position** — every dispatch writes at the shared
    ``pos``; a joining request is a row whose validity window restarts
    (``start[s]`` moves), never a recompile or a cache copy; a row that
    is not generating keeps ``start[s] = C``, an empty window, so that
    the step's attention spans the generating rows' columns only;
  * **dead-column garbage discipline** — the step program does NOT
    mask its cache write per row (the cache is donated for in-place
    column updates; a per-row blend would force XLA into a full-plane
    protective copy every step).  A step therefore writes garbage into
    inactive rows' lanes of the written column(s) — which is safe
    because every such column is DEAD: it lies inside the row's
    pending chunk window ``[act-Pb, act)`` (rewritten by the row's own
    chunks, scheduled after the last garbage write — see
    ``_dispatch_chunks``), below the row's ``start`` (never visible),
    or at ``>= act`` where the row's own active dispatches rewrite it
    before any commit exposes it.  "After" is DISPATCH order
    throughout: the device runs its programs in the order the driver
    thread dispatched them, so a chunk dispatched after a step rewrites
    what that step wrote, whether or not the step's tokens were read;
  * **planned-activation chunk schedule** — a prompt of ``Lp`` tokens
    left-pads into ``n = ceil(Lp/T)`` chunks.  Columns are PER-ROW
    state, so the prompt block is free to end wherever the row starts
    generating: admission at position ``a`` plans the activation at
    ``act = max(Pb, a + n)`` (``Pb = n*T``; the ``Pb`` floor keeps the
    left-padded block at non-negative columns), the chunks write
    ``[act-Pb, act)``.  Chunk ``k`` dispatches in the iteration at
    which ``pos > act - n + k`` — one chunk per iteration over the
    last ``n`` iterations before activation, so a long prompt costs
    ``n`` iterations of everyone's token cadence, not ``Lp``, AND the
    chunk rewrite of each column is dispatched strictly after the last
    decode step that could garbage it (the no-blend invariant above; the
    interval algebra: chunk ``k`` covers ``[act-Pb+kT, act-Pb+(k+1)T)``
    and every step from that iteration on writes columns
    ``>= act-n+k+1``; overlap would need ``n(T-1) < (k+1)(T-1)``,
    i.e. ``k >= n`` — impossible).  The row
    activates exactly when the shared ``pos`` reaches ``act``.
    Speculative strides clamp via ``max_commit`` to land on activation
    boundaries (committing fewer than accepted is always exact), and a
    stride that arrives at ``act`` early just bursts the remaining
    chunks first — chunk writes never depend on ``pos``;
  * **a state without columns is kept, not garbled** — the dead-column
    rule has no meaning for a plane that a feed overwrites in place
    (``cache_spec`` ``columns == 0``).  Two rules say when such a state
    counts, side by side.  *Positional* (a short convolution's last
    inputs, kind ``conv_state``, and the convolution inside a state-space
    layer): the entries stand for the columns just before the block being
    fed and count iff those columns are at or after the row's ``start``.
    *Summed* (a state-space layer's state, kind ``ssm_state``, which
    stands for EVERY earlier column and may be of another dtype than the
    planes beside it, float32): the state handed to a block whose first
    column is ``pos`` counts iff ``pos > start``, else the request begins
    inside the block and the state is zeros; a token before ``start``
    inside the block passes it through unchanged.  Under either a
    previous occupant's leftovers, left padding and a ring restart need
    no reset; and the step hands the model its live rows
    (``cached_forward_takes_rows``), so a row that waits between two of
    its chunks, or is done, keeps what it has
    (``counters["state_rows_held"]``; ``["ssm_rows_updated"]`` counts the
    live rows whose summed state a step updated, ``["chunk_ssm_tokens"]``
    the valid tokens the chunks scanned into one);
  * **bounded ring sessions** — the validity mask compares absolute
    columns, so ``pos`` must stay inside ``[0, C)``: a request admits
    only if ``act + max_new (+ gamma)`` fits, and when the FIFO head
    cannot fit the loop drains and restarts the session at ``pos = 0``
    (amortized cost shrinks with ``C``; documented in the README
    decoding walkthrough).

Host-side, lock-and-condvar concurrency exactly like scheduler.py; the
driver thread owns every device dispatch.  Token-level occupancy
accounting (``decode_slot_occupancy_ratio`` + joined/retired counters,
scheduler.py instruments) feeds Server.signals() and the PR-16
ClusterSignals snapshot.

What the loop measures about itself, always on:

  * **the driver thread's phases** — every moment of an iteration lies
    in exactly one of ``PHASES`` (flat, never nested), each a
    ``profiler.span`` named by ``SPAN_NAMES`` on the device trace's
    clock, its seconds accumulated into ``stats()["phase_s"]``.
    ``activate`` is host bookkeeping and one dispatch of the row write
    for each row that joins; ``chunk_fetch`` is the reading of the
    chunks' counts (a model that hands none back never enters it) and,
    in the speculative loop, of a final chunk's logits; ``restore`` and
    ``publish`` are the prefix cache's block pushes and pulls;
  * **what the prefix cache did** — ``counters["prompt_tokens_admitted",
    "prefix_lookups", "prefix_hits", "prefix_hit_tokens",
    "restore_pushes", "prefix_restored_bytes", "prefix_blocks_published",
    "prefix_blocks_evicted"]``, committed with ``steps``: of the prompt
    tokens admitted, those that came out of cached blocks and were not
    prefilled; the blocks pushed into rows and the bytes they hold (every
    plane of a block: a layer's selector keys beside its latent rows);
  * **how a row was activated** — ``counters["rows_activated"]``, and
    ``["logits_bytes_via_host"]``: the bytes of logits that crossed the
    host boundary for it, either way (0 for a row whose final chunk
    made them; ``V x 4`` up for a session row resumed from its
    snapshot's, and ``V x 4`` down in the speculative loop, whose step
    carries a token for each row and no logits);
  * **a request's life** — ``SlotRequest`` carries the stamps
    ``t_arrival .. t_reply``; once replied, its phases feed
    ``stats()["phases_ms"]``, ``decode_slot_phase_seconds`` and, under
    FLAGS_trace, the children of the request's root span;
  * **every slot-step** — each decode step counts its ``S`` slots as
    emitting, prefilling, drain-blocked or without demand
    (``counters["slot_steps_*"]`` by ``_SLOT_STATES``, summing to
    ``steps x S``), when its tokens are read.  A row the step passed by
    because it had taken the end token emitted nothing: it counts with the
    empty slots, and under ``["slot_steps_retire_lag"]`` beside them;
  * **which side an iteration waited on** — ``["steps_read_ready"]`` of
    ``["steps"]``: the plain steps whose read-back waited less than
    ``READ_READY_S``, i.e. found the tokens already on the host's side:
    the driver's own work outlasted the device's step.  Near 0 where the
    device is the bottleneck, near ``steps`` where the host is
    (``stats()["phase_s"]["step_fetch"]`` has the seconds waited);
  * **the model's own counts** — a step's ride its token read-back; the
    counts of the chunks dispatched BEFORE a step are read behind that
    step's tokens, where they wait for nothing (the device runs its
    programs in order), and committed with that step, like everything
    the driver tallied before it dispatched the step: a chunk that no
    step follows is counted when the loop closes;
  * **the blocks a step's attention reads** — the step program attends
    in column blocks (``cached_attention``): each generating row its
    own, from its ``start`` to the shared frontier (``stats()["step_
    read"]`` ``"per_row"``: one kernel on the TPU), or every row the
    span from the oldest generating row's ``start`` (``"span"``: the
    XLA loops); ``counters["attn_blocks_read"]`` of ``["attn_blocks_
    total"]`` is what a step streamed of the planes' blocks under
    either form, by the same arithmetic on the host.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Any, List, Optional

import numpy as np

from ..framework.enforce import (InvalidArgumentError, OutOfRangeError,
                                 UnavailableError)
from ..nn.functional.attention import decode_block, searched_columns
from ..profiler import span as _span
from ..profiler import tracing as _tracing
from ..profiler.metrics import LatencyWindow
from .scheduler import (SLOT_OCCUPANCY, SLOT_PHASE, SLOT_SESSION_RESETS,
                        SLOT_STEPS, SLOT_TTFT, SLOTS_JOINED, SLOTS_RETIRED)

__all__ = ["SlotLoop", "SlotRequest", "PHASES", "SPAN_NAMES",
           "REQUEST_PHASES"]

_EMPTY, _PREFILL, _GEN = 0, 1, 2

# the driver thread's phases in loop order: the keys of
# ``stats()["phase_s"]``.  ``idle_wait`` (nothing live) and ``step_fetch``
# wait: ``step_fetch`` is the wait for the tokens of the step BEFORE the
# one just dispatched (the plain loop keeps one step in flight; in the
# speculative loop, of the step just dispatched).  ``chunk_fetch`` reads
# the chunks' counts behind a step's tokens, where they have arrived (the
# speculative loop waits there for a final chunk's logits); the others are
# the host's own work, the dispatch of a row's activation write under
# ``activate``.
# ``restore`` (a row's cached blocks pushed into its columns) and
# ``publish`` (an activated row's new blocks pulled for the prefix cache,
# and its bookkeeping) are entered only by a loop that has that cache.
PHASES = ("idle_wait", "admit", "restore", "chunk_dispatch", "chunk_fetch",
          "activate", "publish", "step_dispatch", "step_fetch", "retire")
# their spans in a profiler capture; spelled here and nowhere else
SPAN_NAMES = tuple(f"slot_loop::{p}" for p in PHASES)
_SPAN_OF = dict(zip(PHASES, SPAN_NAMES))
# a replied request's life: five phases that sum to ``total``, and the
# time to its first token from its arrival
REQUEST_PHASES = ("handoff", "admit_wait", "prefill", "decode", "reply_hold",
                  "arrival_ttft", "total")
# what a slot is doing at a decode step
_SLOT_STATES = ("emitting", "prefilling", "drain_blocked", "no_demand")
# a read-back that waits less than this found its step's tokens on the
# host's side already (``steps_read_ready``): a twentieth of the shortest
# device step of any cell of the benchmark, ten times a fetch that waits
# for nothing
READ_READY_S = 0.5e-3


@dataclass
class SlotRequest:
    """One row of slot-loop work: a prompt to continue by ``max_new``
    tokens.  ``future`` resolves to int32 [max_new] generated ids.

    The restore fields are filled by ``SlotLoop.submit`` when a session
    snapshot rides along: ``prompt`` then holds the VIRTUAL prompt (the
    transcript a full re-prefill would run), ``preseed`` the tokens the
    parked turn already emitted (they count against ``max_new`` and are
    replayed into the result), ``planes``/``planes_len`` the host KV
    pytree covering the leading ``planes_len`` transcript tokens, and
    ``resume_logits``/``resume_cur`` the activation payload for the
    no-suffix mid-generation resume (plain / speculative loop).

    The stamps (``time.monotonic``) follow the request from its arrival
    at the Server (``t_arrival``; a bare loop's is ``t_submit``) through
    ``t_submit`` (handed to the loop), ``t_admit`` (a slot), ``t_first``
    (first token), ``t_retire`` (row done) to ``t_reply`` (the client's
    Future resolved; a bare loop's is ``t_retire``).  A Server worker
    that holds the reply back for the row's batch-mates sets
    ``deferred_reply`` and calls ``SlotLoop.replied``."""

    prompt: np.ndarray
    max_new: int
    future: Future = field(default_factory=Future)
    t_submit: float = field(default_factory=time.monotonic)
    t_arrival: Optional[float] = None
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    t_retire: Optional[float] = None
    t_reply: Optional[float] = None
    deferred_reply: bool = False
    trace: Any = None                   # the request's root span, if traced
    session_id: Optional[str] = None
    preseed: List[int] = field(default_factory=list)
    planes: Any = None
    planes_len: int = 0
    resume_logits: Optional[np.ndarray] = None
    resume_cur: Optional[int] = None
    snapshot: Any = None                # original snapshot (re-park on abort)

    def phases(self) -> dict:
        """Seconds of each of ``REQUEST_PHASES`` for a replied request."""
        return {"handoff": self.t_submit - self.t_arrival,
                "admit_wait": self.t_admit - self.t_submit,
                "prefill": self.t_first - self.t_admit,
                "decode": self.t_retire - self.t_first,
                "reply_hold": self.t_reply - self.t_retire,
                "arrival_ttft": self.t_first - self.t_arrival,
                "total": self.t_reply - self.t_arrival}


class _Slot:
    __slots__ = ("state", "req", "chunks", "next_chunk", "act",
                 "start", "emitted", "sent", "row", "_act_logits",
                 "restore", "pin")

    def __init__(self):
        self.state = _EMPTY
        self._act_logits = None         # the final chunk's logits, on the device
        self.req: Optional[SlotRequest] = None
        self.chunks: List[np.ndarray] = []
        self.next_chunk = 0
        self.act = 0                    # planned activation position
        self.start = 0
        self.emitted: List[int] = []
        self.sent = 0                   # ... and those in the step in flight
        self.row = None                 # a leaving session row, pulled
        self.restore: List[tuple] = []  # pending (block_tree, base) pushes
        self.pin = None                 # prefix-cache pin held until pushed


@dataclass
class _InFlight:
    """The plain step the device holds while the driver works: what its
    read-back needs, as it was when the step was dispatched."""

    tok: Any            # device [S (+ n)]: the tokens, then the model's counts
    rows: List[tuple]   # (row, its _Slot) of the rows handed over as generating
    pos: int            # the column it writes
    split: tuple        # its S slots by _SLOT_STATES
    blocked: bool       # ... and whether its empty slots had demand
    held: int           # rows it passed by between two of their chunks
    chunk_counts: list  # handles of the chunks dispatched before it
    tally: dict         # what the driver tallied before it


class SlotLoop:
    """The iteration-level decode loop for one Generator (plain or
    speculative).  ``submit`` enqueues a request and returns a Future;
    a dedicated driver thread admits requests into free slots at token
    boundaries, interleaves prefill chunks, retires finished rows, and
    keeps the occupancy/TTFT accounting honest.  Unit-testable without
    a Server — serving/decode.py wires it behind FLAGS_decode_slots."""

    def __init__(self, gen, slots: int, cache_len: int, chunk: int,
                 eos_token_id: Optional[int] = None,
                 model: str = "decode", prefix_cache=None,
                 session_store=None):
        if slots < 1:
            raise InvalidArgumentError(
                f"slot loop needs >= 1 slot, got {slots}")
        self._gen = gen
        self.S = int(slots)
        self.C = int(cache_len)
        self.T = int(chunk)
        self._eos = eos_token_id
        self._end = -1 if eos_token_id is None else int(eos_token_id)
        self._model = model
        self._spec = getattr(gen, "_draft", None) is not None
        self._gamma = int(gen._gamma) if self._spec else 0
        # what the model's layers say of their planes (Generator.
        # cache_spec): layers that select columns, planes that wrap
        # inside the session; and the counts the model's own forward
        # hands back with the step's tokens and the chunk's logits
        spec = gen.cache_spec(self.C)
        self._plane_kinds = sorted({str(s["kind"]) for s in spec})
        # per layer that reads a plane of its own kind as long as the
        # session (not K/V: those count ``kv_columns_valid``): how many
        # of a token's causal columns its attention reads at most, 0 for
        # all of them (a latent plane with a selector, or without one)
        self._context_tops = [int(s.get("select_top") or 0) for s in spec
                              if int(s["columns"]) and not s.get("wraps")
                              and not str(s["kind"]).startswith("kv")]
        # per layer that selects: the widths its search may take, as the
        # layer hands them out (none: a plane of no more than select_top
        # columns is never searched), and the plane's columns
        self._select_rules = [
            (int(s["select_top"]), tuple(s["select_widths"]),
             int(s["columns"])) for s in spec
            if s.get("select_widths") is not None]
        self._wrap_lens = [int(s["columns"]) for s in spec
                           if s.get("wraps") and int(s["columns"]) < self.C]
        # layers whose cache has no columns: a per-row state that every
        # feed overwrites in place, so a step must leave the rows it does
        # not feed as they are (the model takes the step's live rows)
        self._state_layers = sum(1 for s in spec if not int(s["columns"]))
        # ... those of them whose state sums every earlier token (a
        # state-space layer): each live row of a step updates it, each
        # valid token of a chunk is scanned into it
        self._ssm_layers = sum(1 for s in spec if s["kind"] == "ssm_state")
        # per layer that chooses BLOCKS of its K/V columns from scores
        # over a pooled-key plane: its rule (``select_blocks``)
        self._block_rules = [dict(s["select_blocks"]) for s in spec
                             if s.get("select_blocks")]
        # the kinds of the planes that DO have columns
        column_kinds = sorted({str(s["kind"]) for s in spec
                               if int(s["columns"])})
        # ... all of them a column a token, read whole or in chosen
        # blocks (beside whatever has no columns): K/V planes, and a
        # latent plane without selector or window that lies beside layers
        # of another kind (a model of latent planes alone counts its
        # columns a layer, ``attn_columns_*``, and nothing here)
        mixed = self._plane_kinds != ["latent"]
        self._kv_columns = not self._spec and bool(column_kinds) and all(
            s["kind"] == "kv" or s.get("select_blocks")
            or (mixed and s["kind"] == "latent") for s in spec
            if int(s["columns"]))
        names = getattr(gen, "decode_count_names", None)
        self._count_names = tuple(names()) if names is not None else ()
        if prefix_cache is not None:
            from .prefix_cache import require_kv_planes
            require_kv_planes(spec, self.C)
        if session_store is not None:
            from .sessions import require_kv_planes
            require_kv_planes(self._plane_kinds)
        # compiled once here (ledgered compile or warm cache hit); every
        # later dispatch is a plain __call__ — zero steady-state compiles
        self._step, self._chunk = gen.slot_execs(self.S, self.T, self.C,
                                                 eos_token_id)
        self._kv_heads_per_lane_row = gen.kv_heads_per_lane_row()
        # the form of each program's latent attention (none for a model
        # without latent planes; a speculative step is not one token wide)
        form = getattr(gen, "latent_form", lambda T: None)
        self._latent_form = {} if self._spec or form(1) is None else {
            "latent_form": {"step": form(1), "chunk": form(self.T)}}
        # a row's activation logits go from the chunk's output into the
        # step's input on the device; a speculative step carries tokens,
        # not logits, so it has no plane to write into
        self._put_row = None if self._spec \
            else gen.put_logits_row_exec(self.S)
        self._row_bytes = 4 * gen._vocab_size()
        # the KV reuse plane (prefix cache / session store): its three
        # data movers compile HERE, with the step/chunk programs, so an
        # arbitrary steady-state hit/miss/park/restore mix never
        # compiles — and a loop with both features off compiles nothing
        # extra (off-path = this one branch)
        self._prefix = prefix_cache
        self._sessions = session_store
        self._push_block = self._pull_block = self._pull_row = None
        if prefix_cache is not None or session_store is not None:
            self._push_block = gen.push_block_exec(self.S, self.T, self.C)
        if prefix_cache is not None:
            self._pull_block = gen.pull_block_exec(self.S, self.T, self.C)
        if session_store is not None:
            self._pull_row = gen.pull_row_exec(self.S, self.C)
        self._park_req = None           # guarded-by: _cond  (drain-park handshake)
        self._cond = threading.Condition()
        self._pending: "deque[SlotRequest]" = deque()       # guarded-by: _cond
        self._slots = [_Slot() for _ in range(self.S)]  # driver-thread-owned
        # driver-thread-owned: the plain step whose tokens are not read
        # yet, and the rows that left their slots with their last token in
        # it (a row that reaches max_new leaves when the step is dispatched)
        self._inflight: Optional[_InFlight] = None
        self._leaving: List[_Slot] = []
        self._closed = False                                # guarded-by: _cond
        self._dead: Optional[BaseException] = None          # guarded-by: _cond
        self._thread: Optional[threading.Thread] = None     # guarded-by: _cond
        # device/host loop state (driver-thread-owned after start)
        self._reset_session()
        self.counters = {"joined": 0, "retired": 0, "steps": 0,
                         "chunks": 0, "session_resets": 0,
                         "emitted_tokens": 0, "parked": 0, "restored": 0,
                         "prompt_tokens_admitted": 0, "prefix_lookups": 0,
                         "prefix_hits": 0, "prefix_hit_tokens": 0,
                         "restore_pushes": 0, "prefix_restored_bytes": 0,
                         "prefix_blocks_published": 0,
                         "prefix_blocks_evicted": 0,
                         "rows_activated": 0, "logits_bytes_via_host": 0,
                         **{f"slot_steps_{k}": 0 for k in _SLOT_STATES},
                         **dict.fromkeys(self._count_names, 0)}
        if self._context_tops:
            self.counters.update(attn_columns_valid=0,
                                 attn_columns_selected=0,
                                 chunk_attn_columns_valid=0,
                                 chunk_attn_columns_selected=0)
        if self._context_tops or self._count_names:
            # the chunks' own part of the totals, so that a reader can
            # take the average step and the average chunk apart
            self.counters["chunk_tokens"] = 0
            self.counters.update({
                "chunk_" + k: 0 for k in self._count_names
                if not k.endswith("_max")})
        if self._select_rules:
            self.counters.update(selector_columns_searched=0,
                                 selector_columns_plane=0,
                                 chunk_selector_columns_searched=0,
                                 chunk_selector_columns_plane=0)
        if self._wrap_lens:
            self.counters["window_wraps"] = 0
        if not self._spec:
            self.counters.update(steps_read_ready=0, slot_steps_retire_lag=0)
        if self._state_layers:
            self.counters["state_rows_held"] = 0
        if self._ssm_layers:
            self.counters.update(ssm_rows_updated=0, chunk_ssm_tokens=0)
        if self._block_rules:
            self.counters.update(dict.fromkeys(
                [pre + k for pre in ("", "chunk_") for k in (
                    "sparse_blocks_valid", "sparse_blocks_selected",
                    "pooled_entries_scored")], 0))
        # the plain step over bf16/f32 K/V planes attends in blocks of
        # this many columns (cached_attention), whatever the model keeps
        # beside them that has no columns
        self._attn_block = 0
        self._step_read = {}
        if column_kinds == ["kv"] and not self._spec:
            self._attn_block = decode_block(self.C)
            self.counters.update(attn_blocks_read=0, attn_blocks_total=0)
            # ... each generating row's own blocks in one kernel
            # ("per_row") or the union span in XLA loops ("span"): the
            # form the step program was traced in (Generator.step_read)
            self._step_read = {"step_read": gen.step_read(self.C)}
        if self._kv_columns:
            # the valid columns themselves, of steps and of chunks (what
            # a roofline is counted from)
            self.counters.update(kv_columns_valid=0, chunk_kv_columns_valid=0)
        # driver-thread-owned: what the dispatches since the last commit
        # add to those counters; committed with ``steps`` in one piece
        self._tally = {}
        self._evictions_seen = 0        # of the prefix cache's, tallied so far
        self._chunk_counts = []         # device handles of chunks' counts
        # the driver's phase clock (driver-thread-owned): the phase it is
        # in, since when, its open span, and the seconds not yet
        # committed to _phase_s
        self._ph: Optional[str] = None
        self._ph_t0 = 0.0
        self._ph_span = None
        self._ph_acc = dict.fromkeys(PHASES, 0.0)
        self._blocked = False           # driver-thread-owned: _admit left the head waiting
        self._step_emitted = 0          # driver-thread-owned: tokens of the step under way
        self._phase_s = dict.fromkeys(PHASES, 0.0)          # guarded-by: _cond
        self._phase_win = {k: LatencyWindow()               # guarded-by: _cond
                           for k in REQUEST_PHASES}
        # child instruments resolved once — .labels() is a registry
        # lookup and the step path is hot
        self._m_occ = SLOT_OCCUPANCY.labels(model=self._model)
        self._m_joined = SLOTS_JOINED.labels(model=self._model)
        self._m_retired = SLOTS_RETIRED.labels(model=self._model)
        self._m_ttft = SLOT_TTFT.labels(model=self._model)
        self._m_resets = SLOT_SESSION_RESETS.labels(model=self._model)
        self._m_steps = [SLOT_STEPS.labels(model=self._model, state=k)
                         for k in _SLOT_STATES]
        self._m_phase = {k: SLOT_PHASE.labels(model=self._model, phase=k)
                         for k in REQUEST_PHASES}
        self._occupancy = 0.0               # EWMA of generating/S
        self._ttft: "deque[float]" = deque(maxlen=512)
        if self._spec:
            self._accepted = 0
            self._proposed = 0

    # -- session state -------------------------------------------------------
    def _reset_session(self):
        """Fresh ring session: position 0, zero planes (stale data is
        invisible behind the validity windows, but a cold loop has no
        planes yet), neutral per-row vectors.  ``_finished`` is the
        host's own in the speculative loop; in the plain loop it is what
        the last step handed back, a device array that is never read."""
        self.pos = 0
        self._cache = self._gen.init_slot_cache(self.S, self.C)
        self._start = np.full((self.S,), self.C, np.int32)
        self._finished = np.ones((self.S,), bool)
        self._active = np.zeros((self.S,), bool)
        if getattr(self, "_spec", False):
            self._cur = np.zeros((self.S,), np.int32)
        else:
            vocab = self._gen._vocab_size()
            self._logits = np.zeros((self.S, vocab), np.float32)
            # the rows activated since the last step was dispatched
            self._no_rows = np.zeros((self.S,), bool)
            self._joined = self._no_rows

    def _need(self, prompt_len: int, max_new: int) -> int:
        """Ring columns a request consumes: padded chunk span + its own
        token budget (+ the speculative verify block's overshoot)."""
        n_chunks = -(-int(prompt_len) // self.T)
        return n_chunks * self.T + int(max_new) + self._gamma

    def _min_need(self, req: "SlotRequest") -> int:
        """Minimum ring columns ``req`` can ever consume (admitted at
        ``pos = 0``): the plane-restore path needs only the transcript
        length itself (restored columns are exact, never chunk-padded),
        the plain path the padded chunk span."""
        budget = req.max_new - len(req.preseed)
        if req.planes_len >= self.T:
            return req.prompt.size + budget + self._gamma
        return self._need(req.prompt.size, budget)

    def _prepare_restore(self, req: "SlotRequest", snap) -> None:
        """Fold a session snapshot into the request.  Any mismatch —
        transcript not a prefix of the prompt, wrong loop flavor, wrong
        KV storage dtype, missing or sub-chunk planes — quietly degrades
        (first to plane-less restore, then to a plain submit), which is
        always bit-identical to the full re-prefill; a snapshot can make
        the turn cheaper, never wrong."""
        p = req.prompt
        toks = np.asarray(snap.tokens, np.int32)
        preseed: List[int] = []
        if snap.remaining > 0:
            # mid-generation park (drain): the client redispatched the
            # ORIGINAL request; the transcript extends its prompt by the
            # tokens already emitted — resume, replaying those
            if toks.size < p.size or toks.size != p.size + len(snap.emitted) \
                    or not np.array_equal(toks[:p.size], p):
                return
            if len(snap.emitted) >= req.max_new:
                preseed = list(snap.emitted)[:req.max_new]
            else:
                preseed = list(snap.emitted)
            req.prompt = toks
            req.preseed = preseed
        else:
            # completed turn: the follow-up prompt must extend the
            # transcript (history ++ new turn), leaving a real suffix
            if toks.size >= p.size \
                    or not np.array_equal(p[:toks.size], toks):
                return
        req.snapshot = snap
        planes_ok = (snap.planes is not None
                     and toks.size >= self.T
                     and bool(snap.spec) == self._spec
                     and snap.kv_dtype == self._kv_dtype())
        if not planes_ok:
            return                      # plane-less: plain chunks, bit-exact
        if snap.remaining > 0:
            if self._spec and snap.cur is None:
                return
            if not self._spec and snap.logits is None:
                return
            req.resume_logits = None if snap.logits is None \
                else np.asarray(snap.logits, np.float32).reshape(-1)
            req.resume_cur = None if snap.cur is None else int(snap.cur)
        req.planes = snap.planes
        req.planes_len = int(toks.size)

    def _kv_dtype(self) -> str:
        from ..framework import flags as _flags
        return str(_flags.flag("kv_cache_dtype")).lower()

    # -- producer ------------------------------------------------------------
    def submit(self, prompt, max_new: int, session_id: Optional[str] = None,
               snapshot=None) -> Future:
        return self.enqueue(prompt, max_new, session_id, snapshot).future

    def enqueue(self, prompt, max_new: int,
                session_id: Optional[str] = None, snapshot=None,
                t_arrival: Optional[float] = None,
                trace=None) -> SlotRequest:
        """``submit`` for a caller that replies to its own client later
        (the Server worker): returns the request, stamped with the
        client's ``t_arrival`` and carrying its root span, and leaves
        ``t_reply`` to that caller's ``replied``."""
        p = np.asarray(prompt).reshape(-1).astype(np.int32)
        if p.size == 0:
            raise InvalidArgumentError("empty prompt (0 tokens)")
        mn = int(max_new)
        if mn < 1:
            raise InvalidArgumentError("max_new must be >= 1")
        req = SlotRequest(prompt=p, max_new=mn, session_id=session_id,
                          trace=trace)
        req.deferred_reply = t_arrival is not None
        req.t_arrival = req.t_submit if t_arrival is None else t_arrival
        if snapshot is not None:
            self._prepare_restore(req, snapshot)
        if len(req.preseed) >= mn:
            # the parked turn already emitted the whole budget — resolve
            # without touching a slot (deterministic replay)
            req.future.set_result(
                np.asarray(req.preseed[:mn], np.int32))
            return req
        if self._min_need(req) > self.C:
            raise OutOfRangeError(
                f"prompt of {p.size} tokens + max_new {mn} can never fit "
                f"the slot cache (need {self._min_need(req)} columns, "
                f"C={self.C}, chunk={self.T}, gamma={self._gamma})")
        with self._cond:
            if self._closed:
                raise UnavailableError("slot loop is closed")
            if self._dead is not None:
                raise UnavailableError(
                    f"slot loop died: {self._dead!r}")
            self._pending.append(req)
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._drive, name=f"slot-loop-{self._model}",
                    daemon=True)
                self._thread.start()
            self._cond.notify_all()
        return req

    def replied(self, req: SlotRequest, t_reply: Optional[float] = None):
        """Close a retired request's life at ``t_reply`` (now, if not
        given): its phases go to the windows behind
        ``stats()["phases_ms"]``, to ``decode_slot_phase_seconds`` and,
        if the request is traced, under its root span.  A request whose
        row never retired (failed, parked, resolved from its preseed)
        adds nothing; a second call neither."""
        if req.t_retire is None or req.t_reply is not None:
            return
        req.t_reply = time.monotonic() if t_reply is None else t_reply
        ph = req.phases()
        with self._cond:
            for k, v in ph.items():
                self._phase_win[k].observe(v)
        for k, v in ph.items():
            self._m_phase[k].observe(v)
        if req.trace is not None:
            for name, t0, t1 in (
                    ("slot_queue", req.t_submit, req.t_admit),
                    ("slot_prefill", req.t_admit, req.t_first),
                    ("slot_decode", req.t_first, req.t_retire),
                    ("reply_hold", req.t_retire, req.t_reply)):
                _tracing.child(req.trace, name, t0, t1)

    def close(self):
        """Stop the driver once in-flight work drains (the rows in their
        slots and the step whose tokens are not read yet); pending
        requests not yet admitted fail with UnavailableError."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
            t = self._thread
        if t is not None:
            t.join(timeout=30)

    # -- the driver loop -----------------------------------------------------
    def _phase(self, name: Optional[str]):
        """Driver thread: leave the phase it is in and enter ``name``
        (None: leave only).  One clock reading per boundary; the span
        and the seconds share it, so the phases are flat and cover the
        whole iteration."""
        if name == self._ph:
            return
        now = time.monotonic()
        if self._ph is not None:
            self._ph_acc[self._ph] += now - self._ph_t0
            self._ph_span.end()
        self._ph, self._ph_t0 = name, now
        if name is not None:
            self._ph_span = _span(_SPAN_OF[name])
            self._ph_span.begin()

    def _commit_phases(self):
        """Driver thread, under the lock: hand the seconds gathered since
        the last commit to ``_phase_s`` (what ``stats()`` reads and
        ``reset_stats()`` zeroes)."""
        for k, v in self._ph_acc.items():
            if v:
                self._phase_s[k] += v
                self._ph_acc[k] = 0.0

    def _drive(self):
        died = False
        try:
            while True:
                if all(s.state == _EMPTY for s in self._slots):
                    # nothing to dispatch behind the step in flight (its
                    # rows left by count): read it before the loop idles,
                    # ends, or restarts the ring
                    self._settle()
                self._phase("admit")
                with self._cond:
                    while (not self._pending
                           and all(s.state == _EMPTY
                                   for s in self._slots)):
                        if self._closed:
                            if self._park_req is not None:
                                self._park_req[0].set()
                                self._park_req = None
                            return
                        if self._park_req is not None:
                            # nothing live to park — ack the handshake
                            # so a drain never waits on an idle loop
                            self._park_req[0].set()
                            self._park_req = None
                        self._phase("idle_wait")
                        self._cond.wait(0.05)
                        self._phase("admit")
                        self._commit_phases()
                    if self._closed and not self._any_live():
                        self._fail_pending(UnavailableError(
                            "slot loop closed before this request was "
                            "admitted"))
                        return
                    parking = self._park_req is not None
                if parking:
                    # a row is parked with every token it has: read the
                    # step in flight first, outside the lock (the read
                    # commits and replies under it)
                    self._settle()
                with self._cond:
                    if parking:
                        # (a request that came since waits an iteration)
                        park, self._park_req = self._park_req, None
                        self._do_park(park)
                    self._commit_phases()
                    self._blocked = self._admit()
                self._phase("chunk_dispatch")
                self._dispatch_chunks()
                self._phase("activate")
                self._activate()
                if not any(s.state == _GEN for s in self._slots):
                    self._settle()
                    self._fast_forward()
                    continue
                self._decode_step()
        except BaseException as e:   # noqa: BLE001 — fail rows, not host
            died = True
            with self._cond:
                self._dead = e
                if self._park_req is not None:
                    self._park_req[0].set()
                    self._park_req = None
                # a step in flight is dropped unread: whatever failed,
                # the tokens behind it are no longer in order
                self._inflight = None
                for s in self._leaving + self._slots:
                    if s.req is not None and not s.req.future.done():
                        s.req.future.set_exception(e)
                    s.state, s.req = _EMPTY, None
                self._leaving = []
                self._fail_pending(e)
        finally:
            if not died:
                # chunks that no step followed (their row was parked):
                # their counts belong to the window all the same
                self._read_chunk_counts(self._chunk_counts)
                self._chunk_counts = []
            self._phase(None)
            with self._cond:
                self._commit_tally()
                self._commit_phases()

    def _any_live(self) -> bool:
        return bool(self._pending) or any(s.state != _EMPTY
                                          for s in self._slots)

    def _fail_pending(self, exc):
        while self._pending:
            r = self._pending.popleft()
            if not r.future.done():
                r.future.set_exception(exc)

    # -- admission (FIFO, no starvation) -------------------------------------
    def _plan_act(self, prompt_len: int) -> int:
        """The planned activation position for a prompt admitted NOW:
        one chunk dispatches per loop iteration and the shared ``pos``
        advances at most one token boundary per iteration, so the
        earliest exact meeting point is ``pos + n`` chunks out — floored
        at ``Pb`` so the left-padded block stays at columns >= 0."""
        n_chunks = -(-int(prompt_len) // self.T)
        return max(n_chunks * self.T, self.pos + n_chunks)

    def _plan_act_req(self, req: "SlotRequest") -> int:
        """Planned activation for a request admitted NOW, by mode.  A
        plane-restore row prefills only its uncached suffix (``n_s``
        chunks; zero for a mid-generation resume), floored at the
        transcript length so the restored block stays at columns >= 0
        — restored columns are exact, never chunk-padded."""
        ltot = req.prompt.size
        if req.planes_len >= self.T:
            n_s = self._suffix_chunks(req)
            return max(ltot, self.pos + n_s)
        return self._plan_act(ltot)

    def _suffix_chunks(self, req: "SlotRequest") -> int:
        ls = req.prompt.size - (req.planes_len // self.T) * self.T \
            if req.planes_len < req.prompt.size else 0
        return -(-ls // self.T)

    def _host_block(self, planes, lo, hi):
        import jax.tree_util as tu
        return tu.tree_map(lambda p: p[:, :, lo:hi, :], planes)

    def _admit(self):
        """Move pending FIFO heads into empty slots at the current token
        boundary.  Strict FIFO: if the head does not fit the remaining
        ring columns, nothing behind it jumps the line — the loop drains
        and restarts the session instead.  Returns whether it left the
        head waiting for that drain: the slots then stand empty with
        demand behind them."""
        for slot in self._slots:
            if not self._pending or slot.state != _EMPTY:
                continue
            head = self._pending[0]
            if self._plan_act_req(head) + head.max_new \
                    - len(head.preseed) + self._gamma > self.C:
                if all(s.state == _EMPTY for s in self._slots) \
                        and self.pos > 0:
                    # whole loop idle (so _drive has read the step in
                    # flight): restart the ring session (windows restart,
                    # planes stay — stale columns are invisible)
                    self.pos = 0
                    self.counters["session_resets"] += 1
                    self._m_resets.inc()
                else:
                    return True                  # drain first
            self._pending.popleft()
            self._install(slot, head)
        return False

    def _install(self, slot: "_Slot", head: "SlotRequest"):
        """Stage one admitted request into a slot row: pick the restore
        source (session planes > prefix-cache hit > none), queue the
        restore block pushes, and plan the suffix chunks.  All three
        paths meet the same activation at ``slot.act`` and are
        bit-identical to the plain full prefill of ``head.prompt``."""
        p = head.prompt
        lp = int(p.size)
        slot.restore = []
        slot.pin = None
        self._add("prompt_tokens_admitted", lp)
        if head.planes_len >= self.T:
            # -- session-snapshot restore (host planes) -------------------
            lc = head.planes_len
            m = lc // self.T
            n_s = self._suffix_chunks(head)
            slot.act = max(lp, self.pos + n_s)
            slot.start = slot.act - lp
            for j in range(m):
                slot.restore.append(
                    (self._host_block(head.planes, j * self.T,
                                      (j + 1) * self.T),
                     slot.start + j * self.T))
            if lc % self.T and lc == lp:
                # mid-generation resume: no suffix chunk will recompute
                # the partial tail block — restore it as a T-wide
                # overlap slice ending exactly at the transcript edge
                slot.restore.append(
                    (self._host_block(head.planes, lc - self.T, lc),
                     slot.start + lc - self.T))
            suffix = p[lp - n_s * self.T:] if n_s else p[:0]
            slot.chunks = [suffix[k * self.T:(k + 1) * self.T]
                           for k in range(n_s)]
            self.counters["restored"] += 1
        else:
            blocks, pin = ([], None)
            if self._prefix is not None and lp > self.T:
                # clamp so >= 1 true suffix token remains: the final
                # chunk's last column must be the last prompt token (it
                # produces the activation logits)
                blocks, pin = self._prefix.lookup(
                    p.tolist(), max_blocks=(lp - 1) // self.T)
                self._add("prefix_lookups", 1)
            if blocks:
                # -- prefix-cache hit (device blocks) ---------------------
                lhit = len(blocks) * self.T
                ls = lp - lhit
                n_s = -(-ls // self.T)
                slot.act = max(lp, self.pos + n_s)
                slot.start = slot.act - lp
                slot.restore = [(b, slot.start + j * self.T)
                                for j, b in enumerate(blocks)]
                slot.pin = pin
                # overlap-repeat: the first suffix chunk re-feeds the
                # last n_s*T - ls cached tokens (recomputed K/V is
                # bit-identical, so rewriting restored columns is free)
                suffix = p[lp - n_s * self.T:]
                slot.chunks = [suffix[k * self.T:(k + 1) * self.T]
                               for k in range(n_s)]
                self._add("prefix_hits", 1)
                self._add("prefix_hit_tokens", lhit)
            else:
                if pin:
                    self._prefix.release(pin)
                # -- plain path: full left-padded chunked prefill ---------
                n_chunks = -(-lp // self.T)
                pb = n_chunks * self.T
                padded = np.zeros((pb,), np.int32)
                padded[pb - lp:] = p
                slot.chunks = [padded[k * self.T:(k + 1) * self.T]
                               for k in range(n_chunks)]
                slot.act = self._plan_act(lp)
                slot.start = slot.act - lp
        head.t_admit = time.monotonic()
        slot.req = head
        slot.next_chunk = 0
        slot.emitted = list(head.preseed)
        slot.sent = len(head.preseed)
        slot.state = _PREFILL
        self.counters["joined"] += 1
        self._m_joined.inc()

    # -- chunked prefill -----------------------------------------------------
    def _dispatch_chunks(self):
        """One chunk per prefilling slot per iteration (the Sarathi
        budget: a joining prompt taxes everyone's token cadence by its
        chunk count, not its length), scheduled over the LAST ``n``
        iterations before the row activates: chunk ``k`` dispatches
        once ``pos > act - n + k``.  That late placement is load-
        bearing, not cosmetic — the step program writes unmasked
        garbage into inactive rows' lanes (dead-column discipline, see
        the module docstring), and dispatching chunk ``k`` only after
        the step at ``act - n + k`` has been DISPATCHED (``pos`` moves
        when a step is dispatched, and the device runs its programs in
        that order; whether the step's tokens were read plays no part)
        guarantees the chunk's column block is rewritten strictly after
        the last step that could garbage it.  Chunk writes carry their
        own column base, independent of ``pos`` — a speculative stride
        that lands on an activation boundary early just bursts the
        remaining chunks back-to-back before the row activates (catch-up
        dispatches are safe: running a chunk LATER than planned only
        moves it further from the garbage frontier)."""
        for i, slot in enumerate(self._slots):
            if slot.state != _PREFILL:
                continue
            if slot.restore:
                self._phase("restore")
                self._push_restores(i, slot)
                self._phase("chunk_dispatch")
            if slot.restore:
                # chunks READ restored columns through attention — hold
                # them until every pending push has dispatched.  Never
                # starves: all restore bases are push-eligible by the
                # first chunk's iteration (Ls >= n_s - 1, see _install)
                continue
            n = len(slot.chunks)
            while (slot.next_chunk < n
                   and slot.act - n + slot.next_chunk < self.pos):
                # fresh buffers per dispatch: the CPU runtime may alias
                # a numpy argument zero-copy and read it asynchronously,
                # so a buffer handed to a dispatch is immutable forever
                self._phase("chunk_dispatch")
                ids = slot.chunks[slot.next_chunk].reshape(1, self.T)
                start = np.array([slot.start], np.int32)
                base = slot.act - len(slot.chunks) * self.T \
                    + slot.next_chunk * self.T
                out = self._chunk(
                    *self._gen._state_args(), self._cache, ids, start,
                    np.int32(i), np.int32(base))
                self._cache, logits = out[0], out[1]
                if self._count_names:
                    self._chunk_counts.append(out[2])
                self._tally_columns(base + np.arange(self.T), slot.start,
                                    chunk=True)
                slot.next_chunk += 1
                self.counters["chunks"] += 1
                if slot.next_chunk == len(slot.chunks):
                    # final chunk: its last column is the last prompt
                    # token, so these are the row's activation logits.
                    # They stay the device array they are (holding it
                    # keeps its buffer): nothing is fetched, and the
                    # driver goes on to dispatch behind the chunk
                    slot._act_logits = logits

    def _read_chunk_counts(self, handles):
        """Driver thread: tally the counts of the chunks behind
        ``handles``.  Called where that waits for nothing: behind the
        read-back of the step that was dispatched after them (the device
        runs its programs in order, so each of them has finished), which
        commits them with that step; and when the loop ends."""
        if not handles:
            return
        self._phase("chunk_fetch")
        for h in handles:
            self._tally_counts(np.asarray(h), chunk=True)

    def _tally_columns(self, cols, start, chunk=False):
        """Driver thread: the columns at which a dispatch appends tokens
        of rows whose first valid columns are ``start`` (left padding
        lies below it and counts nothing).  A selecting layer reads
        ``min(context, select_top)`` of a token's ``context = column -
        start + 1`` causal columns: summed over the live rows of the
        steps under ``attn_columns_*``, over the tokens of the chunks
        under ``chunk_attn_columns_*``; a plain K/V layer reads all of
        them (``kv_columns_valid``, once a dispatch, not a layer).  A
        selecting layer's search goes over the narrowest of its widths
        that holds the dispatch's widest context, none where that is no
        more than ``select_top`` (``searched_columns``, the program's own
        rule), of the columns its plane has: ``selector_columns_*``.  A
        write at a column that is a multiple of a wrapping plane's length
        has gone once round it."""
        cols = np.asarray(cols)
        add = self._add
        pre = "chunk_" if chunk else ""
        # (a dispatch with no live row searches nothing)
        span = (int(np.min(start)), int(cols.max())) if cols.size else None
        for top, widths, columns in self._select_rules:
            add(pre + "selector_columns_searched",
                searched_columns(widths, top, *span) if span else 0)
            add(pre + "selector_columns_plane", columns)
        ctx = cols - np.asarray(start) + 1
        cols, ctx = cols[ctx > 0], ctx[ctx > 0]
        if chunk and "chunk_tokens" in self.counters:
            add("chunk_tokens", int(ctx.size))
        if self._ssm_layers:
            add("chunk_ssm_tokens" if chunk else "ssm_rows_updated",
                int(ctx.size))
        if self._kv_columns:
            add(pre + "kv_columns_valid", int(ctx.sum()))
        for rule in self._block_rules:
            # a token past ``dense_len`` scores the pooled windows that
            # lie whole inside its context and reads ``top`` of its
            # context's blocks; any other reads them all and scores none
            blocks = -(-ctx // rule["block"])
            far = ctx > rule["dense_len"]
            add(pre + "sparse_blocks_valid", int(blocks.sum()))
            add(pre + "sparse_blocks_selected", int(np.where(
                far, np.minimum(blocks, rule["top"]), blocks).sum()))
            add(pre + "pooled_entries_scored", int(np.where(
                far, np.maximum((ctx - rule["kernel"]) // rule["stride"] + 1,
                                0), 0).sum()))
        for top in self._context_tops:
            add(pre + "attn_columns_valid", int(ctx.sum()))
            add(pre + "attn_columns_selected",
                int((np.minimum(ctx, top) if top else ctx).sum()))
        for n in self._wrap_lens:
            add("window_wraps", int(((cols > 0) & (cols % n == 0)).sum()))

    def _tally_blocks(self, starts, pos):
        """Driver thread: the column blocks the step at ``pos`` reads
        of each plane, of those the plane has, by the step's own bounds
        (``nn.functional.attention.decode_attention``).  Read
        ``"per_row"``, each row it was handed as generating reads the
        blocks from its own ``start``'s to the block of ``pos`` and the
        other rows none: their sum, of ``slots x blocks``.  Read as a
        ``"span"``, every row reads from the block of the lowest such
        ``start`` to the block of ``pos``: that span, of the plane's
        blocks (the same share of ``slots x blocks``)."""
        block = self._attn_block
        if not block:
            return
        blocks = -(-self.C // block)
        if self._step_read["step_read"] == "per_row":
            self._add("attn_blocks_read",
                      int((pos // block + 1 - starts // block).sum()))
            self._add("attn_blocks_total", self.S * blocks)
            return
        read = pos // block + 1 - int(starts.min()) // block \
            if starts.size else 0
        self._add("attn_blocks_read", read)
        self._add("attn_blocks_total", blocks)

    def _add(self, key, n, chunk=False):
        t = self._tally
        t[key] = t.get(key, 0) + n
        if chunk:
            t["chunk_" + key] = t.get("chunk_" + key, 0) + n

    def _tally_counts(self, values, chunk=False):
        """Driver thread: the model's own counts of one dispatch, by
        ``_count_names``: sums, and the largest of a ``*_max``."""
        t = self._tally
        for k, v in zip(self._count_names, values):
            if k.endswith("_max"):
                t[k] = max(t.get(k, 0), int(v))
            else:
                self._add(k, int(v), chunk)

    def _commit_tally(self):
        """Under the lock, with ``steps``."""
        for k, v in self._tally.items():
            self.counters[k] = max(self.counters[k], v) \
                if k.endswith("_max") else self.counters[k] + v
        self._tally = {}

    def _push_restores(self, i: int, slot: "_Slot"):
        """Dispatch every push-eligible restore block of one row.  A
        block ``[base, base+T)`` is eligible once ``base + T <= pos``:
        every later step writes columns ``>= pos`` (plain and
        speculative alike), so the pushed columns can never be garbaged
        by the dead-column discipline again.  The prefix-cache pin
        releases when the last block is in flight — from then on the
        restored columns live in the row, not the trie."""
        import jax.tree_util as tu
        while slot.restore and slot.restore[0][1] + self.T <= self.pos:
            block, base = slot.restore.pop(0)
            self._cache = self._push_block(
                self._cache, block, np.int32(i), np.int32(base))
            self._add("restore_pushes", 1)
            self._add("prefix_restored_bytes", sum(
                int(p.nbytes) for p in tu.tree_leaves(block)))
        if not slot.restore and slot.pin is not None:
            self._prefix.release(slot.pin)
            slot.pin = None
            self._tally_evictions()

    def _tally_evictions(self):
        """Driver thread: the blocks the prefix cache has evicted since
        the last look (a release and a publish may each evict)."""
        seen = self._prefix.stats()["evictions"]
        self._add("prefix_blocks_evicted", seen - self._evictions_seen)
        self._evictions_seen = seen

    # -- activation ----------------------------------------------------------
    def _activate(self):
        for i, slot in enumerate(self._slots):
            if slot.state != _PREFILL \
                    or slot.next_chunk < len(slot.chunks) \
                    or slot.restore \
                    or self.pos != slot.act:
                continue
            # copy-on-write: these vectors were handed to earlier
            # dispatches, which may alias them zero-copy — mutate a
            # fresh copy, never the buffer a dispatch has seen
            self._start = self._start.copy()
            self._start[i] = slot.start
            self._active = self._active.copy()
            self._active[i] = True
            # a mid-generation resume has no suffix chunk to produce the
            # activation payload: the snapshot carried it (the exact
            # values the pre-park loop held for this row)
            if self._spec:
                self._finished = self._finished.copy()
                self._finished[i] = False
                cur = slot.req.resume_cur
                if slot.chunks:
                    # first committed token = target argmax over the final
                    # chunk's logits (the joint-prefill cur0 computation),
                    # fetched now that the row needs it
                    self._phase("chunk_fetch")
                    cur = np.argmax(np.asarray(slot._act_logits))
                    self._phase("activate")
                    self._add("logits_bytes_via_host", self._row_bytes)
                self._cur = self._cur.copy()
                self._cur[i] = np.int32(cur)
            else:
                # the row's ``finished`` lives on the device: the next
                # step clears it, told by ``joined``
                self._joined = self._joined.copy()
                self._joined[i] = True
                row = slot._act_logits
                if not slot.chunks:
                    row = slot.req.resume_logits    # host [V]: up, once
                    self._add("logits_bytes_via_host", self._row_bytes)
                # written on the device, in place (the plane is donated):
                # the step's logits never come to the host
                self._logits = self._put_row(self._logits, row, np.int32(i))
            slot._act_logits = None
            self._add("rows_activated", 1)
            slot.state = _GEN
            self._publish_prefix(i, slot)

    def _fast_forward(self):
        """No generating rows: the position counter is host state, so
        jump it to the EARLIEST planned activation instead of burning
        empty decode dispatches (never past it — a later row's window
        must still start exactly at its own ``act``)."""
        acts = [s.act for s in self._slots if s.state == _PREFILL]
        if acts:
            self.pos = max(self.pos, min(acts))

    # -- one decode iteration ------------------------------------------------
    def _decode_step(self):
        gen_slots = [i for i, s in enumerate(self._slots)
                     if s.state == _GEN]
        n_prefill = sum(1 for s in self._slots if s.state == _PREFILL)
        n_empty = self.S - len(gen_slots) - n_prefill
        # an empty slot had demand behind it only if _admit, which ran in
        # this same iteration, left the FIFO head waiting for the drain
        split = (len(gen_slots), n_prefill,
                 n_empty if self._blocked else 0,
                 0 if self._blocked else n_empty)
        ratio = len(gen_slots) / self.S
        self._occupancy = ratio if self.counters["steps"] == 0 \
            else 0.9 * self._occupancy + 0.1 * ratio
        self._m_occ.set(round(ratio, 4))
        self._phase("step_dispatch")
        if self._spec:
            self._spec_step(gen_slots, split)
        else:
            self._plain_step(gen_slots, split)

    def _commit_step(self, split):
        """One commit under the lock, so a reset_stats() from another
        thread never splits a step: the four states sum to steps x S.
        Called once the step's tokens are emitted and BEFORE it retires a
        row, so a client that holds its answer finds the step that
        produced it in stats()."""
        with self._cond:
            self.counters["steps"] += 1
            self.counters["emitted_tokens"] += self._step_emitted
            self._commit_tally()
            for k, n in zip(_SLOT_STATES, split):
                self.counters[f"slot_steps_{k}"] += n
        self._step_emitted = 0
        for m, n in zip(self._m_steps, split):
            if n:
                m.inc(n)

    def _plain_step(self, gen_slots, split):
        """Dispatch the step at ``pos``, and only then read the step
        before it: the device holds this one (and the chunks before it)
        while the driver emits, commits and retires (module docstring,
        "One step in flight")."""
        rows = [(i, self._slots[i]) for i in gen_slots]
        try:
            self._cache, self._logits, self._finished, tok = self._step(
                *self._gen._state_args(), self._cache, self._logits,
                self._start, self._finished, self._active, self._joined,
                np.int32(self.pos))
        except BaseException:
            # the step before it ran: its rows get their tokens before
            # this one's are failed
            self._settle()
            raise
        # on its way to the host from the moment it exists: the read, a
        # whole iteration later, waits for nothing
        tok.copy_to_host_async()
        # rows between two of their chunks: their state is what the last
        # chunk left, and this step passes it by
        held = sum(1 for s in self._slots
                   if s.state == _PREFILL and s.next_chunk > 0) \
            if self._state_layers else 0
        before, self._inflight = self._inflight, _InFlight(
            tok, rows, self.pos, split, self._blocked, held,
            self._chunk_counts, self._tally)
        self._chunk_counts, self._tally = [], {}
        self._joined = self._no_rows
        self.pos += 1
        for i, slot in rows:
            # one token a row a step: the host knows without reading it
            # which rows end with this one
            slot.sent += 1
            if slot.sent >= slot.req.max_new:
                self._leave(i, slot)
        if before is not None:
            self._read_step(before)

    def _settle(self):
        """Driver thread: read the step in flight, if there is one (emit,
        commit, retire).  For whatever must see the loop's state whole."""
        flight, self._inflight = self._inflight, None
        if flight is not None:
            self._read_step(flight)

    def _read_step(self, flight: _InFlight):
        self._phase("step_fetch")
        t0 = time.monotonic()
        tok = np.asarray(flight.tok)
        ready = time.monotonic() - t0 < READ_READY_S
        # this step's part of the tally: what the driver gathered before
        # it dispatched the step, and what it reads now; what it has
        # gathered since goes with the next step
        later, self._tally = self._tally, flight.tally
        self._read_chunk_counts(flight.chunk_counts)
        self._phase("retire")
        # a row retired since the dispatch had taken the end token at the
        # step before: this step passed it by, its slot counts as empty
        live = [(i, s) for i, s in flight.rows if s.req is not None]
        lag = len(flight.rows) - len(live)
        self._add("steps_read_ready", int(ready))
        self._add("slot_steps_retire_lag", lag)
        # the model's counts came back behind the S tokens
        self._tally_counts(tok[self.S:])
        self._tally_columns(np.full(len(live), flight.pos),
                            np.array([s.start for _, s in live], np.int64))
        self._tally_blocks(
            np.array([s.start for _, s in flight.rows], np.int64), flight.pos)
        if self._state_layers:
            self._add("state_rows_held", flight.held)
        for i, slot in live:
            self._emit(slot, [int(tok[i])])
        n_gen, n_prefill, n_blocked, n_free = flight.split
        self._commit_step((n_gen - lag, n_prefill,
                           n_blocked + (lag if flight.blocked else 0),
                           n_free + (0 if flight.blocked else lag)))
        self._tally = later
        for i, slot in live:
            if tok[i] == self._end \
                    or len(slot.emitted) >= slot.req.max_new:
                self._retire(i, slot)

    def _leave(self, i, slot):
        """Row ``i`` reaches ``max_new`` with the step just dispatched:
        the next step gets a vacated row and the slot is free for the next
        admission, while the row's record waits in ``_leaving`` for its
        last token.  A session row's snapshot is cut from its columns, so
        those are pulled now, behind the step and before a new occupant's
        pushes and chunks."""
        req = slot.req
        if req.session_id is not None and self._pull_row is not None \
                and req.prompt.size + slot.sent - len(req.preseed) >= self.T:
            slot.row = self._pull_row(self._cache, np.int32(i))
        self._leaving.append(slot)
        self._vacate(i)

    def _retire_done(self, gen_slots):
        for i in gen_slots:
            slot = self._slots[i]
            if self._finished[i] or len(slot.emitted) >= slot.req.max_new:
                self._retire(i, slot)

    def _spec_step(self, gen_slots, split):
        # clamp the stride so the commit lands exactly on the nearest
        # activation boundary — a prefilling row's window must start
        # the moment the frontier reaches its planned position (every
        # remaining PREFILL act is > pos here: rows AT pos activated or
        # burst-chunked in this same iteration)
        boundaries = [s.act - self.pos
                      for s in self._slots if s.state == _PREFILL]
        mc = min([self._gamma + 1] + [b for b in boundaries if b > 0])
        (self._cache, cur, finished, e, ncommit, n) = self._step(
            *self._gen._state_args(), self._cache, self._cur,
            self._start, self._finished, self._active,
            np.int32(self.pos), np.int32(mc))
        self._phase("step_fetch")
        self._cur = np.array(cur)
        self._finished = np.array(finished)
        e = np.asarray(e)
        k = int(ncommit)
        self._read_chunk_counts(self._chunk_counts)
        self._chunk_counts = []
        self._phase("retire")
        self.pos += k
        self._accepted += int(n)
        self._proposed += self._gamma
        for i in gen_slots:
            self._emit(self._slots[i], [int(t) for t in e[i, :k]])
        self._commit_step(split)
        self._retire_done(gen_slots)

    def _emit(self, slot, toks):
        if slot.req.t_first is None:
            slot.req.t_first = time.monotonic()
            if not slot.emitted:
                dt = slot.req.t_first - slot.req.t_submit
                self._ttft.append(dt)
                self._m_ttft.observe(dt)
        take = slot.req.max_new - len(slot.emitted)
        slot.emitted.extend(toks[:take])
        self._step_emitted += min(len(toks), take)

    def _publish_prefix(self, i: int, slot: "_Slot"):
        """Publish the activated row's prompt blocks into the prefix
        trie.  Dedup lives in the trie — the pull dispatches run only
        for blocks not already cached, so a hot shared prefix is pulled
        once and every later activation is pure bookkeeping.  Dispatch
        ordering makes the pulled copy immune to the row's later column
        writes (donation creates fresh buffers; the pull reads the
        pre-donation value)."""
        if self._prefix is None:
            return
        self._phase("publish")
        slot_start = slot.start
        self._add("prefix_blocks_published", self._prefix.publish(
            slot.req.prompt.tolist(),
            lambda j: self._pull_block(self._cache, np.int32(i),
                                       np.int32(slot_start + j * self.T))))
        self._tally_evictions()
        self._phase("activate")

    def _park(self, i: int, slot: "_Slot", remaining: int):
        """Snapshot one session row into the store: one full-width row
        pull, host-sliced to the transcript's validity window (relative
        positions ``[0, Lc)``), plus the resume payload.  Called at
        turn-retire (remaining == 0: the follow-up turn restores instead
        of re-prefilling history) and at drain-park (remaining > 0)."""
        from .sessions import SessionSnapshot
        req = slot.req
        new = slot.emitted[len(req.preseed):]
        tokens = req.prompt.tolist() + [int(t) for t in new]
        lc = len(tokens)
        planes = None
        if self._pull_row is not None and lc >= self.T:
            import jax.tree_util as tu
            # (a row that left by count was pulled when it left)
            row = slot.row if slot.row is not None \
                else self._pull_row(self._cache, np.int32(i))
            planes = tu.tree_map(
                lambda p: np.asarray(p)[:, :, slot.start:slot.start + lc,
                                        :].copy(), row)
        logits = None
        cur = None
        if remaining > 0:
            if self._spec:
                cur = int(self._cur[i])
            else:
                # the whole plane comes down, once a drain (an eager
                # index on the device array would compile, unledgered)
                logits = np.array(np.asarray(self._logits)[i], np.float32)
        self._sessions.put(SessionSnapshot(
            session_id=req.session_id, model=self._model, tokens=tokens,
            remaining=int(remaining), emitted=[int(t) for t in slot.emitted],
            planes=planes, logits=logits, cur=cur,
            kv_dtype=self._kv_dtype(), spec=self._spec))
        self.counters["parked"] += 1

    def _retire(self, i, slot):
        """``slot``, of row ``i``, is done and its tokens are read: park
        its session, resolve its future.  The slot is freed here unless
        the row left it when its last step was dispatched (``_leave``)."""
        req = slot.req
        out = np.full((req.max_new,), self._end, np.int32)
        out[:len(slot.emitted)] = slot.emitted
        if req.session_id is not None and self._sessions is not None:
            # park BEFORE the future resolves and the slot frees: a new
            # admit could reuse this row and overwrite the columns the
            # snapshot needs (its padded block may start below pos)
            self._park(i, slot, remaining=0)
        req.t_retire = time.monotonic()
        if not req.deferred_reply:
            self.replied(req, req.t_retire)
        # eos freeze: every position after finish reads eos, exactly the
        # scanned decode's padding — retiring early never changes bytes
        req.future.set_result(out)
        if self._slots[i] is slot:
            self._vacate(i)
        else:
            self._leaving.remove(slot)
        slot.req = slot.row = None      # done, to a step that still lists it
        self.counters["retired"] += 1
        self._m_retired.inc()

    def _vacate(self, i):
        """Row ``i`` generates no more: its slot is empty (a new record:
        a step in flight may still list the old one), its window too
        (``start = C``, so that the step's attention does not span the
        columns it leaves behind)."""
        self._slots[i] = _Slot()
        # copy-on-write for the same aliasing reason as _activate
        self._start = self._start.copy()
        self._start[i] = self.C
        self._active = self._active.copy()
        self._active[i] = False
        if self._spec:
            # (the plain step reads a row that is not active as finished)
            self._finished = self._finished.copy()
            self._finished[i] = True
            self._cur = self._cur.copy()
            self._cur[i] = 0

    # -- drain-time parking --------------------------------------------------
    def park_sessions(self, timeout: float = 30.0) -> int:
        """Park every session-tagged row and pending request (the
        graceful-drain fast path: a conversation leaves as a snapshot in
        milliseconds instead of decoding to completion).  Generating
        rows snapshot mid-stream (``remaining > 0``) and their futures
        fail with a retryable UnavailableError — the router backs this
        replica off and redispatches the turn, which resumes from the
        snapshot (shared spill dir) or re-prefills (bit-identical
        either way).  Non-session rows keep decoding normally.  Thread-
        safe; the driver thread does the actual device pulls (it owns
        every dispatch).  Returns the number of sessions parked."""
        if self._sessions is None:
            return 0
        evt = threading.Event()
        out = [0]
        with self._cond:
            if self._dead is not None or self._thread is None \
                    or not self._any_live():
                return 0
            self._park_req = (evt, out)
            self._cond.notify_all()
        evt.wait(timeout)
        return out[0]

    def _do_park(self, park):
        """Driver-thread half of :meth:`park_sessions` (called with the
        condition held, between dispatch rounds — no dispatch races;
        ``_drive`` has read the step in flight, so a row is parked with
        every token it has)."""
        evt, out = park
        try:
            exc = UnavailableError(
                "session parked for drain; redispatch to another "
                "replica", retry_after_s=0.05)
            for i, slot in enumerate(self._slots):
                if slot.req is None or slot.req.session_id is None:
                    continue
                if slot.state == _GEN:
                    self._park(i, slot,
                               remaining=slot.req.max_new
                               - len(slot.emitted))
                    out[0] += 1
                elif slot.state == _PREFILL:
                    # nothing committed yet: put the original snapshot
                    # back (if one rode in) and let the redispatched
                    # turn restore or re-prefill from scratch
                    if slot.req.snapshot is not None:
                        self._sessions.put(slot.req.snapshot)
                    if slot.pin is not None:
                        self._prefix.release(slot.pin)
                        slot.pin = None
                    out[0] += 1
                if not slot.req.future.done():
                    slot.req.future.set_exception(exc)
                slot.restore = []
                self._vacate(i)
            keep: "deque[SlotRequest]" = deque()
            while self._pending:
                r = self._pending.popleft()
                if r.session_id is not None:
                    if r.snapshot is not None:
                        self._sessions.put(r.snapshot)
                    if not r.future.done():
                        r.future.set_exception(exc)
                    out[0] += 1
                else:
                    keep.append(r)
            self._pending = keep
        finally:
            evt.set()

    def reset_stats(self):
        """Zero the loop-local accounting (the runtime calls this right
        after its warm-up round-trip so steady-state counters start
        clean — the registry instruments keep their monotonic totals)."""
        with self._cond:
            for k in self.counters:
                self.counters[k] = 0
            self._occupancy = 0.0
            self._ttft.clear()
            self._phase_s = dict.fromkeys(PHASES, 0.0)
            self._phase_win = {k: LatencyWindow() for k in REQUEST_PHASES}
            if self._spec:
                self._accepted = 0
                self._proposed = 0

    # -- observability -------------------------------------------------------
    def signals(self) -> dict:
        """Token-level load snapshot for Server.signals() and the PR-16
        ClusterSignals leg: the occupancy EWMA plus lifetime
        joined/retired counters and queue backlog."""
        with self._cond:
            c = dict(self.counters)
            pending = len(self._pending)
            occ = self._occupancy
        out = {"decode_slot_occupancy_ratio": round(occ, 4),
               "slots_joined_total": c["joined"],
               "slots_retired_total": c["retired"],
               "slot_steps_total": c["steps"],
               "slot_pending": pending}
        if self._sessions is not None:
            out["sessions_parked"] = len(self._sessions)
            out["session_store_bytes"] = self._sessions.nbytes()
        if self._prefix is not None:
            out["prefix_cache_blocks"] = len(self._prefix)
            out["prefix_cache_bytes"] = self._prefix.nbytes()
        return out

    def stats(self) -> dict:
        with self._cond:
            c = dict(self.counters)
            ttft = sorted(self._ttft)
            phase_s = dict(self._phase_s)
            wins = dict(self._phase_win)
        out = {"slots": self.S, "cache": self.C, "chunk": self.T,
               "kv_heads_per_lane_row": self._kv_heads_per_lane_row,
               # how the chunk program reaches its row: written into the
               # full planes in place, or cut out and spliced back
               "chunk_row": self._gen.chunk_row(),
               # the weights the step and the chunk agreed to have relaid
               # (Generator.slot_execs), and those they disagreed on
               **self._gen.weights_layout, **self._latent_form,
               **self._step_read,
               "plane_kinds": list(self._plane_kinds),
               "occupancy_ewma": round(self._occupancy, 4), **c,
               # the driver's seconds by phase, and the phases of the
               # requests replied, both since the last reset_stats()
               "phase_s": phase_s,
               "phases_ms": {k: {"n": w.count,
                                 "p50": 1e3 * w.percentile(50),
                                 "p90": 1e3 * w.percentile(90)}
                             for k, w in wins.items() if w.count}}
        if ttft:
            out["ttft_p50_ms"] = round(
                ttft[len(ttft) // 2] * 1e3, 3)
            out["ttft_p99_ms"] = round(
                ttft[min(len(ttft) - 1,
                         int(len(ttft) * 0.99))] * 1e3, 3)
        if self._spec:
            out["spec_accepted"] = self._accepted
            out["spec_proposed"] = self._proposed
            if self._proposed:
                out["spec_acceptance_rate"] = round(
                    self._accepted / self._proposed, 4)
        return out
