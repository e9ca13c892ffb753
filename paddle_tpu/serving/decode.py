"""Batched autoregressive decode through the serving engine.

The decode workload is the serving engine's hardest shape-discipline
test: every request carries its own prompt length AND runs two phases
(prefill over the prompt, then a scanned per-token decode), so a naive
server compiles per (batch, prompt-length, generation-length) triple —
under real traffic, forever.  The bucketed answer mirrors the dense
path's ladder, squared:

  * request ROWS pack into the batch-bucket ladder exactly like dense
    requests (scheduler.py's continuous batcher is reused unchanged);
  * prompt LENGTHS pad (left) to the FLAGS_decode_buckets sequence
    ladder; the KV-cache length rounds up to the smallest bucket holding
    prompt-bucket + max_new_tokens;
  * warm-up AOT-compiles every (batch-bucket × prefill-bucket) prefill
    executable and every (batch-bucket × cache-bucket) decode executable
    through text.generation.Generator, each ledgered at the model's
    ``serving:<name>`` site — so ``assert_zero_steady_state_recompiles``
    covers mixed prefill/decode traffic with no special casing.

Left-padding makes results batch-invariant: a row's attention window is
``[P - len, pos)`` regardless of which rows share its batch, so a served
greedy decode is bit-identical to a batch-1 ``generate()`` of the same
prompt (the admission test's oracle).

``FLAGS_decode_slots > 0`` swaps the scanned run-to-completion loop for
the iteration-level slot loop (serving/slots.py): ONE single-step
executable per (slot-count, cache-bucket), requests joining and
retiring at token boundaries, prompts chunked ``FLAGS_prefill_chunk``
wide and interleaved into decode steps.  Tokens stay bit-identical to
``generate()``; only the schedule changes.  The flag off (default) is
one Python branch at load — the scanned path is byte-identical to
before.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Any, List, Optional, Sequence

import numpy as np

from ..framework import flags as _flags
from ..framework.enforce import (InvalidArgumentError, OutOfRangeError,
                                 PreconditionNotMetError)
from ..profiler import tracing as _tracing
from ..profiler.metrics import LatencyWindow, RateMeter
from ..utils.monitor import stat_add
from .bucketing import BucketLadder

__all__ = ["DecodeModelSpec", "DecodeRequest"]


@dataclass
class DecodeModelSpec:
    """One served decode model: a LIVE layer implementing the
    init_cache/forward_cached contract (text.models.GPTModel), not a
    frozen export — the decode program (a scanned step over a mutable
    ring cache) is compiled per bucket at warm-up, which is exactly the
    durable artifact the dense path gets from export_for_serving.

    ``draft_layer`` turns the spec into a draft/target PAIR: under
    ``FLAGS_spec_decode`` the runtime serves through speculative
    decoding (text/speculative.py — the draft proposes ``gamma`` tokens
    per step, the target verifies them in one forward; served tokens
    stay bit-identical to plain greedy decode), and the warm-up grid
    AOT-compiles the speculative step per (batch-bucket × cache-bucket)
    so ``assert_zero_steady_state_recompiles`` holds under mixed
    traffic exactly as before.  With the flag off (the default) the
    draft is ignored — one Python branch at load."""

    name: str
    layer: Any
    batch_buckets: Optional[Sequence[int]] = None
    seq_buckets: Optional[Sequence[int]] = None
    max_new_tokens: int = 16
    max_len: Optional[int] = None
    eos_token_id: Optional[int] = None
    draft_layer: Any = None
    gamma: Optional[int] = None
    # sharded replicas (serving/cluster/sharding.py): AOT-compile the
    # grids SPMD over ``mesh`` with params sharded by the autoshard
    # rules table (``rules`` = a PartitionRules / table name; None =
    # the active table).  mesh=None is the single-device path.
    mesh: Any = None
    rules: Any = None


@dataclass
class DecodeRequest:
    """One client decode request: ``rows`` prompts (variable lengths),
    each to be continued by up to ``max_new`` tokens."""

    model: str
    prompts: List[np.ndarray]
    rows: int
    max_new: int
    future: Future = field(default_factory=Future)
    t_enqueue: float = field(default_factory=time.perf_counter)
    # root span (Server.submit_decode) + monotonic enqueue stamp — the
    # same tracing contract as the dense Request
    trace: Optional[object] = None
    t_enqueue_mono: float = field(default_factory=time.monotonic)
    # admission class (scheduler.RequestQueue): same contract as Request
    tenant: str = "default"
    priority: Optional[int] = None
    # conversation identity (FLAGS_session_store): single-prompt requests
    # only — the slot loop parks/restores the KV planes under this key
    session_id: Optional[str] = None
    # slot mode: the SlotRequest of each prompt (execute fills it), whose
    # lives the worker closes when it resolves ``future``
    slot_rows: List[object] = field(default_factory=list)


class _DecodeRuntime:
    """Serving-side runtime for one decode model (the decode analogue of
    server._ModelRuntime): Generator-backed executables, bucket plans,
    metrics, and the strict steady-state discipline."""

    kind = "decode"
    backend = "decode"
    primary = None                      # no Predictor to clone

    def __init__(self, spec: DecodeModelSpec):
        self.spec = spec
        self.name = spec.name
        self.site = f"serving:{spec.name}"
        self.ladder = BucketLadder.from_flag(
            spec.batch_buckets if spec.batch_buckets is not None
            else _flags.flag("serving_buckets"))
        self.steps = int(spec.max_new_tokens)
        self.admitted = False
        self.gen = None
        self.role = "both"              # resolved from the flag at load()
        self._loop = None               # slot mode, resolved at load()
        self.slots = 0
        self._warmed_prefill = set()        # {(B, P, C)}
        self._warmed_decode = set()         # {(B, C)}
        self.latency = LatencyWindow(
            int(_flags.flag("serving_metrics_window")))
        self.rate = RateMeter()
        self._mlock = threading.Lock()
        # injected by the Server before warmup (FLAGS_session_store);
        # the prefix cache is built per-runtime in _warmup_slots
        self.session_store = None
        self.prefix_cache = None
        self.counters = {"requests": 0, "completed": 0,  # guarded-by: _mlock
                         "errors": 0,
                         "batches": 0, "rows": 0, "padded_rows": 0,
                         "steady_compiles": 0}

    def bump(self, **kw):
        with self._mlock:
            for k, v in kw.items():
                self.counters[k] += v

    # -- loading + warm-up ---------------------------------------------------
    def load(self):
        from ..text.generation import Generator
        # pool role (FLAGS_serving_role): a prefill-pool replica warms
        # and serves only the prefill grid, a decode-pool replica only
        # the decode grid (full submit_decode traffic needs "both");
        # resolved at load so one process = one role, like one mesh
        self.role = str(_flags.flag("serving_role")).lower()
        if self.spec.mesh is not None:
            if self.spec.draft_layer is not None \
                    and bool(_flags.flag("spec_decode")):
                raise PreconditionNotMetError(
                    f"decode model {self.name!r}: speculative decoding "
                    "and a sharded mesh cannot combine (the draft runs "
                    "per-replica unsharded) — drop one")
            from .cluster.sharding import serving_shard_specs
            specs = serving_shard_specs(self.spec.layer, self.spec.mesh,
                                        self.spec.rules)
            self.gen = Generator(self.spec.layer, site=self.site,
                                 seq_buckets=self.spec.seq_buckets,
                                 max_len=self.spec.max_len,
                                 mesh=self.spec.mesh, param_specs=specs)
        elif self.spec.draft_layer is not None \
                and bool(_flags.flag("spec_decode")):
            from ..text.speculative import SpeculativeGenerator
            self.gen = SpeculativeGenerator(
                self.spec.layer, self.spec.draft_layer, site=self.site,
                seq_buckets=self.spec.seq_buckets,
                max_len=self.spec.max_len, gamma=self.spec.gamma)
        else:
            self.gen = Generator(self.spec.layer, site=self.site,
                                 seq_buckets=self.spec.seq_buckets,
                                 max_len=self.spec.max_len)
        # every prompt bucket must leave room for max_new_tokens in some
        # cache bucket — refuse at registration time, not under traffic
        self._plan = []
        for p in self.gen.seq_buckets:
            try:
                c = self.gen.cache_bucket(p, self.steps)
            except OutOfRangeError:
                continue                # prompts this long are rejected
            self._plan.append((p, c))
        if not self._plan:
            raise PreconditionNotMetError(
                f"decode model {self.name!r}: no sequence bucket leaves "
                f"room for max_new_tokens={self.steps} under "
                f"max_len={self.gen._max_len}")
        self.max_prompt = max(p for p, _ in self._plan)
        # iteration-level slot mode (FLAGS_decode_slots): one step loop
        # at the LARGEST cache bucket replaces the scanned grid; prompts
        # chunk to FLAGS_prefill_chunk instead of prefill-bucketing
        self._loop = None
        self.slots = int(_flags.flag("decode_slots"))
        self.chunk_width = int(_flags.flag("prefill_chunk"))
        if self.slots:
            if self.spec.mesh is not None:
                raise PreconditionNotMetError(
                    f"decode model {self.name!r}: the slot loop "
                    "(FLAGS_decode_slots) runs per-replica unsharded — "
                    "drop the mesh or set FLAGS_decode_slots=0")
            if self.role != "both":
                raise PreconditionNotMetError(
                    f"decode model {self.name!r}: the slot loop fuses "
                    "chunked prefill into the decode step, so it cannot "
                    f"serve a disaggregated {self.role!r} pool — use "
                    "FLAGS_serving_role=both or FLAGS_decode_slots=0")
            self._slot_cache = max(c for _, c in self._plan)
            gamma = int(getattr(self.gen, "_gamma", 0)) \
                if getattr(self.gen, "_draft", None) is not None else 0
            span = self._slot_cache - self.steps - gamma
            T = self.chunk_width
            # largest admissible prompt: its chunk-padded span plus the
            # full token budget must fit ONE ring session
            self.max_prompt = (span // T) * T
            if self.max_prompt < 1:
                raise PreconditionNotMetError(
                    f"decode model {self.name!r}: slot cache "
                    f"{self._slot_cache} leaves no room for a prompt "
                    f"chunk (chunk={T}, max_new_tokens={self.steps}, "
                    f"gamma={gamma})")

    def lint_gate(self, B, P, C):
        """Graph-lint admission over the prefill program in abstract-eval
        mode (the dense runtimes' gate, FLAGS_graph_lint): ERROR findings
        refuse admission.  The ring-cache dynamic_update_slice writes are
        exactly what the layout pass's KV exemption covers."""
        from .. import analysis
        if not analysis.lint_enabled():
            return
        import jax
        import jax.numpy as jnp
        fn = self.gen._build_prefill(B, P, C)
        try:
            closed = jax.make_jaxpr(fn)(
                *self.gen._state_avals(),
                jax.ShapeDtypeStruct((B, P), jnp.int32),
                jax.ShapeDtypeStruct((B,), jnp.int32))
        except Exception as e:   # noqa: BLE001 — lint must not mask bugs
            import warnings
            warnings.warn(
                f"decode warm-up lint for {self.name!r} b{B} p{P} could "
                f"not abstract-eval the program: {type(e).__name__}: {e}",
                analysis.GraphLintWarning, stacklevel=2)
            return
        ctx = analysis.LintContext(site=self.site, kind="serving",
                                   closed_jaxpr=closed)
        report = analysis.default_pass_manager().run(ctx)
        analysis.emit(report, mode="warn")
        errors = report.by_severity(analysis.Severity.ERROR)
        if errors:
            raise PreconditionNotMetError(
                f"serving refused to admit decode model {self.name!r}: "
                f"graph lint found {len(errors)} ERROR finding(s) at "
                f"(batch={B}, prompt={P}):\n"
                + "\n".join("  " + str(d) for d in errors))

    def lint_gate_slot(self, S, C):
        """Graph-lint admission over the slot STEP program — the slot
        loop's hot path gets the same abstract-eval gate as the scanned
        grid (ERROR findings refuse admission)."""
        from .. import analysis
        if not analysis.lint_enabled():
            return
        import jax
        eos = self.spec.eos_token_id
        end = -1 if eos is None else int(eos)
        fn = self.gen._build_step(S, C, end)
        try:
            closed = jax.make_jaxpr(fn)(*self.gen._state_avals(),
                                        *self.gen.step_avals(S, C))
        except Exception as e:   # noqa: BLE001 — lint must not mask bugs
            import warnings
            warnings.warn(
                f"decode warm-up lint for {self.name!r} slots {S} could "
                f"not abstract-eval the step program: "
                f"{type(e).__name__}: {e}",
                analysis.GraphLintWarning, stacklevel=2)
            return
        ctx = analysis.LintContext(site=self.site, kind="serving",
                                   closed_jaxpr=closed)
        report = analysis.default_pass_manager().run(ctx)
        analysis.emit(report, mode="warn")
        errors = report.by_severity(analysis.Severity.ERROR)
        if errors:
            raise PreconditionNotMetError(
                f"serving refused to admit decode model {self.name!r}: "
                f"graph lint found {len(errors)} ERROR finding(s) in "
                f"the slot step program (slots={S}, cache={C}):\n"
                + "\n".join("  " + str(d) for d in errors))

    def _warmup_slots(self):
        """Slot-mode warm-up: lint-gate + AOT-compile the step and chunk
        executables (persistent cache + ledger, like every grid point),
        build the SlotLoop (which compiles its data movers the same way,
        the activation's row write among them), run one dummy request
        end-to-end so every dispatch path is warm (a chunk, the row
        write, a step), then zero the loop accounting."""
        from .slots import SlotLoop
        S, C, T = self.slots, self._slot_cache, self.chunk_width
        self.lint_gate_slot(S, C)
        eos = self.spec.eos_token_id
        for ex in self.gen.slot_execs(S, T, C, eos):
            self._audit_gate(ex, S, None)
        if bool(_flags.flag("prefix_cache")):
            import jax.tree_util as tu
            from .cluster.handoff import _np_dtype
            from .prefix_cache import PrefixCache, require_kv_planes
            require_kv_planes(self.gen.cache_spec(C), C)
            block_nbytes = sum(
                int(np.prod(tuple(a.shape)))
                * _np_dtype(str(a.dtype)).itemsize
                for a in tu.tree_leaves(self.gen._block_avals(S, T, C)))
            self.prefix_cache = PrefixCache(
                T, block_nbytes,
                hbm_budget_mb=float(_flags.flag("prefix_cache_hbm_mb")))
        self._loop = SlotLoop(self.gen, S, C, T, eos_token_id=eos,
                              model=self.name,
                              prefix_cache=self.prefix_cache,
                              session_store=self.session_store)
        self._loop.submit(np.zeros((1,), np.int32), 1).result(timeout=600)
        self._loop.reset_stats()
        if self.prefix_cache is not None:
            self.prefix_cache.clear()   # drop the warm-up dummy's blocks
        self.admitted = True

    def warmup(self):
        """AOT-compile the (batch-bucket × prefill-bucket) prefill set
        and/or the (batch-bucket × cache-bucket) decode set — the pool
        role decides which (a prefill-pool replica never compiles the
        decode grid and vice versa; "both" compiles everything) — then
        run each warmed phase once on zeros so dispatch paths are warm
        too.  Every compile lands in the ledger at this runtime's site —
        the steady-state mark the server snapshots right after.  Under
        ``spec.mesh`` the grids compile SPMD and each executable is
        HLO-audited at admission (cluster/sharding.py)."""
        import jax
        if self._loop is not None or self.slots:
            self._warmup_slots()
            return
        eos = self.spec.eos_token_id
        warm_prefill = self.role in ("both", "prefill")
        warm_decode = self.role in ("both", "decode")
        for B in self.ladder:
            linted = set()
            for P, C in self._plan:
                if warm_prefill:
                    if P not in linted:
                        self.lint_gate(B, P, C)
                        linted.add(P)
                    ex = self.gen.prefill_exec(B, P, C)
                    self._audit_gate(ex, B, P)
                    self._warmed_prefill.add((B, P, C))
                if warm_decode and (B, C) not in self._warmed_decode:
                    ex = self.gen.decode_exec(B, C, self.steps, 1, eos)
                    self._audit_gate(ex, B, None)
                    self._warmed_decode.add((B, C))
            # one zeros round-trip per batch bucket: warm dispatch/runtime
            # for exactly the phases this pool owns
            P0, C0 = self._plan[0]
            ids = np.zeros((B, P0), np.int32)
            start = np.full((B,), P0 - 1, np.int32)
            if warm_prefill:
                cache, logits0 = self.gen.prefill(ids, start, C0)
                if warm_decode:
                    toks = self.gen.decode(cache, logits0, start, P0,
                                           self.steps, 1, eos)
                    jax.block_until_ready(toks)
                else:
                    jax.block_until_ready(logits0)
            else:
                cache = self._zero_cache(B, C0)
                logits0 = np.zeros((B, self.gen._vocab_size()),
                                   np.float32)
                toks = self.gen.decode(cache, logits0, start, P0,
                                       self.steps, 1, eos)
                jax.block_until_ready(toks)
        self.admitted = True

    def _audit_gate(self, compiled, B, P):
        """Admission HLO audit of one warmed grid executable (sharded
        replicas only; FLAGS_hlo_audit-gated — off-path = one branch)."""
        if self.spec.mesh is None:
            return
        from .cluster.sharding import shard_admission_audit
        shard_admission_audit(
            compiled, site=self.site, mesh=self.spec.mesh,
            param_specs=self.gen._param_specs,
            mesh_label=self.gen._mesh_label())

    def _zero_cache(self, B, C):
        """An all-zeros ring cache at the warmed layout — the decode-only
        pool's warm-dispatch stand-in for a prefill it will never run."""
        import jax
        from .cluster.handoff import _np_dtype
        shapes = jax.eval_shape(lambda: self.gen._init_cache_raw(B, C))
        out = []
        for c in shapes:
            planes = []
            for p in c:
                z = np.zeros(tuple(p.shape), _np_dtype(str(p.dtype)))
                planes.append(jax.device_put(
                    z, self.gen.kv_plane_sharding(tuple(p.shape))))
            out.append(tuple(planes))
        return out

    # -- traffic -------------------------------------------------------------
    def validate(self, prompts, max_new):
        if not prompts:
            raise InvalidArgumentError("empty decode request (0 prompts)")
        out = []
        for i, p in enumerate(prompts):
            a = np.asarray(p)
            if a.ndim != 1 or a.size == 0 \
                    or not np.issubdtype(a.dtype, np.integer):
                raise InvalidArgumentError(
                    f"decode prompt {i} must be a non-empty 1-D int "
                    f"array, got shape {a.shape} dtype {a.dtype}")
            if a.size > self.max_prompt:
                raise OutOfRangeError(
                    f"decode prompt {i} has {a.size} tokens; the largest "
                    f"admissible prompt bucket is {self.max_prompt} "
                    f"(max_new_tokens={self.steps}, ladder "
                    f"{self.gen.seq_buckets})")
            out.append(a.astype(np.int32))
        mn = self.steps if max_new is None else int(max_new)
        if mn < 1 or mn > self.steps:
            raise InvalidArgumentError(
                f"max_new_tokens must be in [1, {self.steps}] "
                f"(the engine's warmed decode length), got {mn}")
        return out, mn

    def execute(self, batch):
        """Run one packed batch through prefill + scanned decode; returns
        generated tokens [bucket, steps] (padding rows included — the
        worker slices per request).  In slot mode the rows go through
        the iteration-level loop instead: each row is its own slot
        tenancy (joins at a token boundary, retires when done), and the
        worker-facing [bucket, steps] contract is assembled from the
        per-row futures — workers and the scheduler don't change."""
        if self._loop is not None:
            futs = []
            for r in batch.requests:
                rows = r.slot_rows = []
                stamp = dict(t_arrival=r.t_enqueue_mono, trace=r.trace)
                sid = getattr(r, "session_id", None)
                snap = None
                if sid is not None and self.session_store is not None:
                    snap = self.session_store.take(sid)
                    if snap is not None and snap.model != self.name:
                        # a stale key collision across models: put the
                        # snapshot back untouched and prefill plainly
                        self.session_store.put(snap)
                        snap = None
                for p in r.prompts:
                    try:
                        rows.append(self._loop.enqueue(
                            p, r.max_new, session_id=sid, snapshot=snap,
                            **stamp))
                    except (InvalidArgumentError, OutOfRangeError):
                        # a malformed snapshot must not fail the turn —
                        # fall back to the plain (bit-identical) prefill
                        rows.append(self._loop.enqueue(
                            p, r.max_new, session_id=sid, **stamp))
                    snap = None             # one snapshot, one restore
                futs += [row.future for row in rows]
            out = np.zeros((batch.bucket, self.steps), np.int32)
            row = 0
            for r in batch.requests:
                err = None
                for _ in range(len(r.prompts)):
                    try:
                        got = futs[row].result(timeout=600)
                        out[row, :got.size] = got
                    except Exception as e:   # noqa: BLE001 — per-request
                        err = e              # isolation: a parked row's
                    row += 1                 # Unavailable must not fail
                if err is not None:          # its batch-mates
                    if not r.future.done():
                        r.future.set_exception(err)
            return out
        prompts = [p for r in batch.requests for p in r.prompts]
        # pad rows up to the batch bucket with 1-token dummy prompts
        prompts += [np.zeros((1,), np.int32)] * (batch.bucket - batch.rows)
        P = self.gen.prefill_bucket(max(p.size for p in prompts))
        C = self.gen.cache_bucket(P, self.steps)
        B = batch.bucket
        key_missing = ((B, P, C) not in self._warmed_prefill
                       or (B, C) not in self._warmed_decode)
        if key_missing:
            if bool(_flags.flag("serving_strict")):
                raise PreconditionNotMetError(
                    f"decode model {self.name!r}: (batch={B}, prompt="
                    f"{P}, cache={C}) has no warm-up executable "
                    "(FLAGS_serving_strict=True refuses steady-state "
                    "compiles — extend the ladders and re-warm)")
            # escape hatch: Generator ledgers the compile at this site,
            # so the zero-recompile invariant visibly fails
            stat_add("serving_steady_compiles")
            self.bump(steady_compiles=1)
        ids, start = self.gen.pack_prompts(prompts, P)
        traced = [r for r in batch.requests
                  if getattr(r, "trace", None) is not None]
        if not traced:                     # off-path: one branch, no fence
            cache, logits0 = self.gen.prefill(ids, start, C)
            toks = self.gen.decode(cache, logits0, start, P, self.steps,
                                   1, self.spec.eos_token_id)
            out = np.asarray(toks)
        else:
            import jax
            t_p0 = time.monotonic()
            cache, logits0 = self.gen.prefill(ids, start, C)
            # fence so the prefill/decode split is honest device time
            # (only traced batches pay this extra sync point)
            jax.block_until_ready(logits0)
            t_p1 = time.monotonic()
            toks = self.gen.decode(cache, logits0, start, P, self.steps,
                                   1, self.spec.eos_token_id)
            out = np.asarray(toks)         # fences the scanned token loop
            t_d1 = time.monotonic()
            dt = (t_d1 - t_p1) / self.steps
            spec = getattr(self.gen, "last_stats", None)
            for r in traced:
                _tracing.child(r.trace, "prefill", t_p0, t_p1,
                               prompt_bucket=P, cache_bucket=C, batch=B)
                d = _tracing.start_span("decode", parent=r.trace,
                                        t0=t_p1, steps=self.steps,
                                        cache_bucket=C, batch=B,
                                        per_token_ms=round(dt * 1e3, 4))
                if d is not None:
                    if spec:
                        # speculative runtime: estimated draft/verify
                        # children (the scan is one device program; the
                        # parameter-byte ratio splits the window) plus
                        # the measured acceptance stats
                        tm = t_p1 + (t_d1 - t_p1) * spec["draft_fraction"]
                        _tracing.child(d, "draft", t_p1, tm,
                                       estimated=True,
                                       gamma=spec["gamma"],
                                       proposed=spec["proposed"],
                                       spec_steps=spec["spec_steps"])
                        _tracing.child(d, "verify", tm, t_d1,
                                       estimated=True,
                                       accepted=spec["accepted"],
                                       acceptance_rate=spec[
                                           "acceptance_rate"])
                        d.set_attr(gamma=spec["gamma"], acceptance_rate=
                                   spec["acceptance_rate"])
                    # no per-token events: the whole token loop is ONE
                    # jitted lax.scan, the host never observes token k
                    # alone; ``per_token_ms`` is what was measured
                    _tracing.finish(d, end=t_d1)
        if key_missing:
            self._warmed_prefill.add((B, P, C))
            self._warmed_decode.add((B, C))
        return out

    # -- disaggregated pools: explicit prefill → handoff → decode ------------
    def _steady_guard(self, warmed, key, what):
        if key in warmed:
            return False
        if bool(_flags.flag("serving_strict")):
            raise PreconditionNotMetError(
                f"decode model {self.name!r}: {what} {key} has no "
                "warm-up executable (FLAGS_serving_strict=True refuses "
                "steady-state compiles — extend the ladders and re-warm)")
        stat_add("serving_steady_compiles")
        self.bump(steady_compiles=1)
        return True

    def prefill_handoff(self, prompts, max_new_tokens=None):
        """Run ONLY the prefill phase over ``prompts`` and return the
        :class:`~.cluster.handoff.KVHandoff` a decode pool resumes from:
        device-resident ring planes (bf16 or int8+scales), next-token
        logits, per-row validity offsets and the cache_position.  The
        prefill-pool entry point (roles "both"/"prefill")."""
        if self._loop is not None:
            raise PreconditionNotMetError(
                f"decode model {self.name!r}: disaggregated KV handoff "
                "rides the scanned run-to-completion path — set "
                "FLAGS_decode_slots=0 to serve a prefill pool")
        if self.role == "decode":
            raise PreconditionNotMetError(
                f"decode model {self.name!r}: this replica is in the "
                "decode pool (FLAGS_serving_role=decode) — prefill "
                "belongs to the prefill pool")
        from .cluster.handoff import KVHandoff, require_kv_planes
        require_kv_planes(self.gen.plane_kinds())
        arrs, mn = self.validate(list(prompts), max_new_tokens)
        rows = len(arrs)
        B = self.ladder.bucket_for(rows)
        padded = arrs + [np.zeros((1,), np.int32)] * (B - rows)
        P = self.gen.prefill_bucket(max(p.size for p in padded))
        C = self.gen.cache_bucket(P, self.steps)
        missed = self._steady_guard(self._warmed_prefill, (B, P, C),
                                    "prefill grid point")
        ids, start = self.gen.pack_prompts(padded, P)
        t0 = time.monotonic()
        cache, logits0 = self.gen.prefill(ids, start, C)
        h = KVHandoff(cache=cache, logits0=logits0,
                      start=np.asarray(start, np.int32), pos=P,
                      meta={"model": self.name, "rows": rows,
                            "max_new": mn, "batch": B,
                            "prompt_bucket": P, "cache_bucket": C,
                            "prefill_s": round(time.monotonic() - t0, 6)})
        if missed:
            self._warmed_prefill.add((B, P, C))
        return h

    def decode_from_handoff(self, handoff):
        """Resume a decode from a prefill pool's handoff: ingest the
        planes (device pass-through when already resident, device_put at
        the pinned KV layout when they arrived serialized), then run the
        scanned decode executable from the carried ``cache_position`` /
        validity window.  Returns generated ids [rows, max_new] — bit-
        identical to the same prompts run through the in-process
        ``generate()`` (the acceptance oracle).  The decode-pool entry
        point (roles "both"/"decode")."""
        if self._loop is not None:
            raise PreconditionNotMetError(
                f"decode model {self.name!r}: disaggregated KV handoff "
                "rides the scanned run-to-completion path — set "
                "FLAGS_decode_slots=0 to serve a decode pool")
        if self.role == "prefill":
            raise PreconditionNotMetError(
                f"decode model {self.name!r}: this replica is in the "
                "prefill pool (FLAGS_serving_role=prefill) — decode "
                "belongs to the decode pool")
        from .cluster.handoff import require_kv_planes
        require_kv_planes(self.gen.plane_kinds())
        cache = handoff.cache
        if not cache:
            raise InvalidArgumentError("empty KV handoff (no planes)")
        if isinstance(cache[0][0], np.ndarray):
            handoff = handoff.device(self.gen.kv_plane_sharding)
            cache = handoff.cache
        B = int(np.shape(handoff.logits0)[0])
        C = int(np.shape(cache[0][0])[2])
        missed = self._steady_guard(self._warmed_decode, (B, C),
                                    "decode grid point")
        toks = self.gen.decode(cache, handoff.logits0, handoff.start,
                               int(handoff.pos), self.steps, 1,
                               self.spec.eos_token_id)
        out = np.asarray(toks)
        if missed:
            self._warmed_decode.add((B, C))
        rows = int(handoff.meta.get("rows", B))
        mn = int(handoff.meta.get("max_new", self.steps))
        return out[:rows, :mn]

    def replied(self, r, t_reply):
        """The worker resolved ``r``'s Future at ``t_reply``: close the
        lives of its slot rows (nothing on the scanned path)."""
        for row in r.slot_rows:
            self._loop.replied(row, t_reply)

    def slot_signals(self):
        """Token-level slot accounting for Server.signals(), or None on
        the scanned path (the ClusterSignals leg is additive)."""
        return None if self._loop is None else self._loop.signals()

    def close(self):
        if self._loop is not None:
            self._loop.close()

    def publish(self):
        self.latency.publish(f"serving_{self.name}")
        self.rate.publish(f"serving_{self.name}")
