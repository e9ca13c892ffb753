"""In-process multi-tenant Predictor server.

Reference parity: the deployment story of the reference stack is
``AnalysisPredictor`` + ``Clone()`` fan-out (analysis_predictor.h:82,214)
behind an application-owned server.  The TPU production shape adds what a
CPU/GPU server never had to think about: batch shape IS compile shape, so
the server owns batching — a request queue feeding an Orca-style
continuous batcher into a fixed bucket ladder, AOT warm-up of every
(model, bucket) executable before traffic is admitted, and a steady-state
zero-recompile invariant proven through the recompile ledger.

Layering:

  * :class:`ModelSpec` / :func:`export_for_serving` — the deploy
    artifact contract (shape-polymorphic export when the model allows,
    per-bucket sibling exports when it does not, ``.serving.json``
    manifest either way);
  * :class:`_ModelRuntime` — one served model: predictor(s), bucket
    ladder, per-bucket AOT executables, lint-gated admission, metrics;
  * :class:`_Worker` — serving thread with its own ``Predictor.clone()``
    (shared weights/executables, per-clone IO buffers) and an in-flight
    pipeline: H2D + dispatch of batch N+1 overlap execution of batch N;
  * :class:`Server` — registry + scheduler + workers + stats.

Everything is gated by ``FLAGS_serving_*``; the graph-lint admission gate
rides ``FLAGS_graph_lint`` (off-path = one branch, PR-5 discipline).
"""
from __future__ import annotations

import json
import os
import queue
import threading
import time
from collections import deque
from concurrent.futures import Future
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..framework import flags as _flags
from ..framework.enforce import (InvalidArgumentError, NotFoundError,
                                 PreconditionNotMetError, UnavailableError)
from ..profiler import ledger as _ledger
from ..profiler import tracing as _tracing
from ..profiler.metrics import LatencyWindow, RateMeter
from ..utils.monitor import stat_add


def _trace_batch(batch, name, t0, t1, **attrs):
    """Emit one ``name`` child span [t0, t1] onto every traced request of
    a batch (batch phases are shared work: each request's waterfall shows
    the phase it rode).  One branch per request when tracing is off."""
    for r in batch.requests:
        if r.trace is not None:
            _tracing.child(r.trace, name, t0, t1, **attrs)


def _first_trace(batch):
    """The batch's ambient span target: the first traced request (ledger
    compile events attach there while the batch executes)."""
    for r in batch.requests:
        if r.trace is not None:
            return r.trace
    return None
from .bucketing import BucketLadder, pad_to_bucket
from .decode import DecodeModelSpec, DecodeRequest, _DecodeRuntime
from .scheduler import Batch, Request, RequestQueue


# ---------------------------------------------------------------------------
# Deploy artifact contract
# ---------------------------------------------------------------------------

@dataclass
class ModelSpec:
    """One served model: a saved artifact + its serving shape contract.

    ``path`` is a jit.save prefix (``m`` for ``m.pdmodel``), a model dir,
    or a static save_inference_model dir.  ``buckets`` defaults to
    FLAGS_serving_buckets; ``input_specs`` (``[(shape, dtype), ...]`` with
    None leading dim) is required for executor-backed models whose feeds
    carry no shape metadata.
    """

    name: str
    path: str
    buckets: Optional[Sequence[int]] = None
    input_specs: Optional[Sequence[Tuple[Sequence[Optional[int]], Any]]] = None
    optim_cache_dir: Optional[str] = None


def _manifest_path(prefix: str) -> str:
    return prefix + ".serving.json"


def export_for_serving(layer, prefix: str, input_spec, buckets=None,
                       int8: bool = False) -> dict:
    """Export ``layer`` for the serving engine and write the
    ``<prefix>.serving.json`` manifest the registry discovers.

    Tries a shape-polymorphic export first (batch dim symbolic — ONE
    artifact serves every bucket); models that defeat shape polymorphism
    (e.g. an attention mask compare) fall back to one sibling export per
    bucket (``<prefix>.b<k>``), which is exactly the bucket ladder made
    durable.  With ``int8`` the artifacts are frozen int8 exports
    (quantization.save_int8_model) and the Predictor's
    FLAGS_use_int8_inference path picks them up unchanged.
    """
    from ..static import InputSpec

    ladder = BucketLadder.from_flag(buckets)

    def norm(spec):
        if isinstance(spec, InputSpec):
            return list(spec.shape), spec.dtype
        shape, dtype = spec
        return list(shape), dtype

    rests = [(list(shape[1:]), dtype) for shape, dtype in map(norm, input_spec)]

    def save(pfx, lead):
        spec = [InputSpec([lead] + rest, dtype=dtype)
                for rest, dtype in rests]
        if int8:
            from ..quantization import save_int8_model
            save_int8_model(layer, pfx, input_spec=spec)
        else:
            from .. import jit as _jit
            _jit.save(layer, pfx, input_spec=spec)

    def verify(pfx, bucket):
        # abstract lowering only (no backend compile): catches call-time
        # shape-refinement failures that a clean export can still hide
        import jax
        from .. import jit as _jit
        tl = _jit.load(pfx + (".int8" if int8 else ""))
        avals = [jax.ShapeDtypeStruct((bucket,) + tuple(r), np.dtype(d))
                 for r, d in rests]
        pavals = [jax.ShapeDtypeStruct(p.shape, p.dtype) for p in tl._params]

        def call(*args):
            return tl._exported.call(*args)

        jax.jit(call).lower(*avals, *pavals)

    mode = "poly"
    try:
        save(prefix, None)
        verify(prefix, ladder.buckets[0])
    except Exception:
        mode = "per_bucket"
        for b in ladder:
            save(f"{prefix}.b{b}", b)
    manifest = {"mode": mode, "buckets": ladder.buckets, "int8": bool(int8),
                "input_specs": [[[None] + rest, str(np.dtype(dtype))]
                                for rest, dtype in rests]}
    with open(_manifest_path(prefix), "w") as f:
        json.dump(manifest, f)
    return manifest


# ---------------------------------------------------------------------------
# One served model
# ---------------------------------------------------------------------------

class _BucketExec:
    """AOT-compiled executable for one (model, bucket): positional device
    inputs + the model's device-resident params as explicit trailing args
    (explicit so every bucket shares ONE set of param buffers instead of
    baking per-bucket constant copies)."""

    __slots__ = ("compiled", "params_dev", "n_inputs")

    def __init__(self, compiled, params_dev, n_inputs):
        self.compiled = compiled
        self.params_dev = params_dev
        self.n_inputs = n_inputs

    def __call__(self, dev_inputs):
        return self.compiled(*dev_inputs, *self.params_dev)


class _ModelRuntime:
    """Loaded model + bucket executables + serving metrics."""

    def __init__(self, spec: ModelSpec):
        self.spec = spec
        self.name = spec.name
        self.site = f"serving:{spec.name}"
        self.ladder = BucketLadder.from_flag(spec.buckets)
        self.backend = None            # "jit" | "jit_per_bucket" | "executor"
        self.primary = None            # clone() target for workers
        self.predictors: Dict[int, Any] = {}   # per-bucket (per_bucket mode)
        self.executables: Dict[int, Optional[_BucketExec]] = {}
        self.templates: List[Tuple[Tuple[int, ...], Any]] = []  # (rest, dtype)
        self.n_outputs = 0
        self.admitted = False
        self.latency = LatencyWindow(int(_flags.flag("serving_metrics_window")))
        self.rate = RateMeter()
        self._mlock = threading.Lock()
        self.counters = {"requests": 0, "completed": 0,  # guarded-by: _mlock
                         "errors": 0,
                         "batches": 0, "rows": 0, "padded_rows": 0,
                         "steady_compiles": 0}

    def bump(self, **kw):
        with self._mlock:
            for k, v in kw.items():
                self.counters[k] += v

    # -- loading -------------------------------------------------------------
    def load(self):
        from ..inference import Config, Predictor

        def make_predictor(path):
            cfg = Config(path)
            if self.spec.optim_cache_dir:
                cfg.set_optim_cache_dir(self.spec.optim_cache_dir)
            return Predictor(cfg)

        manifest = None
        mpath = _manifest_path(self.spec.path)
        if os.path.isfile(mpath):
            with open(mpath) as f:
                manifest = json.load(f)
        if manifest is not None and manifest.get("mode") == "per_bucket":
            self.backend = "jit_per_bucket"
            buckets = [b for b in manifest["buckets"] if b in self.ladder]
            if not buckets:
                raise PreconditionNotMetError(
                    f"serving model {self.name!r}: per-bucket export "
                    f"{manifest['buckets']} shares no bucket with the "
                    f"requested ladder {self.ladder.buckets}")
            self.ladder = BucketLadder(buckets)
            for b in self.ladder:
                self.predictors[b] = make_predictor(f"{self.spec.path}.b{b}")
            self.primary = self.predictors[self.ladder.buckets[0]]
            self._init_templates_from_manifest(manifest)
        else:
            self.primary = make_predictor(self.spec.path)
            if self.primary._translated is not None:
                self.backend = "jit"
                self._init_templates_from_avals()
            else:
                self.backend = "executor"
                self._init_templates_from_spec(manifest)
        self.n_inputs = len(self.templates)

    def _init_templates_from_avals(self):
        tl = self.primary._translated
        avals = tl._exported.in_avals[:tl.num_inputs]
        fixed_batch = None
        for i, av in enumerate(avals):
            lead, rest = av.shape[0], av.shape[1:]
            if any(not isinstance(d, (int, np.integer)) for d in rest):
                raise PreconditionNotMetError(
                    f"serving model {self.name!r}: input {i} has a "
                    f"non-leading symbolic dim {av.shape} — only the "
                    "batch dim may be dynamic under bucketed serving")
            if isinstance(lead, (int, np.integer)):
                fixed_batch = int(lead)
            self.templates.append((tuple(int(d) for d in rest),
                                   np.dtype(av.dtype)))
        if fixed_batch is not None:
            # fixed-batch export with no per-bucket siblings: the ladder
            # collapses to the one batch the artifact can run
            self.ladder = BucketLadder([fixed_batch])

    def _init_templates_from_manifest(self, manifest):
        for shape, dtype in manifest["input_specs"]:
            self.templates.append((tuple(int(d) for d in shape[1:]),
                                   np.dtype(dtype)))

    def _init_templates_from_spec(self, manifest):
        specs = self.spec.input_specs
        if specs is None and manifest is not None:
            specs = [(s, d) for s, d in manifest.get("input_specs", [])]
        if specs is None:
            raise PreconditionNotMetError(
                f"serving model {self.name!r} is executor-backed (static "
                "save_inference_model dir): register it with "
                "ModelSpec(input_specs=[(shape, dtype), ...]) — feeds "
                "carry no shape metadata to bucket on")
        from ..static import InputSpec
        for s in specs:
            if isinstance(s, InputSpec):
                shape, dtype = list(s.shape), s.dtype
            else:
                shape, dtype = list(s[0]), s[1]
            self.templates.append((tuple(int(d) for d in shape[1:]),
                                   np.dtype(dtype)))
        if len(self.templates) != len(self.primary._feed_names):
            raise InvalidArgumentError(
                f"serving model {self.name!r}: {len(self.templates)} "
                f"input_specs for {len(self.primary._feed_names)} feeds "
                f"({self.primary._feed_names})")

    # -- abstract view (lint + AOT avals) ------------------------------------
    def _avals(self, bucket):
        import jax
        return [jax.ShapeDtypeStruct((bucket,) + rest, dt)
                for rest, dt in self.templates]

    def _abstract_callable(self, bucket):
        """(fn, avals) such that ``fn(*avals_like)`` is the served
        program at ``bucket`` — the lint and AOT-compile surface."""
        avals = self._avals(bucket)
        if self.backend in ("jit", "jit_per_bucket"):
            import jax
            tl = (self.primary if self.backend == "jit"
                  else self.predictors[bucket])._translated
            pavals = [jax.ShapeDtypeStruct(p.shape, p.dtype)
                      for p in tl._params]

            def call(*args):
                out = tl._exported.call(*args)
                return tuple(out) if isinstance(out, (list, tuple)) \
                    else (out,)

            return call, avals + pavals, tl
        # executor: rebuild the compiled replay closure abstractly so the
        # pass suite sees the full op graph, not an opaque call
        from ..static.executor import _collect_persistables, global_scope
        p = self.primary
        exe, program = p._exe, p._program
        feed_names = sorted(p._feed_names)
        persist = exe._persistable_names(program)
        written = [n for n in persist
                   if any(n in op.output_names
                          for op in program.global_block().ops)]
        replay = exe._build_replay(program, feed_names,
                                   list(p._fetch_names), persist, written)
        pvals = _collect_persistables(program, global_scope(), persist)
        order = [sorted(p._feed_names).index(n) for n in p._feed_names]

        def call(*feeds):
            ordered = [None] * len(feeds)
            for slot, i in zip(order, range(len(feeds))):
                ordered[slot] = feeds[i]
            return replay(ordered, pvals)[0]

        return call, avals, None

    # -- admission: lint gate ------------------------------------------------
    def lint_gate(self, bucket):
        """Run the analysis PassManager over this bucket's program in
        abstract-eval mode; ERROR findings refuse admission (stricter
        than warn mode's compile-path behavior: a server must not admit a
        model it knows is hazardous).  Gated by FLAGS_graph_lint — the
        off-path is this one branch."""
        from .. import analysis
        if not analysis.lint_enabled():
            return
        import jax
        fn, avals, _ = self._abstract_callable(bucket)
        try:
            closed = jax.make_jaxpr(fn)(*avals)
        except Exception as e:   # noqa: BLE001 — lint must not mask load bugs
            import warnings
            warnings.warn(
                f"serving warm-up lint for {self.name!r} b{bucket} could "
                f"not abstract-eval the program: {type(e).__name__}: {e}",
                analysis.GraphLintWarning, stacklevel=2)
            return
        ctx = analysis.LintContext(
            site=self.site, kind="serving", closed_jaxpr=closed,
            cache_key=self._bucket_key(bucket),
            arg_paths=[f"inputs[{i}]" for i in range(len(self.templates))])
        report = analysis.default_pass_manager().run(ctx)
        analysis.emit(report, mode="warn")     # gauges/JSONL/warnings
        errors = report.by_severity(analysis.Severity.ERROR)
        if errors:
            raise PreconditionNotMetError(
                f"serving refused to admit model {self.name!r}: graph "
                f"lint found {len(errors)} ERROR finding(s) at bucket "
                f"{bucket}:\n" + "\n".join("  " + str(d) for d in errors))

    def _bucket_key(self, bucket):
        return tuple([("arg:bucket", bucket)]
                     + [(f"arg:inputs[{i}]", (bucket,) + rest, str(dt))
                        for i, (rest, dt) in enumerate(self.templates)])

    def _artifact_identity(self, bucket):
        """Restart-stable program identity for the persistent executable
        cache: sha256 of the exported StableHLO bytes + param avals.
        The executable bakes no weights (params are trailing args), so
        every process serving the same ARTIFACT shares entries — which
        is exactly the one-host-compiles/N-hosts-load contract."""
        import hashlib
        tl = (self.primary if self.backend == "jit"
              else self.predictors[bucket])._translated
        blob = getattr(tl._exported, "mlir_module_serialized", None)
        if blob is None:
            blob = str(tl._exported.mlir_module()).encode()
        pav = tuple((tuple(int(d) for d in p.shape), str(p.dtype))
                    for p in tl._params)
        return ("serving_artifact",
                hashlib.sha256(blob).hexdigest(), pav)

    # -- warm-up: AOT compile every bucket -----------------------------------
    def warmup(self):
        import jax
        from ..jit import persistent_cache as _pcache
        for bucket in self.ladder:
            self.lint_gate(bucket)
            zeros = [np.zeros((bucket,) + rest, dt)
                     for rest, dt in self.templates]
            if self.backend == "executor":
                # the Executor's own cache + ledger own this compile
                # (including its persistent-cache seat)
                outs = self.primary.run(zeros)
                self.executables[bucket] = None
                self.n_outputs = len(outs)
                continue
            fn, avals, tl = self._abstract_callable(bucket)
            compiled, _loaded = _pcache.load_or_compile(
                lambda: jax.jit(fn).lower(*avals).compile(),
                site=self.site, kind="serving_aot",
                key=self._bucket_key(bucket),
                extra_key=self._artifact_identity(bucket),
                extra={"bucket": bucket, "model": self.name})
            params_dev = [jax.device_put(p) for p in tl._params]
            ex = _BucketExec(compiled, params_dev, len(self.templates))
            outs = ex([jax.device_put(z) for z in zeros])
            jax.block_until_ready(outs)
            self.executables[bucket] = ex
            self.n_outputs = len(outs)
        self.admitted = True

    # -- steady-state escape hatch -------------------------------------------
    def late_compile(self, bucket):
        """A bucket with no warm-up executable reached a worker.  Strict
        mode refuses; otherwise compile now, LEDGERED as a steady-state
        compile so the zero-recompile invariant visibly fails."""
        if bool(_flags.flag("serving_strict")):
            raise PreconditionNotMetError(
                f"serving model {self.name!r}: bucket {bucket} has no "
                "warm-up executable (FLAGS_serving_strict=True refuses "
                "steady-state compiles — extend the bucket ladder and "
                "re-warm instead)")
        import jax
        from ..jit import persistent_cache as _pcache
        fn, avals, tl = self._abstract_callable(bucket)
        # a cache hit still lands a ledger event at this site (kind
        # cache_load), so the zero-steady-state invariant stays visibly
        # violated — the load is merely cheaper than the compile
        compiled, _loaded = _pcache.load_or_compile(
            lambda: jax.jit(fn).lower(*avals).compile(),
            site=self.site, kind="serving_recompile",
            key=self._bucket_key(bucket),
            extra_key=self._artifact_identity(bucket),
            extra={"bucket": bucket, "model": self.name})
        ex = _BucketExec(compiled, [jax.device_put(p) for p in tl._params],
                         len(self.templates))
        stat_add("serving_steady_compiles")
        self.bump(steady_compiles=1)
        self.executables[bucket] = ex
        return ex

    def publish(self):
        self.latency.publish(f"serving_{self.name}")
        self.rate.publish(f"serving_{self.name}")


# ---------------------------------------------------------------------------
# Worker: clone-per-thread execution with async pipelining
# ---------------------------------------------------------------------------

class _Worker(threading.Thread):
    """One serving thread.  Owns a ``Predictor.clone()`` per model (the
    AnalysisPredictor::Clone seat: shared weights + compiled executables,
    per-clone feed/result buffers) and a bounded in-flight deque: a batch
    is dispatched (H2D + execute, both asynchronous) and only fenced when
    the pipeline is full or the queue runs dry — so host staging of batch
    N+1 overlaps device execution of batch N."""

    def __init__(self, server: "Server", idx: int):
        super().__init__(name=f"serving-worker-{idx}", daemon=True)
        self._server = server
        self.clones = {name: rt.primary.clone()
                       for name, rt in server._models.items()
                       if rt.primary is not None}
        self._depth = max(1, int(_flags.flag("serving_pipeline_depth")))
        self._inflight: deque = deque()

    # -- batch execution -----------------------------------------------------
    def _execute(self, batch: Batch):
        import jax
        rt = self._server._models[batch.model]
        if getattr(rt, "kind", None) == "decode":
            # prefill + scanned decode: one long device program — run it
            # synchronously (the scan IS the pipeline) and slice per
            # request, honoring each request's own max_new cap.  The
            # runtime emits prefill/decode spans (the slot loop: the
            # request's life from its stamps, closed by rt.replied); an
            # eventual escape-hatch compile lands on the ambient span
            with _tracing.use_span(_first_trace(batch)):
                toks = rt.execute(batch)
            now = time.perf_counter()
            t_r0 = time.monotonic()
            off = 0
            for r in batch.requests:
                # the slot rows' lives close here, before the client
                # can see the answer: whoever reads the loop's stats
                # after a reply finds that request in them
                rt.replied(r, time.monotonic())
                # a parked session's future was already failed
                # (UnavailableError) by the slot loop's drain park —
                # don't double-resolve it
                if not r.future.done():
                    r.future.set_result(
                        [toks[off:off + r.rows, :r.max_new]])
                rt.latency.observe(now - r.t_enqueue)
                off += r.rows
            _trace_batch(batch, "reply", t_r0, time.monotonic())
            self._finish_traces(batch)
            rt.rate.add(len(batch.requests))
            rt.bump(completed=len(batch.requests), batches=1,
                    rows=batch.rows,
                    padded_rows=batch.bucket - batch.rows)
            stat_add("serving_completed_total", len(batch.requests))
            stat_add("serving_batches_total")
            stat_add("serving_padding_rows_total",
                     batch.bucket - batch.rows)
            rt.publish()
            return
        t_h0 = time.monotonic()
        host = [np.concatenate([r.inputs[i] for r in batch.requests], axis=0)
                if len(batch.requests) > 1 else batch.requests[0].inputs[i]
                for i in range(rt.n_inputs)]
        padded = pad_to_bucket(host, batch.rows, batch.bucket)
        ex = rt.executables.get(batch.bucket)
        if rt.backend == "executor":
            # synchronous path: the Executor fences internally; its cache
            # hit is the ledger proof that steady state never recompiles
            clone = self.clones[batch.model]
            t_e0 = time.monotonic()
            with _tracing.use_span(_first_trace(batch)):
                outs = clone.run(padded)
            t_e1 = time.monotonic()
            _trace_batch(batch, "h2d", t_h0, t_e0, bucket=batch.bucket)
            _trace_batch(batch, "execute", t_e0, t_e1,
                         bucket=batch.bucket, backend="executor")
            self._complete(batch, outs)
            return
        if ex is None:
            with _tracing.use_span(_first_trace(batch)):
                ex = rt.late_compile(batch.bucket)
        dev = [jax.device_put(a) for a in padded]
        t_e0 = time.monotonic()
        _trace_batch(batch, "h2d", t_h0, t_e0, bucket=batch.bucket)
        outs = ex(dev)
        self._inflight.append((batch, outs, t_e0))
        while len(self._inflight) > self._depth:
            self._fence_oldest()

    def _fence_oldest(self):
        batch, outs, t_e0 = self._inflight.popleft()
        t_f0 = time.monotonic()
        outs_np = [np.asarray(o) for o in outs]
        t_f1 = time.monotonic()
        # execute = dispatch → fence start (the async pipeline residency
        # window); d2h = the blocking fetch that fences it
        _trace_batch(batch, "execute", t_e0, t_f0, bucket=batch.bucket)
        _trace_batch(batch, "d2h", t_f0, t_f1)
        self._complete(batch, outs_np)

    def _drain(self):
        while self._inflight:
            self._fence_oldest()

    def _complete(self, batch: Batch, outs_np):
        rt = self._server._models[batch.model]
        now = time.perf_counter()
        t_r0 = time.monotonic()
        off = 0
        for r in batch.requests:
            r.future.set_result([o[off:off + r.rows] for o in outs_np])
            rt.latency.observe(now - r.t_enqueue)
            off += r.rows
        _trace_batch(batch, "reply", t_r0, time.monotonic())
        self._finish_traces(batch)
        rt.rate.add(len(batch.requests))
        rt.bump(completed=len(batch.requests), batches=1, rows=batch.rows,
                padded_rows=batch.bucket - batch.rows)
        stat_add("serving_completed_total", len(batch.requests))
        stat_add("serving_batches_total")
        stat_add("serving_padding_rows_total", batch.bucket - batch.rows)
        rt.publish()

    @staticmethod
    def _finish_traces(batch: Batch, error: Optional[str] = None):
        for r in batch.requests:
            if r.trace is not None:
                r.trace.set_attr(bucket=batch.bucket,
                                 batch_rows=batch.rows)
                if error is not None:
                    r.trace.set_attr(error=error)
                _tracing.finish(r.trace)

    def _fail(self, batch: Batch, exc: Exception):
        rt = self._server._models[batch.model]
        for r in batch.requests:
            if not r.future.done():
                r.future.set_exception(exc)
        self._finish_traces(batch, error=type(exc).__name__)
        rt.bump(errors=len(batch.requests))
        stat_add("serving_errors_total", len(batch.requests))

    # -- loop ----------------------------------------------------------------
    def run(self):
        q = self._server._dispatch_q
        while True:
            try:
                batch = q.get(timeout=0.02)
            except queue.Empty:
                # queue ran dry: latency beats pipelining — fence now
                self._drain()
                continue
            if batch is None:
                self._drain()
                return
            try:
                self._execute(batch)
            except Exception as e:   # noqa: BLE001 — fail the batch, not the server
                self._fail(batch, e)
            if q.empty():
                self._drain()


# ---------------------------------------------------------------------------
# The server
# ---------------------------------------------------------------------------

@dataclass
class ServingConfig:
    """Server-wide knobs; None fields fall back to FLAGS_serving_*."""

    workers: Optional[int] = None
    queue_capacity: Optional[int] = None
    batch_timeout_ms: Optional[float] = None
    pipeline_depth: Optional[int] = None
    buckets: Optional[Sequence[int]] = None
    optim_cache_dir: Optional[str] = None
    # model-artifact version stamp (rolling updates): published in the
    # replica's rendezvous entry and health report so the rollout
    # controller can tell old from new; None reads as "v0"
    version: Optional[str] = None


class Server:
    """In-process multi-tenant serving engine over inference.Predictor.

    Lifecycle::

        srv = serving.Server()
        srv.register("lenet", prefix, buckets=(1, 2, 4, 8))
        srv.start()                      # warm-up: lint + AOT every bucket
        fut = srv.submit("lenet", [x])   # x: [rows, ...] numpy
        outs = fut.result()              # per-request rows, padding removed
        srv.stop()

    ``start`` traces and compiles every (model, bucket) before a single
    request is admitted; after that the recompile ledger must stay silent
    — :meth:`assert_zero_steady_state_compiles` is the proof hook the
    bench and smoke tests call.
    """

    def __init__(self, config: Optional[ServingConfig] = None):
        self._config = config or ServingConfig()
        self._models: Dict[str, _ModelRuntime] = {}
        self._specs: List[ModelSpec] = []
        self._queue: Optional[RequestQueue] = None
        self._dispatch_q: Optional[queue.Queue] = None
        self._scheduler: Optional[threading.Thread] = None
        self._workers: List[_Worker] = []
        self._started = False
        self._stopped = False
        self._draining = False
        self._warmup_marks: Dict[str, int] = {}
        self._tenant_policies: Dict[str, dict] = {}
        self._session_store = None      # FLAGS_session_store, at start()

    def set_tenant_policy(self, tenant: str, max_pending: Optional[int]
                          = None, priority: Optional[int] = None) -> None:
        """Per-tenant admission knobs (quota + priority class); callable
        before start() — the policy is applied when the queue exists."""
        pol = self._tenant_policies.setdefault(str(tenant), {})
        if max_pending is not None:
            pol["max_pending"] = int(max_pending)
        if priority is not None:
            pol["priority"] = int(priority)
        if self._queue is not None:
            self._queue.set_tenant_policy(tenant, **pol)

    @property
    def version(self) -> str:
        """The served artifact version ("v0" unless configured)."""
        return str(self._config.version or "v0")

    @property
    def draining(self) -> bool:
        return self._draining

    # -- registry ------------------------------------------------------------
    def register(self, spec_or_name, path: Optional[str] = None,
                 **kw) -> ModelSpec:
        """Register a model (a ModelSpec, or name + path + ModelSpec
        kwargs).  Must happen before start()."""
        if self._started:
            raise PreconditionNotMetError(
                "register() after start(): the warm-up contract admits "
                "no un-warmed model — build a new Server")
        if isinstance(spec_or_name, ModelSpec) \
                or hasattr(spec_or_name, "make_runtime"):
            # ModelSpec, or any spec that builds its own runtime (the
            # cluster ShardedModelSpec seat) — duck-typed so server.py
            # never imports the cluster package
            spec = spec_or_name
        else:
            if path is None:
                raise InvalidArgumentError("register(name, path, ...)")
            kw.setdefault("buckets", self._config.buckets)
            kw.setdefault("optim_cache_dir", self._config.optim_cache_dir)
            spec = ModelSpec(name=str(spec_or_name), path=path, **kw)
        if spec.name in {s.name for s in self._specs}:
            raise InvalidArgumentError(
                f"model {spec.name!r} is already registered")
        self._specs.append(spec)
        return spec

    def register_decode(self, spec_or_name, layer=None, **kw
                        ) -> DecodeModelSpec:
        """Register an autoregressive-decode model (a DecodeModelSpec, or
        name + live layer + DecodeModelSpec kwargs).  Warm-up compiles
        the full (batch-bucket × prompt-bucket) prefill set and the
        (batch-bucket × cache-bucket) decode set; traffic goes through
        :meth:`submit_decode`."""
        if self._started:
            raise PreconditionNotMetError(
                "register_decode() after start(): the warm-up contract "
                "admits no un-warmed model — build a new Server")
        if isinstance(spec_or_name, DecodeModelSpec):
            spec = spec_or_name
        else:
            if layer is None:
                raise InvalidArgumentError(
                    "register_decode(name, layer, ...)")
            kw.setdefault("batch_buckets", self._config.buckets)
            spec = DecodeModelSpec(name=str(spec_or_name), layer=layer,
                                   **kw)
        if spec.name in {s.name for s in self._specs}:
            raise InvalidArgumentError(
                f"model {spec.name!r} is already registered")
        self._specs.append(spec)
        return spec

    def models(self) -> List[str]:
        return [s.name for s in self._specs]

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> "Server":
        """Load + lint + AOT-warm every registered model, snapshot the
        ledger, then open the doors (scheduler + worker threads)."""
        if self._started:
            raise PreconditionNotMetError("Server already started")
        if not self._specs:
            raise PreconditionNotMetError("no models registered")
        if bool(_flags.flag("session_store")):
            # one shared store per process: every slot-mode decode model
            # parks into and restores from it (cluster migration moves
            # sessions between these stores through the router)
            from .sessions import SessionStore
            self._session_store = SessionStore(
                spill_dir=str(_flags.flag("session_store_dir")),
                park_after_ms=int(_flags.flag("session_park_after_ms")))
        for spec in self._specs:
            if hasattr(spec, "make_runtime"):
                rt = spec.make_runtime()
            elif isinstance(spec, DecodeModelSpec):
                rt = _DecodeRuntime(spec)
            else:
                rt = _ModelRuntime(spec)
            if self._session_store is not None \
                    and hasattr(rt, "session_store"):
                rt.session_store = self._session_store
            rt.load()
            rt.warmup()
            rt.rate.reset()              # QPS clock starts with traffic
            self._models[spec.name] = rt
        # the zero-recompile invariant is measured from here: any compile
        # event at an owned site after this mark is a steady-state compile
        for site in self._owned_sites():
            self._warmup_marks[site] = len(_ledger.compile_events(site))
        n_workers = self._config.workers or int(_flags.flag("serving_workers"))
        cap = self._config.queue_capacity \
            or int(_flags.flag("serving_queue_capacity"))
        depth = self._config.pipeline_depth \
            or int(_flags.flag("serving_pipeline_depth"))
        self._queue = RequestQueue(cap)
        for tenant, pol in self._tenant_policies.items():
            self._queue.set_tenant_policy(tenant, **pol)
        self._dispatch_q = queue.Queue(maxsize=max(1, n_workers * depth))
        self._workers = [_Worker(self, i) for i in range(n_workers)]
        for w in self._workers:
            w.start()
        self._scheduler = threading.Thread(
            target=self._schedule_loop, name="serving-scheduler", daemon=True)
        self._scheduler.start()
        self._started = True
        return self

    def _owned_sites(self) -> List[str]:
        sites = []
        for rt in self._models.values():
            sites.append(rt.site)
            if rt.backend == "executor":
                sites.append(f"executor:{rt.primary._program._uid}")
        return sites

    def _schedule_loop(self):
        timeout_ms = self._config.batch_timeout_ms
        if timeout_ms is None:
            timeout_ms = float(_flags.flag("serving_batch_timeout_ms"))
        while True:
            batch = self._queue.next_batch(
                lambda m: self._models[m].ladder.max_rows,
                lambda m, rows: self._models[m].ladder.bucket_for(rows),
                timeout_ms / 1e3)
            if batch is None:
                break
            self._dispatch_q.put(batch)      # bounded: backpressure makes
        for _ in self._workers:              # queued requests batch bigger
            self._dispatch_q.put(None)

    def stop(self, drain: bool = True) -> None:
        """Stop accepting traffic; ``drain`` serves what is queued first,
        otherwise pending futures fail with UnavailableError."""
        if not self._started or self._stopped:
            self._stopped = True
            return
        if not drain:
            for r in self._queue.drain():
                if not r.future.done():
                    r.future.set_exception(UnavailableError(
                        "server stopped before this request was served"))
        self._queue.close()
        self._scheduler.join(timeout=30)
        for w in self._workers:
            w.join(timeout=30)
        for rt in self._models.values():
            close = getattr(rt, "close", None)
            if close is not None:
                close()
        self._stopped = True

    # -- graceful drain (cluster lifecycle) ----------------------------------
    def request_drain(self) -> None:
        """Flip to drain mode: new submissions bounce with
        UnavailableError (retry_after = the staleness window, so a
        router redirects and backs this replica off) while everything
        already admitted — queued batches and slot-loop rows — runs to
        completion.  Idempotent; the server keeps serving in-flight
        work until :meth:`drain` reports it empty."""
        self._draining = True

    def _reject_if_draining(self) -> None:
        if self._draining:
            raise UnavailableError(
                "replica is draining (graceful retirement in progress)",
                retry_after_s=float(_flags.flag("router_stale_after_s")))

    def pending_requests(self) -> int:
        """Requests admitted but not yet completed or failed, summed
        over models — slot-loop rows count until their batch future
        resolves, so 0 means every admitted token was served."""
        n = 0
        for rt in self._models.values():
            with rt._mlock:
                c = rt.counters
                n += c["requests"] - c["completed"] - c["errors"]
        return n

    def drain(self, timeout_s: Optional[float] = None) -> dict:
        """Graceful drain: stop admitting (see :meth:`request_drain`),
        then wait until the queue is empty and every admitted request
        has resolved — in-flight batches finish, slot-loop rows retire
        at token boundaries.  Returns a report dict; ``drained`` False
        means the timeout expired with work still pending (the caller's
        escalation path — evict — takes over)."""
        if timeout_s is None:
            timeout_s = float(_flags.flag("drain_timeout_s"))
        t0 = time.monotonic()
        self.request_drain()
        if not self._started or self._stopped:
            return {"drained": True, "pending": 0, "queue_depth": 0,
                    "waited_s": 0.0, "parked_sessions": 0}
        # session-stateful drain (FLAGS_session_store): live slot-loop
        # conversations PARK to the store instead of running their full
        # token budget out — their futures fail retryably (Unavailable)
        # and the router redispatches the turn to a surviving replica,
        # which restores the snapshot and resumes bit-identically
        parked = self.park_sessions(timeout_s=float(timeout_s))
        deadline = t0 + max(0.0, float(timeout_s))
        while True:
            pending = self.pending_requests()
            qdepth = self._queue.depth() if self._queue else 0
            if pending <= 0 and qdepth == 0:
                return {"drained": True, "pending": 0, "queue_depth": 0,
                        "waited_s": round(time.monotonic() - t0, 3),
                        "parked_sessions": parked}
            if time.monotonic() >= deadline:
                return {"drained": False, "pending": int(pending),
                        "queue_depth": int(qdepth),
                        "waited_s": round(time.monotonic() - t0, 3),
                        "parked_sessions": parked}
            time.sleep(min(0.02, max(0.001, timeout_s / 50.0)))

    def park_sessions(self, timeout_s: float = 30.0) -> int:
        """Park every live slot-loop conversation into the session store
        (no-op without FLAGS_session_store); returns sessions parked."""
        if self._session_store is None:
            return 0
        n = 0
        for rt in self._models.values():
            loop = getattr(rt, "_loop", None)
            if loop is not None:
                n += loop.park_sessions(timeout=timeout_s)
        return n

    @property
    def session_store(self):
        """The process-wide session store (None without
        FLAGS_session_store) — the cluster replica's migration seat."""
        return self._session_store

    def __enter__(self):
        if not self._started:
            self.start()
        return self

    def __exit__(self, *exc):
        self.stop(drain=not any(exc))

    # -- traffic -------------------------------------------------------------
    def _runtime(self, model: str) -> _ModelRuntime:
        rt = self._models.get(model)
        if rt is None or not rt.admitted:
            raise NotFoundError(
                f"model {model!r} is not admitted (registered: "
                f"{self.models()})")
        return rt

    def _put(self, rt, req):
        """Enqueue with honest rejection accounting: a backpressure
        rejection (UnavailableError, carrying the queue's machine-
        readable retry-after hint) closes the request's trace span and
        counts an error before propagating — the router reads the hint
        and backs off this replica instead of evicting it."""
        try:
            self._queue.put(req, timeout=req._put_timeout)
        except UnavailableError as e:
            if req.trace is not None:
                req.trace.set_attr(error="UnavailableError",
                                   retry_after_s=getattr(
                                       e, "retry_after_s", None))
                _tracing.finish(req.trace)
            rt.bump(errors=1)
            stat_add("serving_errors_total")
            raise

    def submit(self, model: str, inputs, timeout: Optional[float] = 5.0,
               trace_id: Optional[str] = None, tenant: str = "default",
               priority: Optional[int] = None) -> Future:
        """Enqueue one request of ``rows`` examples (rows = leading dim);
        returns a Future resolving to per-output numpy arrays with
        exactly ``rows`` rows (padding never leaks).  Blocks up to
        ``timeout`` under backpressure, then raises UnavailableError
        carrying the queue's retry-after hint.  ``trace_id`` joins this
        request to a caller-owned trace (the router's cross-process
        propagation seat)."""
        if not self._started or self._stopped:
            raise PreconditionNotMetError(
                "Server is not serving (start() it / already stopped)")
        self._reject_if_draining()
        rt = self._runtime(model)
        if getattr(rt, "kind", None) == "decode":
            raise InvalidArgumentError(
                f"model {model!r} is a decode model: use "
                "submit_decode(model, prompts, max_new_tokens=...)")
        if len(inputs) != rt.n_inputs:
            raise InvalidArgumentError(
                f"model {model!r} takes {rt.n_inputs} inputs, got "
                f"{len(inputs)}")
        arrs, rows = [], None
        for i, (a, (rest, dt)) in enumerate(zip(inputs, rt.templates)):
            a = np.asarray(a, dtype=dt)      # dtype pinned: signature-stable
            if a.ndim != len(rest) + 1 or tuple(a.shape[1:]) != rest:
                raise InvalidArgumentError(
                    f"model {model!r} input {i}: got shape "
                    f"{list(a.shape)}, served shape is [rows, "
                    f"{', '.join(map(str, rest))}]")
            if rows is None:
                rows = a.shape[0]
            elif a.shape[0] != rows:
                raise InvalidArgumentError(
                    f"model {model!r}: inconsistent request rows "
                    f"({rows} vs {a.shape[0]} at input {i})")
            arrs.append(a)
        if rows == 0:
            raise InvalidArgumentError("empty request (0 rows)")
        rt.ladder.bucket_for(rows)           # raises OutOfRange early
        req = Request(model=model, inputs=tuple(arrs), rows=rows,
                      tenant=tenant, priority=priority,
                      trace=_tracing.start_span(
                          "request", trace_id=trace_id, model=model,
                          rows=rows, kind="dense"))
        rt.bump(requests=1)
        stat_add("serving_requests_total")
        req._put_timeout = timeout
        self._put(rt, req)
        return req.future

    def run(self, model: str, inputs, timeout: Optional[float] = 60.0):
        """Synchronous convenience: submit + wait."""
        return self.submit(model, inputs).result(timeout=timeout)

    def submit_decode(self, model: str, prompts,
                      max_new_tokens: Optional[int] = None,
                      timeout: Optional[float] = 5.0,
                      trace_id: Optional[str] = None,
                      tenant: str = "default",
                      priority: Optional[int] = None,
                      session_id: Optional[str] = None) -> Future:
        """Enqueue one decode request: ``prompts`` is a list of 1-D int
        token arrays (variable lengths — they left-pad to the prompt
        bucket at execution).  Resolves to ``[ids]`` where ids is an
        int32 array [len(prompts), max_new_tokens] of generated tokens.
        Rows of one request ride one batch; the continuous batcher packs
        concurrent requests exactly like dense traffic.

        ``session_id`` (FLAGS_session_store) names the conversation:
        single-prompt requests only, with ``prompts[0]`` the FULL
        transcript so far (history + new turn) — the slot loop restores
        the parked KV planes and prefills only the uncached suffix."""
        if not self._started or self._stopped:
            raise PreconditionNotMetError(
                "Server is not serving (start() it / already stopped)")
        self._reject_if_draining()
        rt = self._runtime(model)
        if getattr(rt, "kind", None) != "decode":
            raise InvalidArgumentError(
                f"model {model!r} is not a decode model: use submit()")
        if getattr(rt, "role", "both") != "both":
            raise PreconditionNotMetError(
                f"model {model!r}: this replica serves the "
                f"{rt.role!r} pool only (FLAGS_serving_role) — full "
                "decode requests need role 'both', or route "
                "prefill_handoff → decode_from_handoff across the pools")
        arrs, max_new = rt.validate(list(prompts), max_new_tokens)
        if session_id is not None and len(arrs) != 1:
            raise InvalidArgumentError(
                f"session_id={session_id!r} requires exactly one prompt "
                f"(one conversation = one row), got {len(arrs)}")
        rt.ladder.bucket_for(len(arrs))      # raises OutOfRange early
        req = DecodeRequest(model=model, prompts=arrs, rows=len(arrs),
                            max_new=max_new,
                            tenant=tenant, priority=priority,
                            session_id=None if session_id is None
                            else str(session_id),
                            trace=_tracing.start_span(
                                "request", trace_id=trace_id, model=model,
                                rows=len(arrs), kind="decode",
                                max_new=max_new))
        rt.bump(requests=1)
        stat_add("serving_requests_total")
        req._put_timeout = timeout
        self._put(rt, req)
        return req.future

    def run_decode(self, model: str, prompts,
                   max_new_tokens: Optional[int] = None,
                   timeout: Optional[float] = 60.0):
        """Synchronous convenience: submit_decode + wait."""
        return self.submit_decode(model, prompts, max_new_tokens) \
            .result(timeout=timeout)

    # -- disaggregated pools (serving/cluster) -------------------------------
    def _decode_runtime(self, model: str):
        rt = self._runtime(model)
        if getattr(rt, "kind", None) != "decode":
            raise InvalidArgumentError(
                f"model {model!r} is not a decode model — KV handoff is "
                "a prefill/decode-pool operation")
        return rt

    def prefill_handoff(self, model: str, prompts,
                        max_new_tokens: Optional[int] = None):
        """Prefill-pool entry: run ONLY the prefill phase and return the
        KVHandoff (device planes + logits + validity metadata) a decode
        pool resumes from — serialize with ``.to_bytes()`` to cross a
        process boundary."""
        if not self._started or self._stopped:
            raise PreconditionNotMetError(
                "Server is not serving (start() it / already stopped)")
        self._reject_if_draining()
        return self._decode_runtime(model).prefill_handoff(
            prompts, max_new_tokens)

    def decode_from_handoff(self, model: str, handoff):
        """Decode-pool entry: resume generation from a prefill pool's
        handoff (a KVHandoff, or its serialized bytes); returns ids
        [rows, max_new] bit-identical to the in-process generate()."""
        if not self._started or self._stopped:
            raise PreconditionNotMetError(
                "Server is not serving (start() it / already stopped)")
        self._reject_if_draining()
        if isinstance(handoff, (bytes, bytearray, memoryview)):
            from .cluster.handoff import deserialize_kv
            handoff = deserialize_kv(bytes(handoff))
        return self._decode_runtime(model).decode_from_handoff(handoff)

    # -- observability -------------------------------------------------------
    def compile_events_since_warmup(self) -> List[dict]:
        """Ledger compile events at server-owned sites recorded AFTER the
        warm-up mark — the steady-state window must keep this empty."""
        out = []
        for site, mark in self._warmup_marks.items():
            out.extend(_ledger.compile_events(site)[mark:])
        return out

    def assert_zero_steady_state_recompiles(self) -> None:
        evs = self.compile_events_since_warmup()
        if evs:
            raise PreconditionNotMetError(
                f"steady-state recompile(s) detected ({len(evs)}): "
                + "; ".join(f"{e['site']} {e.get('kind')} {e['diff']}"
                            for e in evs[:4]))

    def stats(self, model: Optional[str] = None) -> dict:
        """Serving health snapshot (the PERF.md serving schema): per-model
        qps / p50 / p99 / padding / steady_compiles, or all models."""
        if model is None:
            return {name: self.stats(name) for name in self._models}
        rt = self._runtime(model)
        with rt._mlock:
            c = dict(rt.counters)
        lat = rt.latency.snapshot()
        rows = max(1, c["rows"])
        return {
            "model": model, "backend": rt.backend,
            "buckets": rt.ladder.buckets,
            "requests": c["requests"], "completed": c["completed"],
            "errors": c["errors"], "batches": c["batches"],
            "qps": round(rt.rate.rate(), 2),
            "p50_ms": lat["p50_ms"], "p99_ms": lat["p99_ms"],
            "max_ms": lat["max_ms"],
            "avg_batch_rows": round(c["rows"] / max(1, c["batches"]), 2),
            "padding_ratio": round(c["padded_rows"] /
                                   (rows + c["padded_rows"]), 4),
            "queue_depth": self._queue.depth() if self._queue else 0,
            "steady_compiles": c["steady_compiles"],
            **({"slot_loop": rt._loop.stats()}
               if getattr(rt, "_loop", None) is not None else {}),
        }

    def signals(self) -> dict:
        """This process's autoscaling-signal snapshot (the per-replica
        leg of cluster/obs.ClusterSignals): queue depth + retry-after
        EWMA from the RequestQueue, average batch occupancy and the
        steady-state recompile count summed over models."""
        out = {"queue_depth": 0, "retry_after_s": 0.0,
               "drain_rate_rps": 0.0}
        if self._queue is not None:
            out.update(self._queue.signals())
        rows = batches = steady = 0
        for rt in self._models.values():
            c = rt.counters
            rows += c.get("rows", 0)
            batches += c.get("batches", 0)
            steady += c.get("steady_compiles", 0)
        out["batch_occupancy_rows"] = round(rows / batches, 3) \
            if batches else 0.0
        out["steady_compiles"] = steady
        # token-level decode-slot accounting (FLAGS_decode_slots):
        # occupancy is the max over slot-mode decode models, the
        # join/retire counters sum — absent entirely on the scanned path
        slot = [s for s in (getattr(rt, "slot_signals", lambda: None)()
                            for rt in self._models.values())
                if s is not None]
        if slot:
            out["decode_slot_occupancy_ratio"] = max(
                s["decode_slot_occupancy_ratio"] for s in slot)
            out["slots_joined_total"] = sum(
                s["slots_joined_total"] for s in slot)
            out["slots_retired_total"] = sum(
                s["slots_retired_total"] for s in slot)
            for k in ("prefix_cache_blocks", "prefix_cache_bytes"):
                if any(k in s for s in slot):
                    out[k] = sum(s.get(k, 0) for s in slot)
        if self._session_store is not None:
            out["sessions_parked"] = len(self._session_store)
            out["session_store_bytes"] = self._session_store.nbytes()
        out["models"] = self.models()
        out["version"] = self.version
        out["draining"] = self._draining
        return out


def create_server(config: Optional[ServingConfig] = None) -> Server:
    """Factory mirroring inference.create_predictor."""
    return Server(config)
