"""Sharded serving replicas: the autoshard rules tables, served.

A model sharded for training by the PR-9 rules tables could not be
*served* — every serving runtime held a full replica.  Here a replica's
warm-up AOT-compiles its bucket grids over a TP/dp mesh with params
sharded by the SAME rules tables (``analysis.autoshard.propose`` over
the live layer's dotted param paths), so the serving layout is the
training layout by construction:

  * :func:`serving_shard_specs` — layer + mesh → {param: PartitionSpec}
    via the active (or given) rules table; hand annotations win exactly
    as in training;
  * :class:`ShardedModelSpec` / :class:`_ShardedRuntime` — a DENSE
    served model backed by a live layer compiled per bucket with sharded
    param avals (persistent-executable-cache-loaded, so replica N boots
    O(load)); registered on a Server like any other spec;
  * :func:`shard_admission_audit` — the PR-8 HLO audit run at admission
    over each compiled bucket executable (collective census + budget
    passes) plus the serving-specific containment check: a param the
    rules sharded must KEEP its live mesh axes in the compiled input
    layout — an executable that quietly replicated the TP shards is
    refused, not served.  Gated by ``FLAGS_hlo_audit`` (off-path = one
    branch, PR-5/8 discipline).

Decode models shard through the same specs via
``DecodeModelSpec(mesh=...)`` → ``Generator(mesh=, param_specs=)``
(text/generation.py), which additionally pins the KV-cache plane layout
(heads sharded by ``mp`` when divisible) so the prefill→decode handoff
is layout-stable across the pools.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

from ...framework import flags as _flags
from ...framework.enforce import PreconditionNotMetError
from ...profiler.metrics import LatencyWindow, RateMeter

__all__ = ["ShardedModelSpec", "serving_shard_specs",
           "shard_admission_audit", "kv_plane_spec"]


def serving_shard_specs(layer, mesh, rules=None) -> Dict[str, Any]:
    """{dotted param path: PartitionSpec-or-None} for serving ``layer``
    over ``mesh``, derived from the autoshard rules table training uses
    (``rules=None`` reads FLAGS_autoshard_rules' active table).  Hand
    annotations win over rule proposals — the training precedence."""
    from ...analysis.autoshard import propose
    if rules is not None and isinstance(rules, str):
        from ...analysis.autoshard import rules_table
        rules = rules_table(rules)
    plan = propose(layer, rules=rules, mesh=mesh)
    return plan.specs()


def kv_plane_spec(shape: Sequence[int], mesh) -> Any:
    """The pinned KV-cache plane layout for sharded decode: ring planes
    are [B, head groups, C, g*H] (bf16/f32 rows: ``g`` adjacent heads
    share a row of the minor dim, ``gen_ring_cache``; g = 1 is
    [B, heads, C, H]) / [B, heads, C, H] and [B, heads, C, 1] (int8
    rows and their scales) — shard axis 1 by ``mp`` when it is live and
    divides, replicate otherwise.  A group holds whole heads, so every
    shard still owns whole heads; with an odd group count (GPT-2 XL: 25
    heads -> 13 groups) the planes replicate where 25 heads did too.
    The price of counting groups: ``mp`` must divide ``heads / g``, not
    ``heads`` (12 heads of 64 shard over mp=2 or 3, no longer over 4).
    This single rule makes prefill outputs, decode inputs and
    cross-pool device ingests agree without consulting each other."""
    from jax.sharding import PartitionSpec as P
    mp = dict(mesh.shape).get("mp", 1)
    if len(shape) >= 3 and mp > 1 and int(shape[1]) % mp == 0:
        return P(None, "mp")
    return P()


def _spec_live_axes(spec, mesh_axes: Dict[str, int]) -> set:
    axes = set()
    if spec is None:
        return axes
    for e in tuple(spec):
        for a in (e if isinstance(e, (tuple, list)) else (e,)):
            if a is not None and mesh_axes.get(a, 1) > 1:
                axes.add(a)
    return axes


def shard_admission_audit(compiled, *, site: str, mesh,
                          param_specs: Optional[Dict[str, Any]] = None,
                          mesh_label: str = "") -> None:
    """Admission-time HLO audit of one serving executable (PR-8 pass
    family: collective census, wire/HBM budgets) plus the serving
    containment contract: every param the rules sharded over a live
    axis must carry that axis in the compiled INPUT layout — a program
    that re-replicated the shards would silently multiply per-device
    HBM by the mesh size, which is exactly what sharded serving exists
    to prevent.  ERROR findings (or a dropped axis) refuse admission.
    Rides FLAGS_hlo_audit; off = this one branch."""
    from ... import analysis
    from ...analysis.hlo import audit_compiled, audit_enabled
    if not audit_enabled():
        return
    res = audit_compiled(compiled, site=site, mesh=mesh,
                         mesh_label=mesh_label, do_emit=True)
    errors = res.report.by_severity(analysis.Severity.ERROR)
    dropped = []
    if param_specs:
        mesh_axes = dict(mesh.shape)
        try:
            in_params = compiled.input_shardings[0][0]
        except Exception:
            in_params = None
        if isinstance(in_params, dict):
            for name, spec in sorted(param_specs.items()):
                want = _spec_live_axes(spec, mesh_axes)
                s = in_params.get(name)
                if not want or s is None:
                    continue
                if getattr(s, "is_fully_replicated", False):
                    dropped.append((name, sorted(want)))
    if errors or dropped:
        lines = ["  " + str(d) for d in errors]
        lines += [f"  param {n!r} lost its sharded axes {a} in the "
                  "compiled input layout (stored full per device)"
                  for n, a in dropped]
        raise PreconditionNotMetError(
            f"serving admission HLO audit refused {site!r} at "
            f"{mesh_label or 'mesh'}:\n" + "\n".join(lines))


# ---------------------------------------------------------------------------
# Dense sharded runtime
# ---------------------------------------------------------------------------

@dataclass
class ShardedModelSpec:
    """One dense served model backed by a LIVE layer sharded over
    ``mesh`` (jax.sharding.Mesh, e.g. ``parallel.make_mesh({'dp': 2,
    'mp': 4})``).  ``input_specs`` is the executor-spec convention
    ``[(shape-with-None-lead, dtype), ...]``; ``rules`` optionally
    names/provides the autoshard table (default: the active table)."""

    name: str
    layer: Any
    input_specs: Sequence[Tuple[Sequence[Optional[int]], Any]]
    mesh: Any
    rules: Any = None
    buckets: Optional[Sequence[int]] = None

    def make_runtime(self):
        return _ShardedRuntime(self)


class _ShardedExec:
    """One compiled (model, bucket) SPMD executable: sharded params +
    replicated buffers held resident, inputs re-placed to the compiled
    input shardings per call (the worker's plain device_put committed
    them to one device; this transfer re-shards them onto the mesh)."""

    __slots__ = ("compiled", "params_dev", "buffers_dev", "in_shardings")

    def __init__(self, compiled, params_dev, buffers_dev, in_shardings):
        self.compiled = compiled
        self.params_dev = params_dev
        self.buffers_dev = buffers_dev
        self.in_shardings = in_shardings

    def __call__(self, dev_inputs):
        import jax
        placed = [jax.device_put(x, s)
                  for x, s in zip(dev_inputs, self.in_shardings)]
        return self.compiled(self.params_dev, self.buffers_dev, *placed)


class _ShardedRuntime:
    """Serving runtime for one sharded dense model — the live-layer
    analogue of server._ModelRuntime, duck-typing its worker-facing
    surface (templates/ladder/executables/late_compile/stats)."""

    kind = None                     # dense traffic (Server.submit)
    backend = "sharded"
    primary = None                  # no Predictor to clone

    def __init__(self, spec: ShardedModelSpec):
        from ..bucketing import BucketLadder
        self.spec = spec
        self.name = spec.name
        self.site = f"serving:{spec.name}"
        self.ladder = BucketLadder.from_flag(spec.buckets)
        self.mesh = spec.mesh
        self.executables = {}
        self.templates = []
        self.n_inputs = 0
        self.n_outputs = 0
        self.admitted = False
        self.param_specs: Dict[str, Any] = {}
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

    def publish(self):
        self.latency.publish(f"serving_{self.name}")
        self.rate.publish(f"serving_{self.name}")

    @property
    def mesh_label(self) -> str:
        return "x".join(f"{a}{n}" for a, n in dict(self.mesh.shape).items())

    # -- loading -------------------------------------------------------------
    def load(self):
        from ...framework.functional import layer_state
        from ...static import InputSpec
        self.spec.layer.eval()
        for s in self.spec.input_specs:
            if isinstance(s, InputSpec):
                shape, dtype = list(s.shape), s.dtype
            else:
                shape, dtype = list(s[0]), s[1]
            self.templates.append((tuple(int(d) for d in shape[1:]),
                                   np.dtype(dtype)))
        self.n_inputs = len(self.templates)
        self.param_specs = serving_shard_specs(self.spec.layer, self.mesh,
                                               self.spec.rules)
        import jax
        params, buffers = layer_state(self.spec.layer)
        self._params = {n: jax.device_put(v, self._sharding(
            self.param_specs.get(n))) for n, v in params.items()}
        self._buffers = {n: jax.device_put(v, self._sharding())
                         for n, v in buffers.items()}

    def _sharding(self, spec=None):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, spec if spec is not None else P())

    def _input_sharding(self, bucket):
        """Batch rows shard over dp when the bucket divides; otherwise
        the (small) activations replicate — correctness first, the
        params are where the memory is."""
        from jax.sharding import PartitionSpec as P
        dp = dict(self.mesh.shape).get("dp", 1)
        spec = P("dp") if dp > 1 and bucket % dp == 0 else P()
        return self._sharding(spec)

    # -- abstract view (lint + AOT avals) ------------------------------------
    def _abstract_callable(self, bucket):
        import jax
        from ...framework import core
        from ...framework.functional import _bound_state
        from ...framework.tensor import Tensor, unwrap
        layer = self.spec.layer

        def call(params, buffers, *inputs):
            with core.no_grad_guard(), _bound_state(layer, params, buffers):
                out = layer(*[Tensor(x) for x in inputs])
            outs = out if isinstance(out, (list, tuple)) else (out,)
            return tuple(unwrap(o) for o in outs)

        in_sh = self._input_sharding(bucket)
        p_avals = {n: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=self._sharding(
                                               self.param_specs.get(n)))
                   for n, a in self._params.items()}
        b_avals = {n: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=self._sharding())
                   for n, a in self._buffers.items()}
        x_avals = [jax.ShapeDtypeStruct((bucket,) + rest, dt, sharding=in_sh)
                   for rest, dt in self.templates]
        return call, [p_avals, b_avals] + x_avals, None

    def _bucket_key(self, bucket):
        return tuple([("arg:bucket", bucket),
                      ("arg:mesh", self.mesh_label)]
                     + [(f"arg:inputs[{i}]", (bucket,) + rest, str(dt))
                        for i, (rest, dt) in enumerate(self.templates)])

    def _program_identity(self):
        """Restart-stable identity for the persistent executable cache:
        layer architecture + param avals + mesh axes + the spec table —
        two replicas of one sharded model share entries, a different
        mesh or table never false-hits."""
        cfg = getattr(self.spec.layer, "config", None)
        cfg_r = repr(sorted(vars(cfg).items())) \
            if cfg is not None and hasattr(cfg, "__dict__") else repr(cfg)
        avals = tuple(sorted((n, tuple(int(d) for d in a.shape),
                              str(a.dtype))
                             for n, a in self._params.items()))
        specs = tuple(sorted((n, repr(s))
                             for n, s in self.param_specs.items()))
        return ("serving_sharded", type(self.spec.layer).__name__, cfg_r,
                avals, specs, self.mesh_label)

    # -- admission: lint gate (PR-6 discipline, shared shape) ----------------
    def lint_gate(self, bucket):
        from ... import analysis
        if not analysis.lint_enabled():
            return
        import jax
        fn, avals, _ = self._abstract_callable(bucket)
        try:
            closed = jax.make_jaxpr(fn)(*avals)
        except Exception as e:   # noqa: BLE001 — lint must not mask bugs
            import warnings
            warnings.warn(
                f"sharded serving warm-up lint for {self.name!r} "
                f"b{bucket} could not abstract-eval the program: "
                f"{type(e).__name__}: {e}",
                analysis.GraphLintWarning, stacklevel=2)
            return
        ctx = analysis.LintContext(
            site=self.site, kind="serving", closed_jaxpr=closed,
            cache_key=self._bucket_key(bucket), mesh=self.mesh)
        report = analysis.default_pass_manager().run(ctx)
        analysis.emit(report, mode="warn")
        errors = report.by_severity(analysis.Severity.ERROR)
        if errors:
            raise PreconditionNotMetError(
                f"serving refused to admit sharded model {self.name!r}: "
                f"graph lint found {len(errors)} ERROR finding(s) at "
                f"bucket {bucket}:\n"
                + "\n".join("  " + str(d) for d in errors))

    # -- warm-up -------------------------------------------------------------
    def _compile_bucket(self, bucket, kind):
        import jax
        from jax.sharding import PartitionSpec as P
        from ...jit import persistent_cache as _pcache
        fn, avals, _ = self._abstract_callable(bucket)
        compiled, _loaded = _pcache.load_or_compile(
            lambda: jax.jit(fn, out_shardings=self._sharding(P()))
            .lower(*avals).compile(),
            site=self.site, kind=kind, key=self._bucket_key(bucket),
            extra_key=self._program_identity(),
            extra={"bucket": bucket, "model": self.name,
                   "mesh": self.mesh_label})
        shard_admission_audit(compiled, site=self.site, mesh=self.mesh,
                              param_specs=self.param_specs,
                              mesh_label=self.mesh_label)
        in_sh = self._input_sharding(bucket)
        return _ShardedExec(compiled, self._params, self._buffers,
                            [in_sh] * self.n_inputs)

    def warmup(self):
        import jax
        for bucket in self.ladder:
            self.lint_gate(bucket)
            ex = self._compile_bucket(bucket, "serving_aot")
            zeros = [jax.device_put(np.zeros((bucket,) + rest, dt), s)
                     for (rest, dt), s in zip(self.templates,
                                              ex.in_shardings)]
            outs = ex.compiled(self._params, self._buffers, *zeros)
            jax.block_until_ready(outs)
            self.executables[bucket] = ex
            self.n_outputs = len(outs)
        self.admitted = True

    # -- steady-state escape hatch (server._ModelRuntime contract) -----------
    def late_compile(self, bucket):
        from ...utils.monitor import stat_add
        if bool(_flags.flag("serving_strict")):
            raise PreconditionNotMetError(
                f"sharded serving model {self.name!r}: bucket {bucket} "
                "has no warm-up executable (FLAGS_serving_strict=True "
                "refuses steady-state compiles — extend the bucket "
                "ladder and re-warm instead)")
        ex = self._compile_bucket(bucket, "serving_recompile")
        stat_add("serving_steady_compiles")
        self.bump(steady_compiles=1)
        self.executables[bucket] = ex
        return ex
