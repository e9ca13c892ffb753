"""The built-in lint pass suite: TPU perf/correctness hazards at trace time.

Each pass is the TPU seat of a family of reference framework/ir passes
(SURVEY §1): where Fluid's ~150 passes walked the ProgramDesc to validate
ops and rewrite subgraphs before execution, these walk the closed jaxpr
(and compile-site metadata) and *report* — rewriting is XLA's job, but
"this program will recompile every step / round-trip to host / double its
HBM" is knowable before the first step executes, and that is exactly when
it is cheapest to fix.

Pass inventory (ids are stable API — suppression keys, gauge names):

  recompile-hazard        python scalars baked into compile-cache keys,
                          weak-typed operands, shape-varying args
                          (cross-checked against the PR-1 recompile
                          ledger's previous key at the same site)
  host-transfer           callbacks / host round-trips inside the graph
  dtype-promotion         bf16→f32 upcasts on tensors, x64 leaks on TPU
  donation                params/opt-state entering a jitted train step
                          without buffer donation (2× HBM peak)
  layout                  dynamic-slice on minor (tiled) dims; matmul/conv
                          operands badly padded against 8×128 tiling
  collective-consistency  collectives/shard_map over axis names the
                          global mesh does not declare
  dead-fetch              computed-but-unfetched outputs (dead subgraphs)
  sharding-coverage       param leaves no partition rule matched while the
                          mesh has live model-parallel axes
                          (match_partition_rules discipline); names the
                          autoshard rule that WOULD cover each leaf
  autoshard-conflict      a hand shard_parameter annotation contradicts
                          the active autoshard rules table (ERROR: the
                          rules engine and the model disagree about the
                          layout — one of them is wrong)
  cache-key-hygiene       weak-typed or scalar-baked jit invars that
                          fragment the PERSISTENT executable cache key
                          space (jit/persistent_cache.py): what the
                          recompile-hazard pass reports as in-process
                          churn becomes on-disk fan-out — one serialized
                          executable per variant — once
                          FLAGS_executable_cache is on (silent while off)
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from .diagnostics import Diagnostic, Severity
from .jaxpr_utils import (all_avals, dead_eqns, iter_eqns, iter_jaxprs,
                          tile_pad_waste, user_source)
from .manager import LintContext, register_pass

__all__ = ["PASS_IDS"]

PASS_IDS = ("recompile-hazard", "host-transfer", "dtype-promotion",
            "donation", "layout", "collective-consistency", "dead-fetch",
            "sharding-coverage", "autoshard-conflict",
            "cache-key-hygiene")


def _diag(pass_id: str, message: str, location: Optional[str] = None,
          **extra) -> Diagnostic:
    return Diagnostic(pass_id=pass_id, severity=Severity.WARNING,
                      message=message, location=location, extra=extra)


def _nbytes(aval) -> int:
    try:
        return int(np.prod(aval.shape)) * aval.dtype.itemsize
    except Exception:
        return 0


# ---------------------------------------------------------------------------
# recompile-hazard
# ---------------------------------------------------------------------------

def _key_leaves(key, path=""):
    """Leaves of a nested cache key, tagged with their positional path —
    the same flattening the recompile ledger diffs with, so the lint and
    the ledger name the same culprit."""
    if isinstance(key, (tuple, list)) and any(
            isinstance(e, (tuple, list, dict)) for e in key):
        for i, e in enumerate(key):
            yield from _key_leaves(e, f"{path}[{i}]")
        return
    yield (path or "·", key)


def _scalar_const_entries(key):
    """('c', <type>, <value>) entries of a jit cache key: python scalars
    baked as static constants — every distinct value is a new program."""
    out = []

    def walk(k, path=""):
        if isinstance(k, (tuple, list)):
            if (len(k) == 3 and k[0] == "c"
                    and k[1] in ("int", "float")):
                out.append((path, k[1], k[2]))
                return
            for i, e in enumerate(k):
                walk(e, f"{path}[{i}]")
    walk(key)
    return out


@register_pass("recompile-hazard", severity=Severity.WARNING,
               doc="cache keys that will churn: scalar constants, "
                   "weak types, shape-varying args")
def _recompile_hazard(ctx: LintContext) -> List[Diagnostic]:
    out: List[Diagnostic] = []
    pid = "recompile-hazard"
    # (1) python scalars baked into the compile-cache key: a changing
    # learning rate / epsilon passed positionally recompiles per value
    if ctx.cache_key is not None:
        for path, tname, val in _scalar_const_entries(ctx.cache_key):
            out.append(_diag(
                pid,
                f"python {tname} {val!r} is baked into the compile-cache "
                f"key at {path}: every distinct value compiles a new "
                f"program — pass it as a Tensor/array operand instead",
                key_path=path))
    # (2) weak-typed operands: a python scalar promoted at trace time
    # carries a weak dtype that jit keys separately from the committed
    # dtype — two silent programs for what looks like the same signature
    if ctx.closed_jaxpr is not None:
        invars, _ = all_avals(ctx.closed_jaxpr)
        for i, aval in enumerate(invars):
            if getattr(aval, "weak_type", False):
                name = (ctx.arg_paths[i]
                        if ctx.arg_paths and i < len(ctx.arg_paths)
                        else f"operand[{i}]")
                out.append(_diag(
                    pid,
                    f"{name} is weak-typed ({aval.dtype}): it was a python "
                    f"scalar at trace time; committing it as a typed array "
                    f"(e.g. np.float32(x)) keeps one stable cache entry",
                    operand=name))
    # (3) ledger cross-check: this site compiled before with a different
    # key — report exactly which entry moved (the ledger's diff), because
    # a per-step moving entry means a recompile per step
    if ctx.prev_key is not None and ctx.cache_key is not None:
        from ..profiler import ledger as _ledger
        for line in _ledger.key_diff(ctx.prev_key, ctx.cache_key):
            if "first compile" in line or "key unchanged" in line:
                continue
            out.append(_diag(
                pid,
                f"this site recompiled: cache-key entry changed — {line}; "
                f"if this argument varies per step (e.g. a growing "
                f"sequence length), pad/bucket it to a stable shape",
                diff=line))
    return out


# ---------------------------------------------------------------------------
# cache-key-hygiene
# ---------------------------------------------------------------------------

def _weak_key_leaves(key):
    """Weak-typed signature leaves of a compile-cache key: both the jit
    signature convention ('t'|'a', shape, dtype, 'weak') and the ledger's
    labeled-leaf convention ('arg:<path>', shape, dtype, 'weak')."""
    out = []

    def walk(k, path=""):
        if isinstance(k, (tuple, list)):
            if len(k) == 4 and k[3] == "weak":
                if k[0] in ("t", "a"):
                    out.append((path or "operand", k[1], k[2]))
                    return
                if isinstance(k[0], str) and k[0].startswith("arg:"):
                    out.append((k[0][4:], k[1], k[2]))
                    return
            for i, e in enumerate(k):
                walk(e, f"{path}[{i}]")
    walk(key)
    return out


@register_pass("cache-key-hygiene", severity=Severity.WARNING,
               doc="weak-typed / scalar-baked jit invars that fragment "
                   "the persistent executable cache key space")
def _cache_key_hygiene(ctx: LintContext) -> List[Diagnostic]:
    """The recompile-hazard findings, re-read through the persistent
    executable cache (jit/persistent_cache.py): a key leaf that churns
    in-process costs a recompile per variant, but under
    FLAGS_executable_cache=readwrite it also SERIALIZES one on-disk
    executable per variant — the cache dir fans out and warm starts stop
    hitting.  Silent (one branch) while the cache flag is off."""
    from ..framework import flags as _flags
    try:
        if str(_flags.flag("executable_cache")).lower() == "off":
            return []
    except KeyError:
        return []
    if ctx.cache_key is None:
        return []
    pid = "cache-key-hygiene"
    out: List[Diagnostic] = []
    for path, tname, val in _scalar_const_entries(ctx.cache_key):
        out.append(_diag(
            pid,
            f"python {tname} {val!r} is baked into the compile key at "
            f"{path}: every distinct value serializes ANOTHER executable "
            f"into FLAGS_executable_cache_dir and none of them load on a "
            f"warm start with a different value — pass it as an array "
            f"operand so one cached entry serves all values",
            key_path=path))
    for path, shape, dtype in _weak_key_leaves(ctx.cache_key):
        out.append(_diag(
            pid,
            f"{path} enters the compile key weak-typed "
            f"({dtype}{list(shape)}): a python scalar at trace time keys "
            f"a DIFFERENT persistent cache entry than the committed "
            f"array a warm start feeds — commit the dtype (e.g. "
            f"np.float32(x)) so cold and warm starts share one entry",
            operand=path))
    # ledger cross-check (the recompile-hazard pass's machinery): a key
    # that already churned at this site is already fanning out on disk
    if ctx.prev_key is not None:
        from ..profiler import ledger as _ledger
        churn = [ln for ln in _ledger.key_diff(ctx.prev_key,
                                               ctx.cache_key)
                 if "first compile" not in ln
                 and "key unchanged" not in ln]
        if churn:
            out.append(_diag(
                pid,
                f"this site's cache key churns ({churn[0]}): each "
                f"variant persists its own executable — the "
                f"recompile-hazard fix (stable shapes/dtypes/buckets) "
                f"is also the disk-footprint fix",
                diff=churn[0]))
    return out


# ---------------------------------------------------------------------------
# host-transfer
# ---------------------------------------------------------------------------

_CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "host_callback_call", "outside_call", "device_get",
})


@register_pass("host-transfer", severity=Severity.ERROR,
               doc="host round-trips (callbacks, numpy coercion) inside "
                   "a traced region")
def _host_transfer(ctx: LintContext) -> List[Diagnostic]:
    out: List[Diagnostic] = []
    if ctx.closed_jaxpr is None:
        return out
    for eqn, _ in iter_eqns(ctx.closed_jaxpr):
        name = eqn.primitive.name
        if name in _CALLBACK_PRIMS:
            out.append(_diag(
                "host-transfer",
                f"'{name}' runs on HOST mid-graph: the TPU stalls for a "
                f"device→host→device round-trip every step — move the "
                f"computation in-graph or hoist it out of the compiled "
                f"region",
                user_source(eqn), primitive=name))
    return out


# ---------------------------------------------------------------------------
# dtype-promotion
# ---------------------------------------------------------------------------

_X64_DTYPES = ("float64", "int64", "uint64", "complex128")
_MXU_CONSUMERS = frozenset({"dot_general", "conv_general_dilated"})


@register_pass("dtype-promotion", severity=Severity.WARNING,
               doc="unintended f32 upcasts in a bf16 graph; x64 dtypes "
                   "on TPU")
def _dtype_promotion(ctx: LintContext) -> List[Diagnostic]:
    out: List[Diagnostic] = []
    if ctx.closed_jaxpr is None:
        return out
    pid = "dtype-promotion"
    invars, _ = all_avals(ctx.closed_jaxpr)
    low_precision_graph = any(
        str(getattr(a, "dtype", "")) in ("bfloat16", "float16")
        for a in invars)
    seen = set()
    for jaxpr in iter_jaxprs(ctx.closed_jaxpr):
        # bf16→f32 upcasts that FEED MXU ops: those cost 4× the matmul
        # FLOPs of staying bf16.  Reduction-epilogue upcasts (mean/softmax
        # accumulating in f32) are accumulation precision, not a hazard —
        # only the producer→dot/conv dataflow edge is flagged.
        if low_precision_graph:
            producer = {}
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "convert_element_type":
                    src = eqn.invars[0].aval
                    dst = eqn.outvars[0].aval
                    if (str(src.dtype) in ("bfloat16", "float16")
                            and str(dst.dtype) == "float32"
                            and len(dst.shape) >= 2):
                        producer[eqn.outvars[0]] = eqn
            for eqn in jaxpr.eqns:
                if eqn.primitive.name not in _MXU_CONSUMERS:
                    continue
                for v in eqn.invars:
                    up = producer.get(v)
                    if up is None:
                        continue
                    src = up.invars[0].aval
                    key = (user_source(up), str(src.dtype),
                           tuple(src.shape))
                    if key in seen:
                        continue
                    seen.add(key)
                    out.append(_diag(
                        pid,
                        f"{src.dtype}[{','.join(map(str, src.shape))}] is "
                        f"upcast to float32 and fed into "
                        f"'{eqn.primitive.name}': the matmul runs at f32 "
                        f"MXU rate (4× the bf16 cost) and the operand "
                        f"doubles its HBM traffic — keep the operand "
                        f"bf16 (preferred_element_type=f32 accumulates "
                        f"safely), or suppress if this is a deliberate "
                        f"master-weight cast",
                        user_source(up), shape=tuple(src.shape)))
        for eqn in jaxpr.eqns:
            for v in eqn.outvars:
                aval = getattr(v, "aval", None)
                dt = str(getattr(aval, "dtype", ""))
                if dt in _X64_DTYPES:
                    key = (user_source(eqn), dt)
                    if key in seen:
                        continue
                    seen.add(key)
                    out.append(_diag(
                        pid,
                        f"{dt} produced in-graph: TPUs have no 64-bit "
                        f"compute units — XLA emulates it at a multiple "
                        f"of the cost (jax_enable_x64 leak?)",
                        user_source(eqn), dtype=dt))
    return out


# ---------------------------------------------------------------------------
# donation
# ---------------------------------------------------------------------------

@register_pass("donation", severity=Severity.ERROR,
               kinds=("train_step",),
               doc="params/opt-state entering a jitted train step without "
                   "buffer donation")
def _donation(ctx: LintContext) -> List[Diagnostic]:
    if ctx.donate is not False:
        return []
    size = 0
    if ctx.params:
        size = sum(_nbytes(v) for v in ctx.params.values())
    mib = size / (1 << 20)
    detail = f" (~{mib:.1f} MiB of parameters alone, before optimizer " \
             f"state)" if size else ""
    return [_diag(
        "donation",
        f"train-step state enters the jitted step WITHOUT buffer "
        f"donation{detail}: XLA must keep both the old and the new "
        f"params/opt-state live across the step — 2× peak HBM. Pass "
        f"donate=True (the default) unless you are aliasing the state "
        f"elsewhere",
        state_bytes=size)]


# ---------------------------------------------------------------------------
# layout
# ---------------------------------------------------------------------------

_MXU_PRIMS = frozenset({"dot_general", "conv_general_dilated"})


@register_pass("layout", severity=Severity.WARNING,
               doc="dynamic-slice on tiled minor dims; matmul/conv "
                   "operands badly padded against 8x128 tiling")
def _layout(ctx: LintContext) -> List[Diagnostic]:
    out: List[Diagnostic] = []
    if ctx.closed_jaxpr is None:
        return out
    pid = "layout"
    seen = set()
    from .jaxpr_utils import Literal, static_vars
    for jaxpr in iter_jaxprs(ctx.closed_jaxpr):
        # per-level static set: slice starts that are functions of
        # trace-time constants fold away; only genuinely traced offsets
        # pay the cross-tile gather
        statics = static_vars(jaxpr)

        def _static(v):
            return isinstance(v, Literal) or v in statics

        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name in ("dynamic_slice", "dynamic_update_slice"):
                operand = eqn.invars[0].aval
                ndim = len(operand.shape)
                if ndim == 0:
                    continue
                if name == "dynamic_slice":
                    sizes = eqn.params.get("slice_sizes", ())
                    starts = eqn.invars[1:]
                else:
                    sizes = eqn.invars[1].aval.shape
                    starts = eqn.invars[2:]
                # minor = the last (lane, 128) and second-to-last
                # (sublane, 8) tiled dims
                for d in range(max(0, ndim - 2), ndim):
                    if d >= len(sizes) or sizes[d] == operand.shape[d]:
                        continue
                    start = starts[d] if d < len(starts) else None
                    if start is None or _static(start):
                        continue
                    if (d == ndim - 2 and len(sizes) == ndim
                            and sizes[ndim - 1] == operand.shape[ndim - 1]):
                        # ring-buffer KV-cache access: a traced start on
                        # the SUBLANE dim with the lane dim fully spanned
                        # lowers to a sublane-masked store/load within
                        # tiles, not a cross-tile gather.  Covers both
                        # the canonical generate() cache append
                        # (dynamic_update_slice, PR 7) and the int8
                        # cache's reads — int8 rows and per-head scale
                        # planes read by dynamic_slice at the traced
                        # cache_position with their (full) lane extent.
                        # Only a traced lane-dim start is a hazard
                        continue
                    which = "lane (last)" if d == ndim - 1 else "sublane"
                    key = (user_source(eqn), name, d)
                    if key in seen:
                        continue
                    seen.add(key)
                    out.append(_diag(
                        pid,
                        f"'{name}' slices the {which} dim of a "
                        f"{operand.dtype}"
                        f"[{','.join(map(str, operand.shape))}] at a "
                        f"dynamic offset: minor dims are tiled 8x128 on "
                        f"TPU, so this lowers to a masked gather across "
                        f"tiles — slice a major dim (transpose first) or "
                        f"use a static offset",
                        user_source(eqn), dim=d))
            elif name in _MXU_PRIMS:
                for v in eqn.invars:
                    aval = getattr(v, "aval", None)
                    shape = tuple(getattr(aval, "shape", ()))
                    if len(shape) < 2 or shape[-1] <= 128:
                        continue
                    waste = tile_pad_waste(shape[-1])
                    if waste <= 0.25:
                        continue
                    key = (user_source(eqn), shape)
                    if key in seen:
                        continue
                    seen.add(key)
                    out.append(_diag(
                        pid,
                        f"MXU operand "
                        f"{aval.dtype}[{','.join(map(str, shape))}] pads "
                        f"its minor dim {shape[-1]} up to "
                        f"{((shape[-1] + 127) // 128) * 128} lanes "
                        f"({waste:.0%} of the tile wasted): pick a "
                        f"feature dim near a multiple of 128",
                        user_source(eqn), dim=shape[-1],
                        waste=round(waste, 3)))
    return out


# ---------------------------------------------------------------------------
# collective-consistency
# ---------------------------------------------------------------------------

_COLLECTIVE_PRIMS = frozenset({
    "psum", "psum2", "pmean", "pmax", "pmin", "ppermute", "pbroadcast",
    "all_gather", "all_to_all", "reduce_scatter", "axis_index", "pgather",
})


def _declared_axes(ctx: LintContext) -> Optional[frozenset]:
    mesh = ctx.mesh
    if mesh is None:
        from ..parallel.mesh import has_mesh, get_mesh
        if not has_mesh():
            return None             # nothing declared -> nothing to check
        mesh = get_mesh()
    return frozenset(str(a) for a in mesh.axis_names)


@register_pass("collective-consistency", severity=Severity.ERROR,
               doc="collectives / shard_map over axis names the global "
                   "mesh does not declare")
def _collective_consistency(ctx: LintContext) -> List[Diagnostic]:
    out: List[Diagnostic] = []
    if ctx.closed_jaxpr is None:
        return out
    declared = _declared_axes(ctx)
    if declared is None:
        return out
    pid = "collective-consistency"
    seen = set()
    for eqn, bound in iter_eqns(ctx.closed_jaxpr):
        name = eqn.primitive.name
        if name == "shard_map":
            mesh = eqn.params.get("mesh")
            axes = [str(a) for a in getattr(mesh, "axis_names", ())]
            unknown = [a for a in axes if a not in declared]
            if unknown:
                key = (user_source(eqn), tuple(unknown))
                if key not in seen:
                    seen.add(key)
                    out.append(_diag(
                        pid,
                        f"shard_map binds mesh axes {unknown} that the "
                        f"global mesh does not declare (declared: "
                        f"{sorted(declared)}): its collectives will run "
                        f"over a private device grouping — rebuild the "
                        f"region over the global mesh axes",
                        user_source(eqn), axes=unknown))
        elif name in _COLLECTIVE_PRIMS:
            axes = eqn.params.get("axes",
                                  eqn.params.get("axis_name", ()))
            if not isinstance(axes, (tuple, list)):
                axes = (axes,)
            named = [a for a in axes if isinstance(a, str)]
            unknown = [a for a in named
                       if a not in declared and a not in bound]
            if unknown:
                key = (user_source(eqn), name, tuple(unknown))
                if key not in seen:
                    seen.add(key)
                    out.append(_diag(
                        pid,
                        f"'{name}' reduces over axis name(s) {unknown} "
                        f"declared by neither the global mesh "
                        f"({sorted(declared)}) nor any enclosing "
                        f"shard_map/pmap: the collective cannot bind — "
                        f"check the axis_name spelling against the mesh",
                        user_source(eqn), axes=unknown))
    return out


# ---------------------------------------------------------------------------
# dead-fetch
# ---------------------------------------------------------------------------

_EXPENSIVE_PRIMS = frozenset({
    "dot_general", "conv_general_dilated", "scan", "while", "sort",
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "cumsum",
    "cumlogsumexp", "pjit", "custom_vjp_call_jaxpr", "custom_jvp_call",
})
_DEAD_EQN_NOISE_FLOOR = 16


@register_pass("dead-fetch", severity=Severity.WARNING,
               doc="computed-but-unfetched outputs: dead subgraphs the "
                   "fetch list forgot")
def _dead_fetch(ctx: LintContext) -> List[Diagnostic]:
    pid = "dead-fetch"
    out: List[Diagnostic] = []
    # static Program view (Executor): op outputs nobody consumes, fetches
    # or persists — the op ran for nothing
    info = ctx.program_info
    if info is not None:
        consumed = set()
        for _, ins, _ in info.get("ops", ()):
            consumed.update(ins)
        keep = (set(info.get("fetches", ())) | set(info.get("written", ()))
                | set(info.get("persistable", ())))
        for op_type, _, outs in info.get("ops", ()):
            dead = [o for o in outs
                    if o not in consumed and o not in keep]
            if dead and len(dead) == len(outs):
                out.append(_diag(
                    pid,
                    f"op '{op_type}' computes {dead} but nothing consumes "
                    f"or fetches them: add them to fetch_list or drop the "
                    f"op from the program",
                    vars=dead, op=op_type))
        return out
    if ctx.closed_jaxpr is None:
        return out
    dead = dead_eqns(ctx.closed_jaxpr)
    if not dead:
        return out
    expensive = [e for e in dead if e.primitive.name in _EXPENSIVE_PRIMS]
    if not expensive and len(dead) < _DEAD_EQN_NOISE_FLOOR:
        return out                 # a couple of dead casts are noise
    head = expensive[0] if expensive else dead[0]
    out.append(_diag(
        pid,
        f"{len(dead)} equation(s) compute values that never reach an "
        f"output ({len(expensive)} expensive, e.g. "
        f"'{head.primitive.name}'): the work is compiled and executed "
        f"every step, then thrown away — fetch the result or delete the "
        f"computation",
        user_source(head), n_dead=len(dead),
        n_expensive=len(expensive)))
    return out


# ---------------------------------------------------------------------------
# sharding-coverage
# ---------------------------------------------------------------------------

@register_pass("sharding-coverage", severity=Severity.WARNING,
               doc="param leaves no partition rule matched while the mesh "
                   "has live model-parallel axes")
def _sharding_coverage(ctx: LintContext) -> List[Diagnostic]:
    out: List[Diagnostic] = []
    if ctx.partition_specs is None or ctx.params is None:
        return out
    mesh = ctx.mesh
    if mesh is None:
        from ..parallel.mesh import has_mesh, get_mesh
        if not has_mesh():
            return out
        mesh = get_mesh()
    from ..parallel.mesh import DP_AXIS
    live_model_axes = sorted(
        a for a, n in mesh.shape.items() if a != DP_AXIS and n > 1)
    if not live_model_axes:
        return out                  # pure-DP mesh: replicated is the rule
    pid = "sharding-coverage"
    for name in sorted(ctx.params):
        v = ctx.params[name]
        shape = tuple(getattr(v, "shape", ()))
        if len(shape) < 2 or int(np.prod(shape)) <= 1:
            continue                # scalars/vectors replicate by design
        spec = ctx.partition_specs.get(name)
        entries = tuple(spec) if spec is not None else ()
        if any(e is not None for e in entries):
            continue
        # name the autoshard rule that WOULD cover this leaf so the
        # warning is actionable (a matched pure-replication rule means
        # replication is the DECIDED layout for this role — no finding)
        rule = _autoshard_rule_for(name, shape)
        if rule is not None and not any(
                e is not None for e in tuple(rule.spec)):
            continue
        if rule is not None:
            from .autoshard import spec_repr
            hint = (f"; autoshard rule '{rule.role}' proposes "
                    f"{spec_repr(rule.spec)} — FLAGS_autoshard=apply "
                    f"closes this (=propose to review the plan first)")
        else:
            hint = ("; no autoshard rule matches — extend the "
                    "FLAGS_autoshard_rules table "
                    "(PartitionRules.with_overrides)")
        out.append(_diag(
            pid,
            f"parameter '{name}' {shape} matched no partition rule: it "
            f"replicates onto every device of the "
            f"{dict(mesh.shape)} mesh while model axes "
            f"{live_model_axes} are live — annotate it "
            f"(shard_parameter) or extend the partition rules "
            f"(match_partition_rules discipline: unmatched leaves are "
            f"a lint, not a silent default)" + hint,
            param=name, shape=shape,
            autoshard_rule=rule.role if rule is not None else None))
    return out


def _autoshard_rule_for(name, shape):
    """The active-table rule that would match one leaf (None when the
    table is unresolvable — sharding-coverage must not depend on a valid
    FLAGS_autoshard_rules value)."""
    try:
        from .autoshard import active_rules
        return active_rules().match(name, shape)
    except Exception:
        return None


# ---------------------------------------------------------------------------
# autoshard-conflict
# ---------------------------------------------------------------------------

@register_pass("autoshard-conflict", severity=Severity.ERROR,
               doc="a hand shard_parameter annotation contradicts the "
                   "active autoshard rules table")
def _autoshard_conflict(ctx: LintContext) -> List[Diagnostic]:
    """Fires when the rules engine and a hand annotation disagree about a
    parameter's layout.  Active when the compile site carries an
    autoshard plan (TrainStep under FLAGS_autoshard != off) or when
    autoshard is enabled and the context has params to re-derive one
    from; silent otherwise, so the pass costs nothing while the
    transform is off."""
    out: List[Diagnostic] = []
    plan = (ctx.extra or {}).get("autoshard_plan")
    if plan is None:
        from .autoshard import autoshard_enabled
        if not autoshard_enabled() or ctx.params is None:
            return out
        from .autoshard import propose
        plan = propose(ctx.params, mesh=ctx.mesh,
                       existing=ctx.partition_specs,
                       sources=(ctx.extra or {}).get("autoshard_sources"))
    from .autoshard import spec_repr
    pid = "autoshard-conflict"
    for e in plan.conflicts:
        out.append(_diag(
            pid,
            f"hand annotation {spec_repr(e.existing)} on parameter "
            f"'{e.name}' {tuple(e.shape)} contradicts autoshard rule "
            f"'{e.rule}' (table {e.table}) proposing "
            f"{spec_repr(e.spec)}: the rules engine and the model "
            f"disagree about this layout — delete the shard_parameter "
            f"call, or override the rule "
            f"(PartitionRules.with_overrides) so the table owns the "
            f"decision",
            param=e.name, shape=tuple(e.shape), rule=e.rule,
            table=e.table, hand=spec_repr(e.existing),
            proposed=spec_repr(e.spec)))
    return out
