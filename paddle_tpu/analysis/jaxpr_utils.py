"""Shared jaxpr-walking machinery for the lint passes.

The closed jaxpr is the TPU analogue of the reference's ProgramDesc graph
(framework/ir/graph.h): passes here never mutate it — they only *read*
equations, so one recursive walker serves every pass.  Nested program
structure (pjit bodies, scan/while/cond branches, shard_map regions,
custom-vjp subfunctions) is flattened by :func:`iter_eqns`, which also
tracks which collective axis names each region binds — the information the
collective-consistency pass needs.
"""
from __future__ import annotations

from typing import Iterator, List, Optional, Set, Tuple

import jax
from jax.extend.core import Literal


def user_source(eqn) -> Optional[str]:
    """``file.py:line (function)`` of the *user* frame that traced ``eqn``
    — jax's source_info filtered of framework/jax internals, so findings
    point at model code (operator.cc's ``Attr("op_callstack")`` analogue,
    but resolved to the outermost user frame)."""
    try:
        from jax._src import source_info_util
        frame = source_info_util.user_frame(eqn.source_info.traceback)
        if frame is None:
            return None
        return (f"{frame.file_name}:{frame.start_line}"
                f" ({frame.function_name})")
    except Exception:
        return None


def _sub_jaxprs(eqn):
    """Every jaxpr nested in an equation's params (pjit/scan/cond/
    shard_map/custom_vjp...), uniformly as open ``Jaxpr`` objects."""
    subs = []
    for v in eqn.params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for sub in vals:
            if hasattr(sub, "eqns"):            # open Jaxpr
                subs.append(sub)
            elif hasattr(sub, "jaxpr") and hasattr(sub.jaxpr, "eqns"):
                subs.append(sub.jaxpr)          # ClosedJaxpr
    return subs


def _bound_axis_names(eqn) -> Set[str]:
    """Axis names an equation's region binds for its body: a shard_map's
    mesh axes, a pmap's axis_name."""
    out: Set[str] = set()
    mesh = eqn.params.get("mesh")
    if mesh is not None and hasattr(mesh, "axis_names"):
        out.update(str(a) for a in mesh.axis_names)
    axis_name = eqn.params.get("axis_name")
    if isinstance(axis_name, str):
        out.add(axis_name)
    elif isinstance(axis_name, (tuple, list)):
        out.update(a for a in axis_name if isinstance(a, str))
    return out


def iter_eqns(closed_jaxpr, _bound: Optional[frozenset] = None
              ) -> Iterator[Tuple[object, frozenset]]:
    """Depth-first over every equation of ``closed_jaxpr`` including nested
    jaxprs.  Yields ``(eqn, bound_axes)`` where ``bound_axes`` is the set of
    collective axis names bound by the *enclosing* regions of that eqn."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    bound = _bound or frozenset()
    for eqn in jaxpr.eqns:
        yield eqn, bound
        subs = _sub_jaxprs(eqn)
        if subs:
            inner = bound | frozenset(_bound_axis_names(eqn))
            for sub in subs:
                yield from iter_eqns(sub, inner)


def iter_jaxprs(closed_jaxpr) -> Iterator[object]:
    """Depth-first over every (open) jaxpr: the top level plus each jaxpr
    nested in equation params — for passes that need per-level dataflow
    (var producers, constvars) rather than a flat equation stream."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    yield jaxpr
    for eqn in jaxpr.eqns:
        for sub in _sub_jaxprs(eqn):
            yield from iter_jaxprs(sub)


def all_avals(closed_jaxpr):
    """(invars, outvars) avals of the top-level jaxpr."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    return ([v.aval for v in jaxpr.invars],
            [getattr(v, "aval", None) for v in jaxpr.outvars])


def _is_dropvar(v) -> bool:
    return type(v).__name__ == "DropVar"


def dead_eqns(closed_jaxpr) -> List[object]:
    """Equations of the TOP-LEVEL jaxpr whose outputs reach no jaxpr output
    — computed, paid for, and thrown away (the reference's graph DCE pass
    would delete them; here we *report* them, because in a fetch-driven
    Executor they usually mean a fetch list forgot an output).

    Effectful equations (callbacks, asserts) are never dead.  The analysis
    is deliberately top-level only: nested jaxprs (scan bodies etc.) are
    DCE'd by jax itself at lowering and their liveness is relative to
    their own carry."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    live = {v for v in jaxpr.outvars if not isinstance(v, Literal)}
    # backwards sweep: an eqn is live iff any output is live (or it has
    # effects); its inputs then become live
    dead: List[object] = []
    for eqn in reversed(jaxpr.eqns):
        outs_live = any((not _is_dropvar(v)) and v in live
                        for v in eqn.outvars)
        if outs_live or getattr(eqn, "effects", None):
            for v in eqn.invars:
                if not isinstance(v, Literal):
                    live.add(v)
        else:
            dead.append(eqn)
    dead.reverse()
    return dead


def static_vars(jaxpr) -> Set[object]:
    """Vars of ``jaxpr`` that are functions of trace-time constants only
    (constvars and literals — one forward constant-propagation sweep).
    A dynamic_slice whose start index is in this set costs nothing extra:
    XLA folds it to a static slice; only genuinely traced offsets pay the
    cross-tile gather."""
    static: Set[object] = set(getattr(jaxpr, "constvars", ()))
    for eqn in jaxpr.eqns:
        if getattr(eqn, "effects", None):
            continue
        if all(isinstance(v, Literal) or v in static
               for v in eqn.invars):
            static.update(v for v in eqn.outvars
                          if type(v).__name__ != "DropVar")
    return static


def tile_pad_waste(dim: int, tile: int = 128) -> float:
    """Fraction of a VMEM/MXU tile wasted by padding ``dim`` up to the next
    multiple of ``tile`` (TPU minor dims tile to 128 lanes)."""
    if dim <= 0 or dim % tile == 0:
        return 0.0
    padded = ((dim + tile - 1) // tile) * tile
    return (padded - dim) / padded
