"""AST-based dygraph-to-static conversion.

Reference parity: python/paddle/fluid/dygraph/dygraph_to_static/ —
ast_transformer.py (DygraphToStaticAst, the 15-transformer pipeline),
ifelse_transformer.py, loop_transformer.py (for→while lowering),
break_continue_transformer.py (escape flags), return_transformer.py
(early-return flags), logical_transformer.py, and convert_operators.py
(convert_ifelse / convert_while_loop / convert_logical_and...).

TPU-shape: the reference rewrites Python control flow into
cond_op/while_op graph ops; here the same AST rewrite targets the
framework's ``ops.control_flow.cond`` / ``while_loop``, which lower to
``lax.cond`` / ``lax.while_loop`` under the jax trace — so a @to_static
function with data-dependent Python ``if``/``while`` compiles into real
XLA control flow instead of being silently frozen at trace time (the
round-1 gap).

Mechanics: branches/bodies become nested functions that mutate the
enclosing frame via ``nonlocal`` (the reference's get_args/set_args
scheme); the runtime converters snapshot + restore those locals around
each traced branch so both arms see the pre-branch state.
"""
from __future__ import annotations

import ast
import functools
import inspect
import textwrap

import jax
import jax.numpy as jnp

from ..framework.tensor import Tensor, unwrap
from ..ops import control_flow as _cf


class Dy2StaticError(RuntimeError):
    pass


# dy2static errors are precise user-facing diagnostics; op-provenance
# wrapping (enforce.op_context) must not bury them in ExternalError
from ..framework.enforce import register_passthrough  # noqa: E402
register_passthrough(Dy2StaticError)


def _is_traced(v):
    x = unwrap(v)
    return isinstance(x, jax.core.Tracer)


def _is_tensorish(v):
    return isinstance(v, Tensor) or isinstance(unwrap(v), jax.Array) \
        or _is_traced(v)


# -- runtime converters (convert_operators.py parity) ---------------------------

def _prep_list_carries(init):
    """Promote Python lists entering a traced region to their
    LoDTensorArray lowering (list_transformer.py parity): empty → an
    EmptyListCarry sentinel typed later by the aval probe; non-empty
    uniformly-shaped → BoundedTensorArray.  Non-tensor lists pass through
    (they keep plain-Python semantics, same as before)."""
    from ..framework.tensor_array import (BoundedTensorArray,
                                          EmptyListCarry)
    out = []
    for v in init:
        u = unwrap(v)
        if isinstance(u, list):
            if not u:
                out.append(EmptyListCarry())
                continue
            try:
                items = [jnp.asarray(unwrap(e)) for e in u]
                if _builtin_all(i.shape == items[0].shape and
                                i.dtype == items[0].dtype for i in items):
                    out.append(BoundedTensorArray.from_list(items))
                    continue
            except (TypeError, ValueError):
                pass
        out.append(v)
    return tuple(out)


def _as_carry(v):
    """Loop/cond carry leafing: tensor arrays ride as pytrees, everything
    else as an array."""
    from ..framework.tensor_array import (BoundedTensorArray,
                                          EmptyListCarry)
    u = unwrap(v)
    if isinstance(u, (BoundedTensorArray, EmptyListCarry)):
        return u
    return jnp.asarray(u)


def _is_list_carry(v):
    from ..framework.tensor_array import (BoundedTensorArray,
                                          EmptyListCarry)
    return isinstance(unwrap(v), (BoundedTensorArray, EmptyListCarry))


def _reconcile_branch_outputs(branches, init, set_args):
    """Both arms of a traced cond must produce the same pytree. Names first
    bound inside one arm start as None (create_undefined_var); where one arm
    yields None and the other an array, substitute zeros so the conditional
    carries a well-typed value — the reference's RETURN_NO_VALUE scheme. The
    value is only observed when the matching flag says the arm ran.
    Returns wrapped branch fns, or the originals when reconciliation is
    unnecessary/impossible."""
    from ..framework.tensor_array import BoundedTensorArray, EmptyListCarry
    if not _builtin_any(unwrap(v) is None or
                        isinstance(unwrap(v), EmptyListCarry)
                        for v in init):
        # reconciliation is only ever needed for branch-first-bound names
        # (start as None) or still-untyped empty lists — skip the double
        # trace otherwise
        return branches
    try:
        avals = []
        for run in branches:
            avals.append(jax.eval_shape(run))
            set_args(init)          # clear eval_shape tracers from the frame
    except Exception:
        return branches
    a, b = avals
    if len(a) != len(b):
        return branches

    def _holey(x):
        return x is None or isinstance(x, EmptyListCarry)

    need = [_holey(x) != _holey(y) for x, y in zip(a, b)]
    if not _builtin_any(need):
        return branches
    merged = [x if not _holey(x) else y for x, y in zip(a, b)]

    def _fill_hole(m):
        if isinstance(m, BoundedTensorArray):
            # one arm appended, the other didn't: the no-append arm yields
            # the same-typed EMPTY array
            return BoundedTensorArray(
                jnp.zeros(m.buffer.shape, m.buffer.dtype),
                jnp.asarray(0, jnp.int32))
        return jnp.zeros(m.shape, m.dtype)

    def wrap(run):
        def go():
            out = run()
            return tuple(
                _fill_hole(m) if _holey(v) and n else v
                for v, m, n in zip(out, merged, need))
        return go

    return [wrap(r) for r in branches]


_builtin_any = any
_builtin_all = all


def convert_ifelse(pred, true_fn, false_fn, get_args, set_args):
    """convert_operators.py convert_ifelse: run both branches under
    lax.cond when pred is a traced Tensor; plain Python branch otherwise."""
    if _is_traced(pred):
        try:
            init = _prep_list_carries(get_args())
        except (NameError, UnboundLocalError) as e:
            raise Dy2StaticError(
                "variables assigned inside a Tensor-dependent `if` must be "
                f"initialized before it ({e})") from e

        def _branch(fn):
            def run():
                set_args(init)
                fn()
                return tuple(unwrap(v) for v in get_args())
            return run

        _converter_depth[0] += 1
        try:
            tb, fb = _reconcile_branch_outputs(
                [_branch(true_fn), _branch(false_fn)], init, set_args)
            out = _cf.cond(pred, tb, fb)
        finally:
            _converter_depth[0] -= 1
        out = out if isinstance(out, (tuple, list)) else (out,)
        _check_ta_overflow(out)
        set_args(tuple(out))
        return
    if bool(unwrap(pred)):
        true_fn()
    else:
        false_fn()


def convert_while_loop(cond_fn, body_fn, get_args, set_args):
    """convert_operators.py convert_while_loop: lax.while_loop when the
    condition is traced; Python while otherwise."""
    first = cond_fn()
    if _is_traced(first):
        try:
            init = _prep_list_carries(
                tuple(unwrap(v) for v in get_args()))
        except (NameError, UnboundLocalError) as e:
            raise Dy2StaticError(
                "loop variables of a Tensor-dependent `while` must be "
                f"initialized before it ({e})") from e

        def c(vals):
            set_args(vals)
            return jnp.reshape(unwrap(cond_fn()), ()).astype(bool)

        def b(vals):
            set_args(vals)
            body_fn()
            return tuple(_as_carry(v) for v in get_args())

        _converter_depth[0] += 1
        try:
            out = _traced_while(c, b, init, set_args)
        finally:
            _converter_depth[0] -= 1
        _check_ta_overflow(out)
        set_args(tuple(out))
        return
    while True:
        try:
            go = bool(unwrap(cond_fn()))
        except jax.errors.TracerBoolConversionError as e:
            raise Dy2StaticError(
                "the loop condition became tensor-dependent only after the "
                "loop started (e.g. a Tensor `break` inside a Python-bound "
                "loop); make the loop bound a Tensor (paddle.arange / "
                "paddle.to_tensor) so the whole loop is traced") from e
        if not go:
            break
        body_fn()


def _traced_while(c, b, init, set_args):
    """Type the carry (probing body-bound names) and run lax.while_loop —
    the traced leg of convert_while_loop, split out so the converter can
    scope the overflow-depth bookkeeping around every body trace (probes
    included)."""
    from ..framework.tensor_array import (BoundedTensorArray,
                                          EmptyListCarry)
    if _builtin_any(v is None or isinstance(v, EmptyListCarry)
                    for v in init):
        # a carry first bound inside the body (lowered for-loop target,
        # __pt_rv of an in-loop return, escape flags) starts as None;
        # discover the body's output aval by probing and seed typed
        # zeros — sound because the body writes such a carry before any
        # read. The probe is a small fixpoint: placeholder dtypes are
        # cycled and refined from the observed body output, since a
        # wrong placeholder dtype makes the body's own cond branches
        # disagree before we can see the real aval.
        fill = {i: None for i, v in enumerate(init) if v is None}

        def mk_probe():
            return tuple(
                (jnp.zeros(fill[i].shape, fill[i].dtype)
                 if fill.get(i) is not None
                 else jnp.zeros((), dt)) if i in fill
                else _as_carry(v)
                for i, v in enumerate(init))

        avals = None
        last_err = None
        for dt in (jnp.float32, jnp.int32, jnp.bool_):
            for _refine in range(3):
                try:
                    avals = jax.eval_shape(b, mk_probe())
                except Exception as e:
                    last_err = e
                    avals = None
                    break
                stable = _builtin_all(
                    fill[i] is not None
                    and (fill[i].shape, fill[i].dtype)
                    == (avals[i].shape, avals[i].dtype)
                    for i in fill) if fill else True
                for i in fill:
                    fill[i] = avals[i]
                if stable:
                    break
            if avals is not None:
                break
            fill = {i: None for i in fill}
        if avals is None:
            raise Dy2StaticError(
                "could not type a loop variable that is first assigned "
                "inside a Tensor-dependent loop; initialize it before "
                f"the loop ({last_err})") from last_err
        set_args(init)      # clear probe tracers from the frame

        def _seed(v, a):
            if v is None:
                return jnp.zeros(a.shape, a.dtype)
            if isinstance(v, EmptyListCarry) and \
                    isinstance(a, BoundedTensorArray):
                # the body appended to this empty list: seed the typed
                # empty BoundedTensorArray the probe discovered
                return BoundedTensorArray(
                    jnp.zeros(a.buffer.shape, a.buffer.dtype),
                    jnp.asarray(0, jnp.int32))
            return v

        init = tuple(_seed(v, a) for v, a in zip(init, avals))
    return jax.lax.while_loop(c, b, init)


def convert_logical_and(x_fn, y_fn):
    x = x_fn()
    if _is_tensorish(x):
        from ..ops import logical_and
        return logical_and(x, y_fn())
    return x and y_fn()


def convert_logical_or(x_fn, y_fn):
    x = x_fn()
    if _is_tensorish(x):
        from ..ops import logical_or
        return logical_or(x, y_fn())
    return x or y_fn()


def convert_logical_not(x):
    if _is_tensorish(x):
        from ..ops import logical_not
        return logical_not(x)
    return not x


# -- iteration helpers (loop_transformer.py parity) -----------------------------

class _RangeProxy:
    """range() whose bounds may be traced Tensors: indexable arithmetic
    stand-in so a for-over-range with a Tensor bound lowers to
    lax.while_loop instead of crashing in range().__init__."""

    def __init__(self, start, stop=None, step=None):
        if stop is None:
            start, stop = 0, start
        if step is None:
            step = 1
        self.start, self.stop, self.step = start, stop, step

    def length(self):
        s0, s1, st = (unwrap(self.start), unwrap(self.stop),
                      unwrap(self.step))
        n = (s1 - s0 + st - jnp.sign(st)) // st
        return jnp.maximum(n, 0)

    def getitem(self, i):
        return self.start + unwrap(i) * self.step


def convert_range(*args):
    vals = [unwrap(a) for a in args]
    if _builtin_any(isinstance(v, jax.core.Tracer) for v in vals):
        return _RangeProxy(*vals)
    return range(*(int(v) for v in vals))


class _LazySeq:
    """Pull-on-demand adapter giving a lazy iterable (generator, stream,
    DataLoader) positional getitem without materializing it. The lowered
    loop accesses indices monotonically, so consumed elements are evicted
    (base-offset window): an infinite generator with a break never hangs
    and a long epoch holds O(1) elements, not the whole stream."""

    def __init__(self, it):
        self._it = iter(it)
        self._buf = []
        self._base = 0
        self._done = False

    def has(self, i):
        i = int(i)
        if i > self._base:
            # monotonic consumption: everything before i is dead
            drop = min(i - self._base, len(self._buf))
            del self._buf[:drop]
            self._base += drop
        while self._base + len(self._buf) <= i and not self._done:
            try:
                self._buf.append(next(self._it))
            except StopIteration:
                self._done = True
        return i - self._base < len(self._buf)

    def get(self, i):
        self.has(i)
        return self._buf[int(i) - self._base]


def convert_indexable(x):
    """Normalize a for-loop iterable for the indexed-while lowering.
    Positionally-indexable things (and mappings, whose KEY list is sized
    and cheap) pass through; lazy iterables wrap in _LazySeq — never
    list()'d up front."""
    import collections.abc
    if isinstance(x, (_RangeProxy, range, list, tuple)):
        return x
    if _is_tensorish(x):
        return x
    if isinstance(x, collections.abc.Mapping):
        return list(x)               # iterate by key, like Python
    if hasattr(x, "__len__") and hasattr(x, "__getitem__"):
        return x
    return _LazySeq(x)


def convert_more(x, i):
    """Loop-continuation test for the lowered for: is there an i-th
    element? Traced-length iterables return a traced bool (lax.while_loop
    path); _LazySeq pulls and answers in Python."""
    if isinstance(x, _LazySeq):
        return x.has(i)
    n = convert_len(x)
    return unwrap(i) < n


def convert_list_append(l, x):
    """list_transformer.py parity: ``l.append(x)`` rebinds functionally.
    Plain Python lists keep eager append semantics (dygraph parity);
    lists promoted into the BoundedTensorArray carry grow their traced
    size; an untyped EmptyListCarry materializes on first append."""
    from ..framework.tensor_array import (BoundedTensorArray,
                                          EmptyListCarry)
    if isinstance(l, BoundedTensorArray):
        out = l.append(jnp.asarray(unwrap(x)))
        # a concrete overflow flag (straight-line appends) raises right
        # here at trace time; a traced one is checked at the loop/cond
        # exit (_check_ta_overflow)
        if not isinstance(out.ovf, jax.core.Tracer) and bool(out.ovf):
            raise Dy2StaticError(_ta_overflow_msg(out.capacity))
        return out
    if isinstance(l, EmptyListCarry):
        xa = jnp.asarray(unwrap(x))
        return BoundedTensorArray.empty_like_elem(xa).append(xa)
    l.append(x)
    return l


def convert_len(x):
    from ..framework.tensor_array import (BoundedTensorArray,
                                          EmptyListCarry)
    if isinstance(x, BoundedTensorArray):
        from ..framework.tensor import Tensor
        return Tensor(x.size)
    if isinstance(x, EmptyListCarry):
        return 0
    if isinstance(x, _RangeProxy):
        return x.length()
    if _is_tensorish(x):
        u = unwrap(x)
        if u.ndim == 0:
            raise Dy2StaticError("cannot iterate over a 0-d Tensor")
        return u.shape[0]
    return len(x)


def convert_getitem(x, i):
    from ..framework.tensor_array import BoundedTensorArray
    if isinstance(x, BoundedTensorArray):
        return x[unwrap(i)]           # -> Tensor (dynamic index)
    if isinstance(x, _LazySeq):
        return x.get(i)
    if isinstance(x, _RangeProxy):
        return x.getitem(i)
    iv = unwrap(i)
    if isinstance(x, range):
        if isinstance(iv, jax.core.Tracer):
            return x.start + iv * x.step
        return x[int(iv)]
    if _is_tensorish(x):
        return x[i]
    if isinstance(iv, jax.core.Tracer):
        try:
            return jnp.asarray(x)[iv]
        except Exception as e:
            raise Dy2StaticError(
                "a Python list/tuple cannot be indexed by a traced loop "
                "counter; convert it to a Tensor first") from e
    return x[int(iv)]


def _concrete_bound(v):
    """A non-traced slice bound as the plain-python value x[a:b] expects."""
    if v is None or isinstance(v, int):
        return v
    u = unwrap(v) if _is_tensorish(v) else v
    return int(u) if hasattr(u, "shape") else u


def convert_slice(x, lo, up, st, size=None):
    """slice_transformer parity: ``x[lo:up]`` where a bound may be a
    traced loop carry.  Static bounds keep exact Python semantics; traced
    bounds lower to lax.dynamic_slice with the SYNTACTICALLY derived
    window size (the AST pass recognizes ``x[i:i+k]`` / ``x[k+i:i]``-
    shaped pairs) — the reference's slice_op.cc StartsTensor: runtime
    starts, static extent."""
    if not (_is_traced(lo) or _is_traced(up)):
        return x[slice(_concrete_bound(lo), _concrete_bound(up),
                       _concrete_bound(st))]
    if st is not None and _concrete_bound(st) != 1:
        raise Dy2StaticError(
            "a traced-bound slice must be contiguous (step 1)")
    if size is None or _is_traced(size):
        raise Dy2StaticError(
            "slice bounds derived from a traced value need a statically-"
            "derivable window size: write x[i:i+k] (or x[i-k:i]) with a "
            "constant k so the extent is known at trace time "
            "(slice_op.cc StartsTensor semantics)")
    from ..ops.manipulation import dynamic_slice
    size = int(size)
    if _is_tensorish(x):
        return dynamic_slice(x, lo, size, axis=0)
    return jax.lax.dynamic_slice_in_dim(jnp.asarray(x), unwrap(lo), size,
                                        axis=0)


def convert_setslice(x, lo, up, st, value, size=None):
    """``x[lo:up] = value`` as a functional rebind (the AST pass emits
    ``x = _jst_setslice(...)``), so a traced start lowers to
    lax.dynamic_update_slice and the write survives inside lowered
    control flow."""
    if not (_is_traced(lo) or _is_traced(up)):
        x[slice(_concrete_bound(lo), _concrete_bound(up),
                _concrete_bound(st))] = value
        return x
    if st is not None and _concrete_bound(st) != 1:
        raise Dy2StaticError(
            "a traced-bound slice must be contiguous (step 1)")
    if size is None or _is_traced(size):
        raise Dy2StaticError(
            "slice bounds derived from a traced value need a statically-"
            "derivable window size: write x[i:i+k] = v with a constant k "
            "(set_value_op StartsTensorList semantics)")
    from ..framework.tensor import Tensor
    from ..ops.manipulation import dynamic_update_slice
    size = int(size)
    xv = unwrap(x)
    vv = jnp.broadcast_to(jnp.asarray(unwrap(value), xv.dtype),
                          (size,) + xv.shape[1:])
    if _is_tensorish(x):
        return dynamic_update_slice(x, Tensor(vv), lo, axis=0)
    return jax.lax.dynamic_update_slice_in_dim(jnp.asarray(xv), vv,
                                               unwrap(lo), axis=0)


_cb_verdict = []   # memo: [bool] once probed OUTSIDE any trace


def _host_callbacks_supported() -> bool:
    """Whether the default backend can run host callbacks inside compiled
    programs (a PJRT backend may refuse host send/recv callbacks).
    Probed once with a tiny jitted program.

    Trace guard: the first probe can fire INSIDE a trace (a nested
    @to_static function is first called while its caller is being traced,
    so ast_transform's pre-warm runs lazily then).  Inside a trace the
    probe's jit would be STAGED into the enclosing jaxpr instead of
    executed — no exception at trace time → a false 'supported' verdict
    AND the probe's own callback inlined into the user's program, which a
    callback-less backend then rejects at runtime.  So inside a trace:
    answer a conservative False (the fetched-flag fallback is correct on
    every backend) WITHOUT caching; the verdict is only memoized when
    probed cleanly."""
    if _cb_verdict:
        return _cb_verdict[0]
    try:
        from jax._src import core as _src_core
        if not _src_core.trace_state_clean():
            return False   # uncached: re-probe next time outside a trace
    except Exception:
        pass
    try:
        def probe(x):
            jax.debug.callback(lambda: None)
            return x + 1
        # block: the UNIMPLEMENTED error surfaces at execution, not trace
        jax.block_until_ready(jax.jit(probe)(jnp.zeros(())))
        _cb_verdict.append(True)
    except Exception:
        _cb_verdict.append(False)
    return _cb_verdict[0]


_assert_frames = []   # trace-local stacks of (flag, msg) collected per trace
_frame_depths = []    # converter nesting depth at each frame's open
_converter_depth = [0]   # live traced-converter (loop/cond) nesting


def push_assert_frame():
    """Open a collection frame for fallback assert flags (StaticFunction
    traces its body inside one; see jit/__init__.py _concrete.pure)."""
    _assert_frames.append([])
    _frame_depths.append(_converter_depth[0])


def pop_assert_frame():
    _frame_depths.pop()
    return _assert_frames.pop()


def _record_assert_flag(cond, msg) -> bool:
    """Fallback for backends without host callbacks: materialize the
    condition as an extra (fetchable) program output; the StaticFunction
    wrapper checks it host-side after execution and raises.  Returns False
    when no frame is open (a bare jit outside @to_static)."""
    if not _assert_frames:
        return False
    _assert_frames[-1].append((jnp.all(cond), msg))
    return True


def _ta_overflow_msg(cap):
    return (f"list append exceeded the tensor array capacity ({cap}); "
            f"raise it with paddle.jit.set_tensor_array_capacity")


def _check_ta_overflow(vals):
    """Route BoundedTensorArray capacity overflow through the fetched-
    assert channel so it raises host-side instead of passing as a silent
    last-slot overwrite.  A concrete flag raises at trace time; a traced
    flag (an append inside a loop/cond body) is recorded where the carry
    re-enters the frame's own trace level — recording at a deeper level
    would leak an inner-trace tracer into the fetch frame, so nested
    converters skip here and the flag rides the enclosing carry to the
    next exit (depth bookkeeping: _converter_depth vs _frame_depths)."""
    from ..framework.tensor_array import BoundedTensorArray
    for v in vals:
        u = unwrap(v)
        if not isinstance(u, BoundedTensorArray):
            continue
        ovf = u.ovf
        if isinstance(ovf, jax.core.Tracer):
            if _assert_frames and _converter_depth[0] == _frame_depths[-1]:
                _record_assert_flag(jnp.logical_not(ovf),
                                    _ta_overflow_msg(u.capacity))
        elif bool(ovf):
            raise Dy2StaticError(_ta_overflow_msg(u.capacity))


def convert_assert(cond, msg=None):
    """assert_transformer.py parity.  A traced condition becomes an
    IN-GRAPH check — a host callback that raises when the runtime value is
    falsy (the reference lowers to assert_op.cc, which prints and aborts);
    eager conditions keep Python assert semantics.  The message expression
    is evaluated eagerly either way (it was already rewritten into the
    converter call).

    Backends without host-callback support (see
    ``_host_callbacks_supported``) fall back to a FETCHED flag: the
    condition rides out of the compiled program as an extra output and the
    StaticFunction wrapper raises host-side after the run — asserts still
    fail there, one step later than a host callback would."""
    import numpy as np
    c = unwrap(cond) if _is_tensorish(cond) else cond
    if _is_traced(cond):
        if not _host_callbacks_supported():
            if _record_assert_flag(c, msg):
                return
            import warnings
            warnings.warn(
                "@to_static assert on a traced value cannot be checked at "
                "runtime on this backend (no host-callback support) and no "
                "fetch frame is open; the assert is skipped",
                RuntimeWarning, stacklevel=2)
            return

        def _chk(v):
            if not bool(np.all(v)):
                raise AssertionError(
                    msg if msg is not None
                    else "Assert failed inside @to_static graph")
        jax.debug.callback(_chk, c)
        return
    if not bool(np.all(np.asarray(c))):
        if msg is not None:
            raise AssertionError(msg)
        raise AssertionError()


def convert_print(*args, sep=" ", end="\n", **kw):
    """print_transformer.py parity: printing a traced intermediate prints
    the RUNTIME value when the program executes (a host callback running
    builtin print, so sep/end/file/flush keep their semantics); all-eager
    prints stay builtin print.  Backends without host-callback support
    print the abstract value at trace time instead (the reference's
    static-mode print shows the Variable desc)."""
    if any(_is_traced(a) for a in args):
        vals = [unwrap(a) if _is_tensorish(a) else a for a in args]
        if not _host_callbacks_supported():
            shown = [f"Tensor(shape={list(v.shape)}, dtype={v.dtype})"
                     if isinstance(v, jax.core.Tracer) else v
                     for v in vals]
            print(*shown, sep=sep, end=end, **kw)
            return
        # only array-valued positions travel through the callback;
        # static values (strings, ints) ride the closure
        arr_idx = [i for i, v in enumerate(vals)
                   if isinstance(v, (jax.Array, jax.core.Tracer))]

        def show(*arrs):
            out = list(vals)
            for i, a in zip(arr_idx, arrs):
                out[i] = a
            print(*out, sep=sep, end=end, **kw)

        jax.debug.callback(show, *[vals[i] for i in arr_idx])
    else:
        print(*args, sep=sep, end=end, **kw)


def _make_cast(py_type, dtype):
    def convert_cast(x):
        """cast_transformer.py parity: int/float/bool on a tensor becomes
        a dtype cast instead of a trace-time concretization error."""
        if _is_tensorish(x):
            from .. import ops
            return ops.cast(x, dtype)
        return py_type(x)
    return convert_cast


convert_int = _make_cast(int, "int64")
convert_float = _make_cast(float, "float32")
convert_bool = _make_cast(bool, "bool")


_JST = {
    "_jst_ifelse": convert_ifelse,
    "_jst_while": convert_while_loop,
    "_jst_append": convert_list_append,
    "_jst_and": convert_logical_and,
    "_jst_or": convert_logical_or,
    "_jst_not": convert_logical_not,
    "_jst_range": convert_range,
    "_jst_indexable": convert_indexable,
    "_jst_more": convert_more,
    "_jst_len": convert_len,
    "_jst_getitem": convert_getitem,
    "_jst_slice": convert_slice,
    "_jst_setslice": convert_setslice,
    "_jst_assert": convert_assert,
    "_jst_print": convert_print,
    "_jst_int": convert_int,
    "_jst_float": convert_float,
    "_jst_bool": convert_bool,
}


# -- AST transformer ------------------------------------------------------------

def _assigned_names(nodes):
    """Names bound (Store ctx) in a statement list, excluding nested
    function/class scopes."""
    names = []

    class V(ast.NodeVisitor):
        # function/class defs neither descend (new scope) nor count as
        # branch outputs: a def is not a lax.cond-carriable value (and the
        # transformer's own __pt_* helpers must never become loop vars)
        def visit_FunctionDef(self, node):
            pass

        def visit_AsyncFunctionDef(self, node):
            pass

        def visit_ClassDef(self, node):
            pass

        def visit_Name(self, node):
            if isinstance(node.ctx, ast.Store):
                names.append(node.id)

    v = V()
    for n in nodes:
        v.visit(n)
    out = []
    for n in names:
        if n not in out:
            out.append(n)
    return out


def _has_escape(nodes):
    """True if the statement list contains a return, or a break/continue
    that would escape the branch (break/continue inside a nested loop
    belong to that loop and are fine)."""
    found = False

    def walk(n, in_loop):
        nonlocal found
        if found:
            return
        if isinstance(n, ast.Return):
            found = True
            return
        if isinstance(n, (ast.Break, ast.Continue)) and not in_loop:
            found = True
            return
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda, ast.ClassDef)):
            return
        nested = in_loop or isinstance(n, (ast.For, ast.AsyncFor,
                                           ast.While))
        for c in ast.iter_child_nodes(n):
            walk(c, nested)

    for n in nodes:
        walk(n, False)
    return found


RET_FLAG = "__pt_ret"
RET_VAL = "__pt_rv"


def _assigns_name(nodes, name):
    """True if any statement in ``nodes`` (excluding nested def/class
    scopes) binds ``name``."""
    todo = list(nodes)
    while todo:
        n = todo.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.ClassDef, ast.Lambda)):
            continue
        if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Store) \
                and n.id == name:
            return True
        todo.extend(ast.iter_child_nodes(n))
    return False


def _not_flags_test(flags):
    src = " and ".join(f"(not {f})" for f in flags)
    return ast.parse(src, mode="eval").body


def _guard_stmts(stmts, flags):
    """break_continue_transformer.py guard scheme: after any statement that
    may set one of ``flags``, wrap the remainder of the list in
    ``if not flag...:`` so setting a flag skips the rest. Recurses into
    every compound statement with linear bodies (if/with/try) so a flag set
    inside one also skips that block's own remainder."""
    out = []
    for idx, s in enumerate(stmts):
        if isinstance(s, ast.If):
            s = ast.If(test=s.test, body=_guard_stmts(s.body, flags),
                       orelse=_guard_stmts(s.orelse, flags))
        elif isinstance(s, (ast.With, ast.AsyncWith)):
            s = type(s)(items=s.items, body=_guard_stmts(s.body, flags))
        elif isinstance(s, ast.Try):
            s = ast.Try(
                body=_guard_stmts(s.body, flags),
                handlers=[ast.ExceptHandler(
                    type=h.type, name=h.name,
                    body=_guard_stmts(h.body, flags)) for h in s.handlers],
                orelse=_guard_stmts(s.orelse, flags),
                finalbody=_guard_stmts(s.finalbody, flags))
        out.append(s)
        if _builtin_any(_assigns_name([s], f) for f in flags) \
                and idx + 1 < len(stmts):
            rest = _guard_stmts(stmts[idx + 1:], flags)
            out.append(ast.If(test=_not_flags_test(flags), body=rest,
                              orelse=[]))
            break
    return out


class _ForToWhile(ast.NodeTransformer):
    """loop_transformer.py parity: lower ``for`` to an indexed ``while`` so
    the while machinery (and lax.while_loop for traced bounds) applies. The
    counter increments BEFORE the body so a later ``continue`` transform
    cannot skip it."""

    def __init__(self):
        self._n = 0
        self.count = 0
        self._entered = False

    def visit_FunctionDef(self, node):
        # transform the outermost def only; nested defs keep their own
        # semantics
        if self._entered:
            return node
        self._entered = True
        self.generic_visit(node)
        return node

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_For(self, node):
        self.generic_visit(node)
        if node.orelse:
            return node      # for-else keeps Python semantics
        self._n += 1
        self.count += 1
        u = self._n
        it, i = f"__pt_it_{u}", f"__pt_i_{u}"
        iter_expr = node.iter
        if (isinstance(iter_expr, ast.Call)
                and isinstance(iter_expr.func, ast.Name)
                and iter_expr.func.id == "range"):
            iter_expr = ast.Call(
                func=ast.Name(id="_jst_range", ctx=ast.Load()),
                args=iter_expr.args, keywords=iter_expr.keywords)
        # single-body lowering: the continuation test _jst_more() speaks
        # both protocols (positional len for indexed/traced iterables,
        # buffered pull for lazy ones), so the body is emitted ONCE — a
        # dual indexed/lazy dispatch would copy it 2^depth times for
        # nested loops
        pre = ast.parse(f"{it} = _jst_indexable(None)\n{i} = 0").body
        pre[0].value.args = [iter_expr]
        tgt = ast.Assign(
            targets=[node.target],
            value=ast.parse(f"_jst_getitem({it}, {i})", mode="eval").body)
        inc = ast.parse(f"{i} = {i} + 1").body[0]
        test = ast.parse(f"_jst_more({it}, {i})", mode="eval").body
        return pre + [ast.While(test=test, body=[tgt, inc] + node.body,
                                orelse=[])]


class _ReturnTransformer(ast.NodeTransformer):
    """return_transformer.py parity: every ``return X`` becomes
    ``__pt_rv = X; __pt_ret = True`` (+ ``break`` inside a loop); the
    function tail returns ``__pt_rv``. Guarding + loop-condition
    augmentation happen in _guard_stmts/_LoopEscapeTransformer."""

    def __init__(self):
        self.count = 0
        self._depth = 0

    def visit_FunctionDef(self, node):
        return node

    visit_AsyncFunctionDef = visit_FunctionDef

    def _visit_list(self, stmts):
        out = []
        for s in stmts:
            r = self.visit(s)
            out.extend(r if isinstance(r, list) else [r])
        return out

    def _visit_loop(self, node):
        # break/continue are only legal in the loop BODY — the orelse runs
        # at the enclosing depth, so a return there must not emit a break
        self._depth += 1
        node.body = self._visit_list(node.body)
        self._depth -= 1
        node.orelse = self._visit_list(node.orelse)
        return node

    visit_For = _visit_loop
    visit_While = _visit_loop

    def visit_Return(self, node):
        self.count += 1
        stmts = []
        if node.value is not None:
            asg = ast.parse(f"{RET_VAL} = 0").body[0]
            asg.value = node.value
            stmts.append(asg)
        else:
            stmts.append(ast.parse(f"{RET_VAL} = None").body[0])
        stmts.append(ast.parse(f"{RET_FLAG} = True").body[0])
        if self._depth > 0:
            stmts.append(ast.Break())
        return stmts

    def run(self, fdef):
        """Transform unless the only return is a single tail statement."""
        rets = []
        todo = list(fdef.body)
        while todo:
            n = todo.pop()
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef, ast.Lambda)):
                continue
            if isinstance(n, ast.Return):
                rets.append(n)
            todo.extend(ast.iter_child_nodes(n))
        if not rets or (len(rets) == 1 and fdef.body
                        and fdef.body[-1] is rets[0]):
            return False
        fdef.body = [self.visit(s) if not isinstance(s, list) else s
                     for s in fdef.body]
        # visit() may return lists; flatten
        flat = []
        for s in fdef.body:
            flat.extend(s if isinstance(s, list) else [s])
        fdef.body = flat
        return True


class _LoopEscapeTransformer(ast.NodeTransformer):
    """break_continue_transformer.py parity: rewrite a loop's own
    break/continue into flag assignments, guard trailing statements, and
    fold the flags (plus the function-level return flag when the body sets
    it) into the loop condition."""

    class _Replacer(ast.NodeTransformer):
        def __init__(self, brk, cont):
            self.brk, self.cont = brk, cont
            self.found_brk = self.found_cont = False

        def _stop(self, node):
            return node

        visit_While = _stop
        visit_For = _stop
        visit_FunctionDef = _stop
        visit_AsyncFunctionDef = _stop
        visit_ClassDef = _stop

        def visit_Break(self, node):
            self.found_brk = True
            return ast.parse(f"{self.brk} = True").body[0]

        def visit_Continue(self, node):
            self.found_cont = True
            return ast.parse(f"{self.cont} = True").body[0]

    def __init__(self):
        self._n = 0
        self.count = 0
        self._entered = False

    def visit_FunctionDef(self, node):
        if self._entered:
            return node
        self._entered = True
        self.generic_visit(node)
        return node

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_While(self, node):
        self.generic_visit(node)     # inner loops first
        self._n += 1
        u = self._n
        brk, cont = f"__pt_brk_{u}", f"__pt_cont_{u}"
        rep = self._Replacer(brk, cont)
        body = [rep.visit(s) for s in node.body]
        has_ret = _assigns_name(body, RET_FLAG)
        if not rep.found_brk and not rep.found_cont and not has_ret:
            return node
        self.count += 1
        cond_flags = ([brk] if rep.found_brk else []) \
            + ([RET_FLAG] if has_ret else [])
        guard_flags = cond_flags + ([cont] if rep.found_cont else [])
        body = _guard_stmts(body, guard_flags)
        if rep.found_cont:
            body = [ast.parse(f"{cont} = False").body[0]] + body
        test = node.test
        if cond_flags:
            test = ast.BoolOp(op=ast.And(),
                              values=[_not_flags_test(cond_flags),
                                      node.test])
        pre = []
        if rep.found_brk:
            pre.append(ast.parse(f"{brk} = False").body[0])
        out = pre + [ast.While(test=test, body=body, orelse=[])]
        if node.orelse:
            # while-else runs iff the loop exited without break/return;
            # with the flag scheme that is exactly "no flag set"
            if cond_flags:
                out.append(ast.If(test=_not_flags_test(cond_flags),
                                  body=list(node.orelse), orelse=[]))
            else:       # only continues: the else always runs
                out.extend(node.orelse)
        return out


def _is_generator_def(node):
    """Yield/YieldFrom in THIS def's own scope (not in defs nested inside)."""
    todo = list(node.body)
    while todo:
        n = todo.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda)):
            continue
        if isinstance(n, (ast.Yield, ast.YieldFrom)):
            return True
        todo.extend(ast.iter_child_nodes(n))
    return False


class _ControlFlowTransformer(ast.NodeTransformer):
    """Rewrite if/while into converter calls (ifelse_transformer.py /
    loop_transformer.py). Generator defs are skipped — hoisting a while
    body containing ``yield`` into a converter body_fn would make it a
    generator function that never executes; ordinary nested closures DO
    get converted (they trace like any code when called)."""

    def __init__(self):
        self._n = 0

    def visit_FunctionDef(self, node):
        if _is_generator_def(node):
            return node
        self.generic_visit(node)
        return node

    visit_AsyncFunctionDef = visit_FunctionDef

    def _uid(self):
        self._n += 1
        return self._n

    # -- helpers (build nodes from parsed templates so every field the
    # running Python version requires — e.g. 3.12's type_params — is set)
    def _fn_def(self, name, body, nonlocals):
        f = ast.parse(f"def {name}():\n    pass").body[0]
        stmts = []
        if nonlocals:
            stmts.append(ast.Nonlocal(names=list(nonlocals)))
        stmts.extend(body)
        f.body = stmts or [ast.Pass()]
        return f

    def _getter(self, name, names):
        tup = ", ".join(names)
        src = f"def {name}():\n    return ({tup}{',' if names else ''})"
        return ast.parse(src).body[0]

    def _setter(self, name, names):
        if names:
            tup = ", ".join(names)
            src = (f"def {name}(__pt_vals):\n"
                   f"    nonlocal {tup}\n"
                   f"    ({tup},) = __pt_vals")
        else:
            src = f"def {name}(__pt_vals):\n    pass"
        return ast.parse(src).body[0]

    @staticmethod
    def _initializers(names):
        """Guarantee an enclosing-scope binding for every branch-assigned
        name (ifelse_transformer's create_undefined_var): names already
        bound keep their value; names first bound inside the branch start
        as None."""
        stmts = []
        for n in names:
            src = (f"try:\n    {n}\n"
                   f"except (NameError, UnboundLocalError):\n"
                   f"    {n} = None")
            stmts.extend(ast.parse(src).body)
        return stmts

    # -- boolean operators in conditions --------------------------------------
    @staticmethod
    def _lambda_of(expr):
        lam = ast.parse("lambda: 0", mode="eval").body
        lam.body = expr
        return lam

    def _convert_bool_ops(self, node):
        if isinstance(node, ast.BoolOp):
            fn = "_jst_and" if isinstance(node.op, ast.And) else "_jst_or"
            out = self._convert_bool_ops(node.values[-1])
            for v in reversed(node.values[:-1]):
                out = ast.Call(
                    func=ast.Name(id=fn, ctx=ast.Load()),
                    args=[self._lambda_of(self._convert_bool_ops(v)),
                          self._lambda_of(out)],
                    keywords=[])
            return out
        if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.Not):
            return ast.Call(func=ast.Name(id="_jst_not", ctx=ast.Load()),
                            args=[self._convert_bool_ops(node.operand)],
                            keywords=[])
        return node

    # -- if ------------------------------------------------------------------
    def visit_If(self, node):
        self.generic_visit(node)
        if _has_escape(node.body) or _has_escape(node.orelse):
            return node     # early return/break: keep Python semantics
        uid = self._uid()
        names = _assigned_names(node.body + node.orelse)
        test = self._convert_bool_ops(node.test)
        true_fn = self._fn_def(f"__pt_true_{uid}", node.body, names)
        false_fn = self._fn_def(f"__pt_false_{uid}", node.orelse, names)
        getter = self._getter(f"__pt_get_{uid}", names)
        setter = self._setter(f"__pt_set_{uid}", names)
        call = ast.Expr(value=ast.Call(
            func=ast.Name(id="_jst_ifelse", ctx=ast.Load()),
            args=[test,
                  ast.Name(id=f"__pt_true_{uid}", ctx=ast.Load()),
                  ast.Name(id=f"__pt_false_{uid}", ctx=ast.Load()),
                  ast.Name(id=f"__pt_get_{uid}", ctx=ast.Load()),
                  ast.Name(id=f"__pt_set_{uid}", ctx=ast.Load())],
            keywords=[]))
        return self._initializers(names) + \
            [true_fn, false_fn, getter, setter, call]

    # -- while ----------------------------------------------------------------
    def visit_While(self, node):
        self.generic_visit(node)
        if _has_escape(node.body) or node.orelse:
            return node
        uid = self._uid()
        names = _assigned_names(node.body)
        test = self._convert_bool_ops(node.test)
        cond_fn = ast.parse(f"def __pt_cond_{uid}():\n    return 0").body[0]
        cond_fn.body[0].value = test
        body_fn = self._fn_def(f"__pt_body_{uid}", node.body, names)
        getter = self._getter(f"__pt_get_{uid}", names)
        setter = self._setter(f"__pt_set_{uid}", names)
        call = ast.Expr(value=ast.Call(
            func=ast.Name(id="_jst_while", ctx=ast.Load()),
            args=[ast.Name(id=f"__pt_cond_{uid}", ctx=ast.Load()),
                  ast.Name(id=f"__pt_body_{uid}", ctx=ast.Load()),
                  ast.Name(id=f"__pt_get_{uid}", ctx=ast.Load()),
                  ast.Name(id=f"__pt_set_{uid}", ctx=ast.Load())],
            keywords=[]))
        return self._initializers(names) + \
            [cond_fn, body_fn, getter, setter, call]


class _ListAppendTransformer(ast.NodeTransformer):
    """list_transformer.py parity: a bare ``name.append(x)`` statement
    becomes ``name = _jst_append(name, x)`` so appends into traced loop
    carries rebind functionally (plain lists keep eager semantics inside
    the converter)."""

    def __init__(self):
        self.count = 0

    def visit_Expr(self, node):
        self.generic_visit(node)
        call = node.value
        if (isinstance(call, ast.Call)
                and isinstance(call.func, ast.Attribute)
                and call.func.attr == "append"
                and isinstance(call.func.value, ast.Name)
                and len(call.args) == 1 and not call.keywords):
            self.count += 1
            name = call.func.value.id
            return ast.copy_location(ast.Assign(
                targets=[ast.Name(id=name, ctx=ast.Store())],
                value=ast.Call(
                    func=ast.Name(id="_jst_append", ctx=ast.Load()),
                    args=[ast.Name(id=name, ctx=ast.Load()),
                          call.args[0]],
                    keywords=[])), node)
        return node

    def visit_Call(self, node):
        # len(x) → convert_len: a list promoted to a BoundedTensorArray
        # reports its TRACED live size; plain containers keep builtin len
        self.generic_visit(node)
        if (isinstance(node.func, ast.Name) and node.func.id == "len"
                and len(node.args) == 1 and not node.keywords):
            self.count += 1
            return ast.copy_location(ast.Call(
                func=ast.Name(id="_jst_len", ctx=ast.Load()),
                args=node.args, keywords=[]), node)
        return node


class _SliceTransformer(ast.NodeTransformer):
    """slice_transformer.py parity: two-bound subscripts become converter
    calls carrying the syntactically-derived window size, so traced-bound
    slicing (``x[i:i+k]`` with ``i`` a loop carry) lowers to
    lax.dynamic_slice instead of crashing on a traced Python ``slice``.
    Static bounds round-trip through the converter unchanged."""

    def __init__(self):
        self.count = 0

    @staticmethod
    def _size_expr(lo, up):
        """The static window size when the bounds differ by a constant
        expression: x[i:i+k] / x[i:k+i] → k; x[i-k:i] → k."""
        d = ast.dump
        if isinstance(up, ast.BinOp) and isinstance(up.op, ast.Add):
            if d(up.left) == d(lo):
                return up.right
            if d(up.right) == d(lo):
                return up.left
        if isinstance(lo, ast.BinOp) and isinstance(lo.op, ast.Sub) \
                and d(lo.left) == d(up):
            return lo.right
        return None

    @staticmethod
    def _two_bound(node):
        return (isinstance(node, ast.Subscript)
                and isinstance(node.slice, ast.Slice)
                and node.slice.lower is not None
                and node.slice.upper is not None)

    def _args(self, node):
        sl = node.slice
        size = self._size_expr(sl.lower, sl.upper)
        return [node.value, sl.lower, sl.upper,
                sl.step if sl.step is not None else ast.Constant(None),
                size if size is not None else ast.Constant(None)]

    def visit_Subscript(self, node):
        self.generic_visit(node)
        if self._two_bound(node) and isinstance(node.ctx, ast.Load):
            self.count += 1
            return ast.copy_location(ast.Call(
                func=ast.Name(id="_jst_slice", ctx=ast.Load()),
                args=self._args(node), keywords=[]), node)
        return node

    def visit_Assign(self, node):
        self.generic_visit(node)
        tgt = node.targets[0]
        if (len(node.targets) == 1 and self._two_bound(tgt)
                and isinstance(tgt.value, ast.Name)):
            self.count += 1
            base = tgt.value.id
            tgt2 = ast.Subscript(value=ast.Name(id=base, ctx=ast.Load()),
                                 slice=tgt.slice, ctx=ast.Load())
            return ast.copy_location(ast.Assign(
                targets=[ast.Name(id=base, ctx=ast.Store())],
                value=ast.Call(
                    func=ast.Name(id="_jst_setslice", ctx=ast.Load()),
                    args=self._args(tgt2)[:4] + [node.value,
                                                 self._args(tgt2)[4]],
                    keywords=[])), node)
        return node


class _AssertPrintCastTransformer(ast.NodeTransformer):
    """The assert/print/cast leg of the reference pipeline
    (assert_transformer.py, print_transformer.py, cast_transformer.py):
    ``assert`` → convert_assert, ``print(...)`` → convert_print,
    ``int/float/bool(x)`` → dtype casts when x is a tensor."""

    _CASTS = ("int", "float", "bool")

    def __init__(self):
        self.count = 0

    def visit_FunctionDef(self, node):
        if _is_generator_def(node):
            return node
        self.generic_visit(node)
        return node

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Assert(self, node):
        self.generic_visit(node)
        self.count += 1
        args = [node.test] + ([node.msg] if node.msg is not None else [])
        return ast.copy_location(ast.Expr(value=ast.Call(
            func=ast.Name(id="_jst_assert", ctx=ast.Load()),
            args=args, keywords=[])), node)

    def visit_Call(self, node):
        self.generic_visit(node)
        if isinstance(node.func, ast.Name):
            if node.func.id == "print" and not any(
                    kw.arg is None for kw in node.keywords):
                self.count += 1
                return ast.copy_location(ast.Call(
                    func=ast.Name(id="_jst_print", ctx=ast.Load()),
                    args=node.args, keywords=node.keywords), node)
            if (node.func.id in self._CASTS and len(node.args) == 1
                    and not node.keywords):
                self.count += 1
                return ast.copy_location(ast.Call(
                    func=ast.Name(id=f"_jst_{node.func.id}",
                                  ctx=ast.Load()),
                    args=node.args, keywords=[]), node)
        return node


def _src_location(raw):
    code = getattr(raw, "__code__", None)
    if code is None:
        return "<unknown>", 0
    return code.co_filename, code.co_firstlineno


def ast_transform(func):
    """Rewrite ``func``'s if/while into converter calls. Returns the new
    function, or None when the source is unavailable/untransformable
    (lambdas, closures, C extensions) — callers fall back to plain tracing
    (program_translator.py's to-static fallback).  Unsupported syntax that
    can NEVER convert (generators) raises Dy2StaticError with the original
    source location — the reference's error-report path
    (dygraph_to_static/error.py)."""
    raw = getattr(func, "__func__", func)
    if raw.__closure__:          # can't rebuild closure cells faithfully
        return None
    try:
        src = textwrap.dedent(inspect.getsource(raw))
        tree = ast.parse(src)
    except (OSError, TypeError, SyntaxError, IndentationError):
        return None
    fdef = tree.body[0]
    if not isinstance(fdef, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return None
    if _is_generator_def(fdef):
        fname, line = _src_location(raw)
        raise Dy2StaticError(
            f"@to_static cannot convert generator function "
            f"'{raw.__name__}' ({fname}:{line}): `yield` has no graph "
            f"form — iterate eagerly outside the compiled program")
    fdef.decorator_list = []
    # transformer pipeline (ast_transformer.py order): assert/print/cast,
    # for→while, returns, break/continue escapes, then if/while →
    # converter calls
    pc = _AssertPrintCastTransformer()
    tree = pc.visit(tree)
    la = _ListAppendTransformer()
    tree = la.visit(tree)
    sl = _SliceTransformer()
    tree = sl.visit(tree)
    if pc.count:
        # probe host-callback support NOW, outside any trace (probing
        # inside convert_assert/print would inline the probe's callback
        # into the user's traced program); lru_cache serves the verdict
        # at trace time
        _host_callbacks_supported()
    ft = _ForToWhile()
    tree = ft.visit(tree)
    rt = _ReturnTransformer()
    did_ret = rt.run(fdef)
    et = _LoopEscapeTransformer()
    tree = et.visit(tree)
    if did_ret:
        fdef.body = (ast.parse(f"{RET_VAL} = None\n{RET_FLAG} = False").body
                     + _guard_stmts(fdef.body, [RET_FLAG])
                     + [ast.parse(f"return {RET_VAL}").body[0]])
    t = _ControlFlowTransformer()
    new_tree = t.visit(tree)
    fname, first = _src_location(raw)
    if (t._n == 0 and ft.count == 0 and et.count == 0 and not did_ret
            and pc.count == 0 and la.count == 0 and sl.count == 0):
        # nothing to rewrite — still attach the runtime diagnostic guard so
        # unconvertible dynamic control flow reports guidance, not a bare
        # tracer error
        return _guard_diagnostics(raw, raw, fname, first)
    ast.fix_missing_locations(new_tree)
    # error-report mapping: compile against the ORIGINAL file with linenos
    # shifted to the function's real position, so tracebacks out of the
    # transformed code point into the user's source
    try:
        ast.increment_lineno(new_tree, first - 1)
        code = compile(new_tree, filename=fname, mode="exec")
    except Exception:
        code = compile(new_tree, filename=f"<dy2static {raw.__name__}>",
                       mode="exec")
    globs = dict(raw.__globals__)
    globs.update(_JST)
    ns = {}
    exec(code, globs, ns)
    new = ns[fdef.name]
    functools.update_wrapper(new, raw)
    return _guard_diagnostics(new, raw, fname, first)


def _guard_diagnostics(new, raw, fname, first):
    """Wrap a (possibly transformed) function so unconvertible dynamic
    control flow surfaces as a guided Dy2StaticError with the original
    source location — the reference's error-report layer
    (dygraph_to_static/error.py)."""

    @functools.wraps(new)
    def guarded(*a, **k):
        try:
            return new(*a, **k)
        except (jax.errors.ConcretizationTypeError,
                jax.errors.TracerArrayConversionError) as e:
            # a kept-Python construct concretized a tracer (bool() or
            # numpy() on a data-dependent value outside convertible flow)
            raise Dy2StaticError(
                f"unsupported data-dependent operation in '{raw.__name__}' "
                f"({fname}:{first}): a traced value was concretized — by a "
                f"construct that kept Python semantics (loop with "
                f"break/else feeding a traced condition, truth-testing "
                f"outside a convertible if/while) or by a host conversion "
                f"(.numpy(), np.asarray, item()). Rewrite with plain "
                f"if/while (no early escapes into the condition), keep "
                f"host conversions outside @to_static, or make the value "
                f"static. Underlying error: {type(e).__name__}.") from e
    guarded.__pt_dy2static__ = True
    return guarded
