"""Static-API long tail (python/paddle/static/__init__.py parity).

Thin, honest shims where the TPU design subsumes the reference machinery:
places enumerate jax devices; program/persistable (de)serialization rides
the pickle program format in io.py; py_func wraps a host callback via
pure_callback (the py_func_op analogue); name_scope/create_global_var/
create_parameter mirror fluid.layers helpers.
"""
from __future__ import annotations

import contextlib
import pickle

import jax
import jax.numpy as jnp
import numpy as np

from .program import Program, default_main_program, default_startup_program
from .executor import global_scope
from .io import _program_to_dict, _program_from_dict


def cpu_places(device_count=None):
    try:
        devs = jax.devices("cpu")
    except RuntimeError:
        devs = []
    return list(devs[:device_count] if device_count else devs)


def cuda_places(device_ids=None):
    return []      # no CUDA devices in a TPU build (is_compiled_with_cuda())


def xpu_places(device_ids=None):
    return []


def tpu_places(device_ids=None):
    devs = [d for d in jax.devices() if d.platform != "cpu"]
    if device_ids is not None:
        devs = [devs[i] for i in device_ids]
    return devs


@contextlib.contextmanager
def name_scope(prefix=None):
    """fluid name_scope: a no-op grouping context (names are framework-
    generated; the scope only affects display names in the reference)."""
    yield


def create_global_var(shape, value, dtype, persistable=False,
                      force_cpu=False, name=None):
    """layers.create_global_var parity: a persistable var seeded in the
    global scope."""
    from .program import current_block
    b = current_block()
    v = b.create_var(name=name, shape=list(shape), dtype=dtype,
                     persistable=persistable)
    global_scope().set_var(v.name, jnp.full(tuple(shape), value,
                                            jnp.dtype(dtype)))
    return v


def create_parameter(shape, dtype, name=None, attr=None, is_bias=False,
                     default_initializer=None):
    from .nn import _make_param
    from ..nn import initializer as I
    init = default_initializer or (I.Constant(0.0) if is_bias
                                   else I.XavierUniform())
    return _make_param(list(shape), dtype, attr, init, name or "param")


def Print(input, first_n=-1, message=None, summarize=20,
          print_tensor_name=True, print_tensor_type=True,
          print_tensor_shape=True, print_tensor_lod=False,
          print_phase="both"):
    """print_op parity via host callback: prints at execution time and
    passes the value through."""
    def cb(x):
        msg = message or ""
        print(f"{msg}{x}")
        return x

    return py_func(cb, input, input)


_py_func_prims = {}    # strong refs: (func, primitive) keyed by id(func)


def py_func(func, x, out, backward_func=None,
            skip_vars_in_backward_input=None):
    """py_func_op parity: run a host Python function inside the graph via
    jax.pure_callback. ``out`` provides the result spec — a
    Variable/Tensor (or list of them) whose shape+dtype the callback must
    produce."""
    from ..framework.primitive import Primitive
    from ..framework.tensor import unwrap

    def spec_of(o):
        ov = unwrap(o)
        return jax.ShapeDtypeStruct(tuple(ov.shape), jnp.dtype(ov.dtype))

    multi = isinstance(out, (list, tuple))
    spec = tuple(spec_of(o) for o in out) if multi else spec_of(out)

    # eager fast path: concrete inputs run the callback directly on host —
    # also the only path on backends without host-callback support
    from ..framework import core as _core
    from ..framework.tensor import Tensor as _T
    xv = unwrap(x)
    if not _core.in_static_mode() and not isinstance(xv, jax.core.Tracer):
        res = func(np.asarray(xv))
        if multi:
            return [_T(jnp.asarray(np.asarray(r, dtype=sp.dtype)))
                    for r, sp in zip(res, spec)]
        return _T(jnp.asarray(np.asarray(res, dtype=spec.dtype)))

    # one primitive per callback object, cached with a strong func ref —
    # id() reuse after GC must never alias a recorded program's op name
    hit = _py_func_prims.get(id(func))
    if hit is not None and hit[0] is func:
        p = hit[1]
    else:
        def fn(v, _func=func, _spec=spec, _multi=multi):
            if _multi:
                def host(a):
                    res = _func(a)
                    return tuple(np.asarray(r, dtype=sp.dtype)
                                 for r, sp in zip(res, _spec))
            else:
                def host(a):
                    return np.asarray(_func(a), dtype=_spec.dtype)
            return jax.pure_callback(host, _spec, v)

        p = Primitive(f"py_func_{id(func)}", fn, differentiable=False,
                      multi_output=multi)
        _py_func_prims[id(func)] = (func, p)
    return p(x)


# -- program/state (de)serialization ------------------------------------------

def serialize_program(feed_vars=None, fetch_vars=None, program=None):
    """static.serialize_program parity -> bytes."""
    program = program or default_main_program()
    return pickle.dumps(_program_to_dict(program), protocol=4)


def deserialize_program(data: bytes) -> Program:
    return _program_from_dict(pickle.loads(data))


def serialize_persistables(feed_vars=None, fetch_vars=None, program=None):
    program = program or default_main_program()
    scope = global_scope()
    blob = {}
    for v in program.list_vars():
        if v.persistable:
            val = scope.find_var(v.name)
            if val is not None:
                blob[v.name] = np.asarray(val)
    return pickle.dumps(blob, protocol=4)


def deserialize_persistables(program, data: bytes, executor=None):
    blob = pickle.loads(data)
    scope = global_scope()
    for name, val in blob.items():
        scope.set_var(name, jnp.asarray(val))


def save_to_file(path: str, content: bytes):
    with open(path, "wb") as f:
        f.write(content)


def load_from_file(path: str) -> bytes:
    with open(path, "rb") as f:
        return f.read()


def save(program, model_prefix, protocol=4):
    """static.save parity: <prefix>.pdmodel + <prefix>.pdiparams."""
    save_to_file(model_prefix + ".pdmodel", serialize_program(program=program))
    save_to_file(model_prefix + ".pdiparams",
                 serialize_persistables(program=program))


def load(program, model_prefix, executor=None, var_list=None):
    deserialize_persistables(
        program, load_from_file(model_prefix + ".pdiparams"))


def get_program_state(program=None):
    program = program or default_main_program()
    scope = global_scope()
    return {v.name: np.asarray(scope.find_var(v.name))
            for v in program.list_vars()
            if v.persistable and scope.find_var(v.name) is not None}


def load_program_state(model_path, var_list=None):
    """static.load_program_state parity: read a static.save prefix from
    disk -> {name: ndarray} (apply with set_program_state)."""
    blob = pickle.loads(load_from_file(model_path + ".pdiparams"))
    if var_list is not None:
        wanted = {v.name if hasattr(v, "name") else str(v)
                  for v in var_list}
        blob = {k: v for k, v in blob.items() if k in wanted}
    return {k: np.asarray(v) for k, v in blob.items()}


def set_program_state(program, state_dict):
    scope = global_scope()
    for name, val in state_dict.items():
        scope.set_var(name, jnp.asarray(val))
