"""Convolution functionals.

Reference parity: paddle/fluid/operators/conv_op.cc, conv_transpose_op.cc and
python/paddle/nn/functional/conv.py. TPU-first: everything lowers to
lax.conv_general_dilated, which XLA tiles directly onto the MXU; the cuDNN
algorithm-search machinery of the reference (conv_cudnn_helper.h) has no
equivalent because XLA picks the layout/tiling.

Weight layout follows Paddle: OIHW (out, in/groups, kh, kw); data NCHW or NHWC.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...framework.primitive import Primitive
from ...framework.tensor import Tensor, unwrap


def _norm_tuple(v, n):
    if isinstance(v, (int, np.integer)):
        return (int(v),) * n
    v = tuple(int(x) for x in v)
    if len(v) == 1:
        return v * n
    return v


def _norm_padding(padding, n):
    """Return lax padding spec: 'SAME'/'VALID' or [(lo,hi)]*n."""
    if isinstance(padding, str):
        return padding.upper()
    if isinstance(padding, (int, np.integer)):
        return tuple((int(padding), int(padding)) for _ in range(n))
    padding = list(padding)
    if len(padding) == n and all(isinstance(p, (int, np.integer)) for p in padding):
        return tuple((int(p), int(p)) for p in padding)
    if len(padding) == 2 * n:
        return tuple((int(padding[2 * i]), int(padding[2 * i + 1]))
                     for i in range(n))
    # nested [[lo,hi],...]
    return tuple((int(p[0]), int(p[1])) for p in padding)


def _dims(ndim_spatial, channel_last):
    if ndim_spatial == 1:
        return ("NWC", "WIO", "NWC") if channel_last else ("NCW", "OIW", "NCW")
    if ndim_spatial == 2:
        return ("NHWC", "HWIO", "NHWC") if channel_last else ("NCHW", "OIHW", "NCHW")
    return ("NDHWC", "DHWIO", "NDHWC") if channel_last else ("NCDHW", "OIDHW", "NCDHW")


def _conv_fn(x, w, b=None, stride=(1, 1), padding="VALID", dilation=(1, 1),
             groups=1, channel_last=False, nsp=2):
    lhs_spec, rhs_spec, out_spec = _dims(nsp, channel_last)
    if channel_last:
        # paddle weights stay OIHW; transpose once for the NHWC conv form
        perm = tuple(range(2, 2 + nsp)) + (1, 0)
        w = jnp.transpose(w, perm)
    dn = jax.lax.conv_dimension_numbers(x.shape, w.shape,
                                        (lhs_spec, rhs_spec, out_spec))
    # NB: no preferred_element_type=f32 here — it makes the VJP's
    # transpose-rhs conv see (bf16 activations, f32 cotangent) and the
    # dtype rule rejects that; XLA:TPU already accumulates bf16 convs in
    # f32 on the MXU, so bf16-in/bf16-out loses nothing
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=stride, padding=padding,
        rhs_dilation=dilation, dimension_numbers=dn,
        feature_group_count=groups)
    out = out.astype(x.dtype)
    if b is not None:
        bshape = (1, -1) + (1,) * nsp if not channel_last else (1,) * (1 + nsp) + (-1,)
        out = out + jnp.reshape(b, bshape)
    return out


_conv_p = Primitive("conv2d", _conv_fn)


def _conv_impl(x, weight, bias, stride, padding, dilation, groups, data_format,
               nsp):
    channel_last = data_format in ("NHWC", "NWC", "NDHWC", "NLC")
    stride = _norm_tuple(stride, nsp)
    dilation = _norm_tuple(dilation, nsp)
    pad = _norm_padding(padding, nsp)
    args = [x, weight] + ([bias] if bias is not None else [])
    if bias is not None:
        return _conv_p(x, weight, bias, stride=stride, padding=pad,
                       dilation=dilation, groups=int(groups),
                       channel_last=channel_last, nsp=nsp)
    return _conv_nb_p(x, weight, stride=stride, padding=pad, dilation=dilation,
                      groups=int(groups), channel_last=channel_last, nsp=nsp)


_conv_nb_p = Primitive("conv2d_nobias",
                       lambda x, w, **kw: _conv_fn(x, w, None, **kw))


def _conv_bn_act_fn(x, w, gamma, beta, rmean, rvar, momentum=0.9, eps=1e-5,
                    stride=1, padding=0, relu=True, s2d=False):
    """Fused NHWC conv+BN(+ReLU) through the Pallas pipeline
    (ops/pallas/fused_conv.py), with the batch_norm_train running-stat
    contract.  ``s2d=True`` applies the space-to-depth stem reorg (7×7/s2
    → 4×4/s1 over 12 channels) INSIDE the op so the reorged conv feeds
    the fused kernel directly — s2d at the XLA level alone was measured
    slower (PERF.md r3) and must not ship without the kernel."""
    from ...ops.pallas import fused_conv
    from .norm import _running_update
    if s2d:
        x = fused_conv.stem_s2d_input(x)
        w = fused_conv.stem_s2d_weight(w)
        stride, padding = 1, 0
    y, mean, var = fused_conv.fused_conv_bn_act(
        x, w, gamma.astype(jnp.float32), beta.astype(jnp.float32),
        int(stride), int(padding), float(eps), bool(relu))
    new_rmean, new_rvar = _running_update(rmean, rvar, mean, var, momentum)
    return y, new_rmean, new_rvar


_conv_bn_act_p = Primitive("conv2d_bn_act", _conv_bn_act_fn,
                           multi_output=True)


def conv_bn_fusable(x, weight, stride, padding, dilation, groups,
                    data_format, s2d=False):
    """One cheap static check deciding the fused-vs-XLA branch (the
    off-path must stay one branch — ISSUE 2 acceptance)."""
    from ...framework import core
    from ...framework.tensor import Tensor
    from ...ops.pallas import fused_conv
    if not fused_conv.enabled() or core.in_static_mode():
        return False
    xv, wv = unwrap(x), unwrap(weight)
    itemsize = jnp.dtype(xv.dtype).itemsize
    if s2d:
        return fused_conv.stem_supported(tuple(xv.shape), tuple(wv.shape),
                                         itemsize=itemsize)
    return fused_conv.supports(
        tuple(xv.shape), tuple(wv.shape), stride, padding, dilation, groups,
        channel_last=data_format in ("NHWC",), itemsize=itemsize)


def conv_bn_act(x, weight, gamma, beta, running_mean, running_var,
                momentum=0.9, epsilon=1e-5, stride=1, padding=0, dilation=1,
                groups=1, data_format="NHWC", act=None, training=True,
                s2d=False, name=None):
    """conv2d → batch_norm → activation, fused through the Pallas
    conv+BN+ReLU pipeline when ``FLAGS_use_pallas_fused_conv`` is on and
    the site is eligible; otherwise the exact XLA composition (reference:
    operators/fused/conv_fusion_op.cc).  Running stats update with the
    shared momentum convention either way."""
    relu = act == "relu"
    if training and act in (None, "relu") and conv_bn_fusable(
            x, weight, stride, padding, dilation, groups, data_format, s2d):
        def _i(v):
            return int(v[0]) if isinstance(v, (tuple, list)) else int(v)
        out, nm, nv = _conv_bn_act_p(
            x, weight, gamma, beta, running_mean, running_var,
            momentum=float(momentum), eps=float(epsilon), stride=_i(stride),
            padding=_i(padding), relu=relu, s2d=bool(s2d))
        # functional-state write-back, same as F.batch_norm's train path
        if isinstance(running_mean, Tensor) and isinstance(nm, Tensor):
            running_mean.set_value(nm._value)
            running_var.set_value(nv._value)
        return out
    from .norm import batch_norm
    y = conv2d(x, weight, None, stride, padding, dilation, groups,
               data_format)
    y = batch_norm(y, running_mean, running_var, gamma, beta,
                   training=training, momentum=momentum, epsilon=epsilon,
                   data_format=data_format)
    if act is not None:
        from . import activation as A
        y = getattr(A, act)(y)
    return y


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCL", name=None):
    df = "NWC" if data_format in ("NLC",) else "NCW"
    return _conv_impl(x, weight, bias, stride, padding, dilation, groups, df, 1)


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCHW", name=None):
    return _conv_impl(x, weight, bias, stride, padding, dilation, groups,
                      data_format, 2)


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCDHW", name=None):
    return _conv_impl(x, weight, bias, stride, padding, dilation, groups,
                      data_format, 3)


def _conv_transpose_fn(x, w, b=None, stride=(1, 1), padding=(0, 0),
                       output_padding=(0, 0), dilation=(1, 1), groups=1,
                       channel_last=False, nsp=2):
    lhs_spec, rhs_spec, out_spec = _dims(nsp, channel_last)
    if channel_last:
        perm = tuple(range(2, 2 + nsp)) + (1, 0)
        wt = jnp.transpose(w, perm)  # spatial..., I, O with paddle w = (in, out/g, k)
        wt = jnp.swapaxes(wt, -1, -2)
    else:
        # paddle conv_transpose weight layout: (in, out/groups, kh, kw) = IOHW
        wt = jnp.swapaxes(w, 0, 1)  # -> (out/g, in, kh, kw)
        if groups > 1:
            # regroup: (g*out_g, in_g, ...) expected by transposed conv below
            pass
    # implement via gradient of forward conv: conv_transpose == lhs-dilated conv
    pads = tuple((d * (k - 1) - p[0], d * (k - 1) - p[1] + op)
                 for p, op, k, d in zip(padding, output_padding,
                                        wt.shape[2:2 + nsp] if not channel_last
                                        else wt.shape[:nsp], dilation))
    if channel_last:
        wt2 = jnp.flip(wt, axis=tuple(range(nsp)))
        dn = jax.lax.conv_dimension_numbers(
            x.shape, wt2.shape, (lhs_spec, rhs_spec, out_spec))
        out = jax.lax.conv_general_dilated(
            x, wt2, window_strides=(1,) * nsp, padding=pads,
            lhs_dilation=stride, rhs_dilation=dilation, dimension_numbers=dn,
            feature_group_count=groups)
    else:
        wt2 = jnp.flip(wt, axis=tuple(range(2, 2 + nsp)))
        if groups > 1:
            # (out/g, in, k): split input-channel dim across groups
            o_g, i_all = wt2.shape[0], wt2.shape[1]
            wt2 = jnp.reshape(wt2, (o_g, groups, i_all // groups) + wt2.shape[2:])
            wt2 = jnp.transpose(wt2, (1, 0) + tuple(range(2, wt2.ndim)))
            wt2 = jnp.reshape(wt2, (groups * o_g,) + wt2.shape[2:])
        dn = jax.lax.conv_dimension_numbers(
            x.shape, wt2.shape, (lhs_spec, rhs_spec, out_spec))
        out = jax.lax.conv_general_dilated(
            x, wt2, window_strides=(1,) * nsp, padding=pads,
            lhs_dilation=stride, rhs_dilation=dilation, dimension_numbers=dn,
            feature_group_count=groups)
    out = out.astype(x.dtype)
    if b is not None:
        bshape = (1, -1) + (1,) * nsp if not channel_last else (1,) * (1 + nsp) + (-1,)
        out = out + jnp.reshape(b, bshape)
    return out


_convt_p = Primitive("conv2d_transpose", _conv_transpose_fn)
_convt_nb_p = Primitive("conv2d_transpose_nobias",
                        lambda x, w, **kw: _conv_transpose_fn(x, w, None, **kw))


def _conv_transpose_impl(x, weight, bias, stride, padding, output_padding,
                         dilation, groups, data_format, nsp):
    channel_last = data_format in ("NHWC", "NWC", "NDHWC", "NLC")
    stride = _norm_tuple(stride, nsp)
    dilation = _norm_tuple(dilation, nsp)
    output_padding = _norm_tuple(output_padding, nsp)
    pad = _norm_padding(padding, nsp)
    if isinstance(pad, str):
        if pad == "VALID":
            pad = tuple((0, 0) for _ in range(nsp))
        else:
            raise ValueError("SAME padding unsupported for conv_transpose; "
                             "give explicit pads (paddle parity)")
    kw = dict(stride=stride, padding=pad, output_padding=output_padding,
              dilation=dilation, groups=int(groups),
              channel_last=channel_last, nsp=nsp)
    if bias is not None:
        return _convt_p(x, weight, bias, **kw)
    return _convt_nb_p(x, weight, **kw)


def conv1d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1,
                     data_format="NCL", name=None):
    df = "NWC" if data_format == "NLC" else "NCW"
    return _conv_transpose_impl(x, weight, bias, stride, padding,
                                output_padding, dilation, groups, df, 1)


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1,
                     data_format="NCHW", name=None):
    return _conv_transpose_impl(x, weight, bias, stride, padding,
                                output_padding, dilation, groups,
                                data_format, 2)


def conv3d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1,
                     data_format="NCDHW", name=None):
    return _conv_transpose_impl(x, weight, bias, stride, padding,
                                output_padding, dilation, groups,
                                data_format, 3)
