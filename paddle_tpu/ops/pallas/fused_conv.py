"""Fused conv+BN(+ReLU) Pallas pipeline for the bandwidth-bound high-res
ResNet stages, plus the space-to-depth stem reorg.

Reference parity: the hand-fused conv kernels of
paddle/fluid/operators/conv_cudnn_op.cu + operators/fused/
(conv_fusion_op.cc, fused_batch_norm_act) — the reference's answer to the
same problem this module attacks (VERDICT r5 #1: ResNet-50 at 30% MFU,
stages 1–2 at ~72 ms against a 24–32 ms memory floor).

Why a FULL conv+BN+ReLU chain and not a BN epilogue: the round-4 BN-only
Pallas kernel measured 974 vs 1,971 img/s end-to-end — an opaque custom
call between XLA's conv and its epilogue breaks XLA's own conv fusion, so
the fix must own the whole chain.  Structure (streaming-tile discipline of
ops/pallas/flash_attention.py):

- ``_conv_stats``: ONE kernel computes the conv (sum of shifted matmuls on
  the MXU, f32 accumulators) AND the per-channel sum/sumsq of its output —
  the conv activation is written once and never re-read for the stats
  pass (XLA materializes the conv output, re-reads it for stats, and
  re-reads+writes for normalize: PERF.md round-3 "+4.5 ms on a 411 MB
  activation").
- apply: the normalize+affine+ReLU pass reuses fused_bn's `_apply` kernel
  (one read + one write of the activation).
- backward: dγ/dβ and the BN part of dX run through fused_bn's shared
  reduce/coefficient kernels on the saved conv output (one streaming pass
  each); the conv's own dX/dW transposes go through lax.conv (XLA's conv
  backward is compute-bound and healthy — 55/64 TFLOP/s measured r3 — the
  bandwidth win is the epilogue, not the conv transpose).

Space-to-depth stem: the 7×7/s2 C_in=3 stem uses ~2% of the MXU's input
lanes (19.2 ms measured, r3).  ``stem_s2d_*`` reorganizes the padded input
[N,230,230,3] → [N,115,115,12] and folds the 7×7/s2 weights into an
equivalent 4×4/s1 kernel over 12 channels — and unlike the rejected r3
s2d-at-XLA attempt (fwd 12.3 ms vs 8.4 plain: XLA's own im2col undid the
lane win), the reorged conv feeds THIS kernel directly.  (At 224px the
reorged 4×4 conv's 16 taps over a 112×112 output exceed the VMEM stack the
v5e compiler allows — ``supports`` declines it and the stem stays on XLA;
smaller inputs, e.g. 160px, fit.)

Gating (the flash/fused_bn honesty rule): ships OFF by default —
``FLAGS_use_pallas_fused_conv`` / ``PADDLE_TPU_PALLAS_CONV=1`` opts in.
The default flips only with an end-to-end ResNet-50 win recorded on the
chip in PERF.md (the kernel compiles and matches its reference there —
chip_smoke.py's kernels phase — but has not been measured).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import _mode, fused_bn


def enabled() -> bool:
    """Honest gate (see module docstring): opt-in via the flags registry
    (paddle.set_flags({"FLAGS_use_pallas_fused_conv": True}) or the env
    seed) or the PADDLE_TPU_PALLAS_CONV=1 env var."""
    from ...framework.flags import flag
    return bool(flag("use_pallas_fused_conv")) or \
        os.environ.get("PADDLE_TPU_PALLAS_CONV", "0") == "1"


# Per-image block estimate of ``supports`` (padded input + f32 accumulator +
# stored output + weights, f32 upper bound) — the pipeline's buffers
_VMEM_CAP_BYTES = 12 * 1024 * 1024
# The kernel's own stack, which the chip's compiler holds to 16 MiB of
# scoped VMEM on v5e.  ``_stack_bytes`` is fitted to what that compiler
# reports when it refuses a site ("ran out of memory in memory space vmem
# while allocating on stack", compiles for a described v5e:2x2): it
# reproduces the reported size of the refused bf16 sites within a few
# percent and leans high elsewhere, so every site it admits in those
# sweeps compiles (tests/test_tpu_compile.py keeps the boundary cases).
_STACK_CAP_BYTES = int(15.5 * 1024 * 1024)
_S2_WINDOW_BYTES_PER_PIXEL = 32 * 1024      # per 128 channels, at 9 taps


def _stack_bytes(ho, wo, cin, cout, kh, kw, stride, itemsize):
    """VMEM stack of one ``_conv_stats_kernel`` step: the f32 accumulator
    over lane-padded Cout (twice when the tap's product is a temporary of
    its own: Cout beyond one 128-lane tile), plus max(kw, 2) live
    [Ho*Wo, Cin] window copies over lane-padded Cin — three times that
    when a packed (2-byte) window's rows do not fill whole 16-row tiles
    and the collapse goes through an unpacked copy; f32 windows count at
    twice their size (a margin over the few f32 sites probed, not a fit).
    A strided tap instead folds the
    stride into a [Ho, 2, Wo, 2, Cin] reshape whose (2, Cin) minor dims
    pad to whole tiles."""
    lane = lambda c: -(-c // 128) * 128
    px = ho * wo
    wide = cout > 128 or (itemsize == 4 and cout >= 128)
    acc = px * lane(cout) * 4 * (2 if wide else 1)
    if stride == 2 and kh * kw > 1:
        windows = _S2_WINDOW_BYTES_PER_PIXEL * px * (lane(cin) // 128) \
            * kh * kw // 9
    else:
        relayout = 2 if itemsize == 4 else (3 if wo % 16 else 1)
        windows = max(kw, 2) * px * lane(cin) * itemsize * relayout
    return acc + windows


def _out_hw(h, w, kh, kw, stride, padding):
    ho = (h + 2 * padding - kh) // stride + 1
    wo = (w + 2 * padding - kw) // stride + 1
    return ho, wo


def supports(x_shape, w_shape, stride=1, padding=0, dilation=1, groups=1,
             channel_last=True, itemsize=4) -> bool:
    """Static eligibility of the fused kernel for a conv+BN(+ReLU) site.

    NHWC, groups=1, dilation=1, stride 1 or 2, symmetric int padding,
    kernels ≤5 (the 7×7 stem goes through the s2d reorg instead — at
    C_in=3 a direct 49-tap kernel wastes the very lanes s2d reclaims),
    single device (pallas_call has no GSPMD partition rule), and the
    per-image working set must fit VMEM.  ``itemsize``: bytes per
    activation element (the f32 default is the conservative side)."""
    def _pair(v):
        return (v, v) if isinstance(v, int) else tuple(v)
    if not channel_last or groups != 1 or len(x_shape) != 4:
        return False
    if _pair(dilation) != (1, 1):
        return False
    s = _pair(stride)
    if s[0] != s[1] or s[0] not in (1, 2):
        return False
    if not isinstance(padding, int):
        if isinstance(padding, (tuple, list)) and len(padding) == 2 and \
                all(isinstance(p, int) for p in padding) and \
                padding[0] == padding[1]:
            padding = padding[0]
        else:
            return False
    n, h, w, cin = x_shape
    cout, cin_w, kh, kw = w_shape
    if cin_w != cin or kh > 5 or kw > 5:
        return False
    ho, wo = _out_hw(h, w, kh, kw, s[0], padding)
    if ho <= 0 or wo <= 0:
        return False
    if (n * ho * wo) % 8 != 0:
        return False         # apply/backward tiles ladder in units of 8
    if jax.device_count() > 1 and not _mode.interpret():
        # compiled pallas_call has no GSPMD partition rule; interpret mode
        # lowers to plain jax ops and partitions like any jnp code, so the
        # CPU test mesh keeps exercising the fused path
        return False
    # per-image VMEM working set: padded input + f32 accumulator + stored
    # output + weights (f32 upper bound)
    hp = h + 2 * padding + (s[0] - 1)
    wp = w + 2 * padding + (s[0] - 1)
    vmem = 4 * (hp * wp * cin + 2 * ho * wo * cout + kh * kw * cin * cout)
    if vmem > _VMEM_CAP_BYTES:
        return False
    if wo % 8 and cin % 128:
        # Mosaic refuses the window's [Ho, Wo, Cin] -> [Ho*Wo, Cin]
        # collapse ("unsupported shape cast") when neither dim tiles
        return False
    return _stack_bytes(ho, wo, cin, cout, kh, kw, s[0],
                        itemsize) <= _STACK_CAP_BYTES


# -- forward: conv with fused output statistics -------------------------------

def _conv_stats_kernel(x_ref, w_ref, y_ref, sum_ref, sq_ref, *, stride, kh,
                       kw, ho, wo):
    """One image per grid step: conv as the sum of kh·kw shifted matmuls
    (each tap is a [Ho·Wo, Cin] × [Cin, Cout] MXU contraction, f32
    accumulate), output written once, per-channel Σy/Σy² accumulated from
    the f32 accumulator before the store — the stats pass costs zero extra
    HBM traffic."""
    i = pl.program_id(0)
    x = x_ref[0]                                   # [Hp, Wp, Cin]
    cin = x.shape[-1]
    cout = y_ref.shape[-1]
    acc = jnp.zeros((ho * wo, cout), jnp.float32)
    for u in range(kh):
        for v in range(kw):
            if stride == 1:
                win = x[u:u + ho, v:v + wo, :]
            else:
                # strided window without a strided slice (Mosaic-safe):
                # take the dense [2·Ho, 2·Wo] slab, fold the stride into a
                # reshape and keep phase 0 (the caller padded one extra
                # row/col so the slab stays in bounds for every tap)
                slab = x[u:u + stride * ho, v:v + stride * wo, :]
                slab = slab.reshape(ho, stride, wo, stride, cin)
                win = slab[:, 0, :, 0, :]
            acc += jnp.dot(win.reshape(ho * wo, cin), w_ref[u, v],
                           preferred_element_type=jnp.float32)

    @pl.when(i == 0)
    def _():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        sq_ref[...] = jnp.zeros_like(sq_ref)

    sum_ref[...] += jnp.sum(acc, axis=0)
    sq_ref[...] += jnp.sum(acc * acc, axis=0)
    y_ref[0] = acc.reshape(ho, wo, cout).astype(y_ref.dtype)


def _conv_stats(x, w, stride, padding):
    """Fused conv + output moments.  Returns (y_conv [N,Ho,Wo,Cout],
    mean, var, xp) — xp is the padded input saved for the backward."""
    n, h, w_, cin = x.shape
    cout, _, kh, kw = w.shape
    ho, wo = _out_hw(h, w_, kh, kw, stride, padding)
    extra = stride - 1        # high-side slack for the fold-stride slab
    xp = jnp.pad(x, ((0, 0), (padding, padding + extra),
                     (padding, padding + extra), (0, 0)))
    hp, wp = xp.shape[1], xp.shape[2]
    wk = jnp.transpose(w, (2, 3, 1, 0))            # [kh, kw, Cin, Cout]
    y, s, q = pl.pallas_call(
        functools.partial(_conv_stats_kernel, stride=stride, kh=kh, kw=kw,
                          ho=ho, wo=wo),
        grid=(n,),
        in_specs=[pl.BlockSpec((1, hp, wp, cin), lambda i: (i, 0, 0, 0)),
                  pl.BlockSpec((kh, kw, cin, cout),
                               lambda i: (0, 0, 0, 0))],
        out_specs=[pl.BlockSpec((1, ho, wo, cout), lambda i: (i, 0, 0, 0)),
                   pl.BlockSpec((cout,), lambda i: (0,)),
                   pl.BlockSpec((cout,), lambda i: (0,))],
        out_shape=[jax.ShapeDtypeStruct((n, ho, wo, cout), x.dtype),
                   jax.ShapeDtypeStruct((cout,), jnp.float32),
                   jax.ShapeDtypeStruct((cout,), jnp.float32)],
        interpret=_mode.interpret(),
    )(xp, wk)
    m = n * ho * wo
    mean = s / m
    var = jnp.maximum(q / m - mean * mean, 0.0)
    return y, mean, var, xp


def _lax_conv(xp, wk, stride):
    """The mathematically-equal XLA conv on the already-padded input —
    differentiated in the backward for dX/dW (compute-bound, healthy)."""
    dn = jax.lax.conv_dimension_numbers(xp.shape, wk.shape,
                                        ("NHWC", "HWIO", "NHWC"))
    return jax.lax.conv_general_dilated(
        xp, wk, window_strides=(stride, stride), padding="VALID",
        dimension_numbers=dn).astype(xp.dtype)


# -- public fused op ----------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def fused_conv_bn_act(x, w, gamma, beta, stride=1, padding=0, eps=1e-5,
                      relu=True):
    """NHWC conv (Paddle OIHW weight, bias-free, groups=1, dilation=1) +
    train-mode BN over N·H·W + optional fused ReLU.  Returns
    (y, mean, var) — the batch_norm_train contract, so the Layer-side
    running-stat update is shared with the XLA path."""
    y, mean, var, *_ = _fwd_impl(x, w, gamma, beta, stride, padding, eps,
                                 relu)
    return y, mean, var


def _fwd_impl(x, w, gamma, beta, stride, padding, eps, relu):
    y_conv, mean, var, xp = _conv_stats(x, w, stride, padding)
    inv = jax.lax.rsqrt(var + eps)
    scale = inv * gamma.astype(jnp.float32)
    shift = beta.astype(jnp.float32) - mean * scale
    n, ho, wo, cout = y_conv.shape
    m = n * ho * wo
    tm = fused_bn._pick_tile(m, cout)
    if tm == 0:
        raise ValueError(f"fused_conv_bn_act: M={m} has no tile; "
                         f"pad N·Ho·Wo to a multiple of 8")
    out2d = fused_bn._apply(y_conv.reshape(m, cout), scale, shift, tm, relu)
    out = out2d.reshape(y_conv.shape)
    return out, mean, var, xp, y_conv, inv, scale, shift


def _fwd_rule(x, w, gamma, beta, stride, padding, eps, relu):
    out, mean, var, xp, y_conv, inv, scale, shift = _fwd_impl(
        x, w, gamma, beta, stride, padding, eps, relu)
    beta_tag = jnp.zeros((0,), beta.dtype)
    res = (xp, w, gamma, beta_tag, y_conv, mean, inv, scale, shift)
    return (out, mean, var), res


def _bwd_rule(stride, padding, eps, relu, res, cts):
    xp, w, gamma, beta_tag, y_conv, mean, inv, scale, shift = res
    dy, dmean, dvar = cts
    n, ho, wo, cout = y_conv.shape
    m = n * ho * wo
    # BN backward on the saved conv output: one streaming reduce pass
    # (dγ/dβ) + one fused multiply-add pass (coefficient-form dX of BN =
    # the conv's output cotangent), relu gate recomputed from y_conv
    y2d = y_conv.reshape(m, cout)
    dy2d = dy.reshape(m, cout)
    tm = fused_bn._pick_tile(m, cout)
    sum_dyx, dbeta = fused_bn.bn_bwd_reduce(y2d, dy2d, scale, shift, relu,
                                            tm)
    dgamma, a, b, cc = fused_bn.bn_dx_coeffs(gamma, inv, mean, dbeta,
                                             sum_dyx, m, dmean, dvar)
    dyc2d = fused_bn.bn_bwd_dx(y2d, dy2d, scale, shift, a, b, cc, relu, tm)
    dyc = dyc2d.reshape(y_conv.shape)
    # conv transposes through XLA (compute-bound; the bandwidth win above
    # is the epilogue): differentiate the equal lax conv.  The saved xp
    # carries a (stride-1) high-side slack row/col for the kernel's
    # fold-stride slab — the lax conv must see the slack-free pad or its
    # output gains a phantom row
    extra = stride - 1
    xpb = xp if extra == 0 else xp[:, :-extra, :-extra, :]
    wk = jnp.transpose(w, (2, 3, 1, 0))
    _, conv_vjp = jax.vjp(functools.partial(_lax_conv, stride=stride),
                          xpb, wk)
    dxp, dwk = conv_vjp(dyc)
    h = xpb.shape[1] - 2 * padding
    w_ = xpb.shape[2] - 2 * padding
    dx = dxp[:, padding:padding + h, padding:padding + w_, :]
    dw = jnp.transpose(dwk, (3, 2, 0, 1)).astype(w.dtype)
    return (dx, dw, dgamma.astype(gamma.dtype),
            dbeta.astype(beta_tag.dtype))


fused_conv_bn_act.defvjp(_fwd_rule, _bwd_rule)


# -- space-to-depth stem reorg ------------------------------------------------

STEM_BLOCK = 2


def stem_s2d_input(x):
    """[N,H,W,3] → pad-3 → space-to-depth(2) → [N,(H+6)/2,(W+6)/2,12].
    Channel order (dh, dw, c) — must match stem_s2d_weight."""
    n, h, w, c = x.shape
    b = STEM_BLOCK
    xp = jnp.pad(x, ((0, 0), (3, 3), (3, 3), (0, 0)))
    hp, wp = h + 6, w + 6
    x2 = xp.reshape(n, hp // b, b, wp // b, b, c)
    x2 = jnp.transpose(x2, (0, 1, 3, 2, 4, 5))
    return x2.reshape(n, hp // b, wp // b, b * b * c)


def stem_s2d_weight(w):
    """7×7/s2 OIHW weights [O,C,7,7] → the equivalent 4×4/s1 kernel over
    the s2d(2) channel layout, [O, 4·C, 4, 4].  Tap (2k+dh, 2l+dw) of the
    original lands at tap (k, l), channel (dh·2+dw)·C+c; the 8th tap row/
    col that stride-2 never reaches is zero-padded."""
    o, c, kh, kw = w.shape
    b = STEM_BLOCK
    wp = jnp.pad(w, ((0, 0), (0, 0), (0, 1), (0, 1)))        # 8×8 taps
    wr = wp.reshape(o, c, (kh + 1) // b, b, (kw + 1) // b, b)
    w2 = jnp.transpose(wr, (0, 3, 5, 1, 2, 4))   # [o, dh, dw, c, k, l]
    return w2.reshape(o, b * b * c, (kh + 1) // b, (kw + 1) // b)


def stem_supported(x_shape, w_shape, itemsize=4) -> bool:
    """The s2d reorg applies to the canonical 7×7/s2/p3 NHWC stem with an
    even input size, and only when the reorged conv itself passes
    ``supports`` — s2d WITHOUT the fused kernel was measured slower at
    the XLA level (r3: fwd 12.3 vs 8.4 ms) and must not re-ship."""
    if len(x_shape) != 4 or len(w_shape) != 4:
        return False
    n, h, w, c = x_shape
    cout, cin, kh, kw = w_shape
    if (kh, kw) != (7, 7) or cin != c or h % 2 != 0 or w % 2 != 0:
        return False
    s2d_x = (n, (h + 6) // 2, (w + 6) // 2, 4 * c)
    s2d_w = (cout, 4 * c, 4, 4)
    return supports(s2d_x, s2d_w, stride=1, padding=0, itemsize=itemsize)
