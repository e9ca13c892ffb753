"""Block-tiled flash attention as a Pallas TPU kernel (fwd + custom-VJP bwd).

Fills the fused-attention slot of the reference's fused-op family
(/root/reference/paddle/fluid/operators/fused/, e.g.
fused_attention-style kernels): instead of materializing the (Sq, Sk)
probability matrix in HBM, both passes stream K/V blocks through VMEM with an
online softmax, so HBM traffic is O(S*H) rather than O(S^2) and the matmuls
stay on the MXU.

Layout: (B, N, S, H) batch/heads/seq/head_dim, internally collapsed to
(B*N, S, H).  Supports causal masking, an additive bias/mask broadcastable
over batch or heads, head_dim 64/128/256, and any Sq/Sk that are multiples of
the block size (128).  The bias input is non-differentiable (its VJP is
zero); the nn.functional dispatch gate routes trainable masks to the XLA
path instead.

Runs compiled on TPU and in interpret mode on CPU (used by the grad-check
tests against the plain XLA softmax-attention path).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _mode

DEFAULT_BLOCK = 128
# Measured on v5e (chained-dispatch, bf16): larger blocks feed the MXU much
# better — bq=512/bk=1024 reaches 64 TF/s at S=4096 vs 10 TF/s with 128x128
# blocks (and 16 TF/s for the materializing XLA path).
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024
# Below this key length the materializing XLA softmax-attention is faster
# (dispatch- and bandwidth-bound regime); callers should prefer it.
MIN_SEQ_FOR_FLASH = 1024
_NEG_INF = -1e30  # finite mask value: exp(s - lse) underflows to exactly 0


def _pick_block(size: int, target: int) -> int:
    """Largest multiple of 128 that divides ``size`` and is <= target."""
    b = min(target, size)
    b -= b % 128
    while b > 128 and size % b:
        b -= 128
    return max(b, min(size, 128))


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, b_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, bq, bk, offset):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # bottom-right-aligned causal (query row i sees keys <= i + offset,
    # offset = Sk - Sq >= 0): the last k block with any valid column for
    # this q block, and whether this (iq, ik) pair contributes at all
    last = jnp.minimum(nk - 1, ((iq + 1) * bq - 1 + offset) // bk) \
        if causal else nk - 1
    run = (ik <= last) if causal else (ik >= 0)

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if b_ref is not None:
            s = s + b_ref[0, 0].astype(jnp.float32)
        if causal:
            row = lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + iq * bq
            col = lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + ik * bk
            s = jnp.where(row + offset >= col, s, _NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == last)
    def _finish():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse = m_scr[:, 0] + jnp.log(l_safe[:, 0])
        lse_ref[0] = jnp.broadcast_to(lse[None, :], lse_ref.shape[1:])


def _bias_spec(bias_shape, n_heads, bq, bk, qmajor=True):
    """BlockSpec for a (Bb, Nb, Sq, Sk) bias under the collapsed (B*N) grid,
    broadcasting over batch/head dims of size 1.  ``qmajor`` selects whether
    grid dim 1 is the q-block (fwd/dq) or the k-block (dkv) index."""
    Bb, Nb, Sq, Sk = bias_shape
    rows = Sq > 1

    def idx(b, i, j):
        iq, ik = (i, j) if qmajor else (j, i)
        bb = (b // n_heads) if Bb > 1 else 0
        nb = (b % n_heads) if Nb > 1 else 0
        return (bb, nb, iq if rows else 0, ik)

    return pl.BlockSpec((1, 1, bq if rows else 1, bk), idx)


def _flash_fwd_call(q3, k3, v3, bias4, n_heads, scale, causal, bq, bk):
    BN, Sq, H = q3.shape
    Sk = k3.shape[1]
    nq, nk = Sq // bq, Sk // bk
    grid = (BN, nq, nk)
    offset = Sk - Sq

    in_specs = [
        pl.BlockSpec((1, bq, H), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bk, H), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, bk, H), lambda b, i, j: (b, j, 0)),
    ]
    args = [q3, k3, v3]
    if bias4 is not None:
        in_specs.append(_bias_spec(bias4.shape, n_heads, bq, bk, qmajor=True))
        args.append(bias4)
        kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                                   bq=bq, bk=bk, offset=offset)
    else:
        kernel = functools.partial(
            lambda qr, kr, vr, o, ls, m, l, a, **kw: _fwd_kernel(
                qr, kr, vr, None, o, ls, m, l, a, **kw),
            scale=scale, causal=causal, bq=bq, bk=bk, offset=offset)

    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, H), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 8, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BN, Sq, H), q3.dtype),
            # lse rows replicated over 8 sublanes: Mosaic requires the last
            # two block dims to tile as (8, 128)
            jax.ShapeDtypeStruct((BN, 8, Sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, H), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=4 * BN * Sq * Sk * H // (2 if causal else 1),
            bytes_accessed=(2 * q3.size + k3.size + v3.size) * 2,
            transcendentals=BN * Sq * Sk),
        interpret=_mode.interpret(),
    )(*args)
    return out, lse


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, b_ref, dq_ref,
               dq_scr, *, scale, causal, bq, bk, offset):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    last = jnp.minimum(nk - 1, ((iq + 1) * bq - 1 + offset) // bk) \
        if causal else nk - 1
    run = (ik <= last) if causal else (ik >= 0)

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if b_ref is not None:
            s = s + b_ref[0, 0].astype(jnp.float32)
        if causal:
            row = lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + iq * bq
            col = lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + ik * bk
            s = jnp.where(row + offset >= col, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0, :][:, None])
        do = do_ref[0]
        dp = lax.dot_general(do, v_ref[0], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - dd_ref[0, 0, :][:, None]) * scale
        dq_scr[:] = dq_scr[:] + lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == last)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, b_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal, bq, bk,
                offset):
    ik = pl.program_id(1)
    iq = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    # this q block contributes iff its bottom row can see this k block
    run = ((iq + 1) * bq - 1 + offset >= ik * bk) if causal else (iq >= 0)

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if b_ref is not None:
            s = s + b_ref[0, 0].astype(jnp.float32)
        if causal:
            row = lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + iq * bq
            col = lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + ik * bk
            s = jnp.where(row + offset >= col, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0, :][:, None])
        do = do_ref[0]
        dv_scr[:] = dv_scr[:] + lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = lax.dot_general(do, v_ref[0], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - dd_ref[0, 0, :][:, None]) * scale
        dk_scr[:] = dk_scr[:] + lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(iq == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd_call(q3, k3, v3, bias4, out3, lse, do3, n_heads, scale,
                    causal, bq, bk):
    BN, Sq, H = q3.shape
    Sk = k3.shape[1]
    nq, nk = Sq // bq, Sk // bk

    # D_i = rowsum(dO * O): one cheap fused elementwise+reduce in XLA,
    # replicated over 8 sublanes to match the lse tiling
    dd = jnp.sum(do3.astype(jnp.float32) * out3.astype(jnp.float32),
                 axis=-1)  # (BN, Sq)
    dd = jnp.broadcast_to(dd[:, None, :], (BN, 8, Sq))

    common = dict(scale=scale, causal=causal, bq=bq, bk=bk,
                  offset=Sk - Sq)
    interp = _mode.interpret()

    def specs(qmajor):
        # index helpers: i is the "owner" block dim, j sweeps
        def qi(b, i, j):
            return (b, i, 0) if qmajor else (b, j, 0)

        def ki(b, i, j):
            return (b, j, 0) if qmajor else (b, i, 0)

        sp = [
            pl.BlockSpec((1, bq, H), qi),                     # q
            pl.BlockSpec((1, bk, H), ki),                     # k
            pl.BlockSpec((1, bk, H), ki),                     # v
            pl.BlockSpec((1, bq, H), qi),                     # do
            pl.BlockSpec((1, 8, bq), lambda b, i, j:
                         (b, 0, i) if qmajor else (b, 0, j)),  # lse
            pl.BlockSpec((1, 8, bq), lambda b, i, j:
                         (b, 0, i) if qmajor else (b, 0, j)),  # dd
        ]
        if bias4 is not None:
            sp.append(_bias_spec(bias4.shape, n_heads, bq, bk, qmajor=qmajor))
        return sp

    def wrap(kern):
        if bias4 is not None:
            return functools.partial(kern, **common)

        def no_bias(*refs, **kw):
            # insert b_ref=None after dd_ref (6 input refs without bias)
            return kern(*refs[:6], None, *refs[6:], **kw)
        return functools.partial(no_bias, **common)

    args = [q3, k3, v3, do3, lse, dd] + ([bias4] if bias4 is not None else [])

    dq = pl.pallas_call(
        wrap(_dq_kernel),
        grid=(BN, nq, nk),
        in_specs=specs(qmajor=True),
        out_specs=[pl.BlockSpec((1, bq, H), lambda b, i, j: (b, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((BN, Sq, H), q3.dtype)],
        scratch_shapes=[pltpu.VMEM((bq, H), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interp,
    )(*args)[0]

    dk, dv = pl.pallas_call(
        wrap(_dkv_kernel),
        grid=(BN, nk, nq),
        in_specs=specs(qmajor=False),
        out_specs=[
            pl.BlockSpec((1, bk, H), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, H), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BN, Sk, H), k3.dtype),
            jax.ShapeDtypeStruct((BN, Sk, H), v3.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, H), jnp.float32),
            pltpu.VMEM((bk, H), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interp,
    )(*args)
    return dq, dk, dv


# --------------------------------------------------------------------------
# custom-vjp wrapper
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _flash_core(n_heads, scale, causal, bq, bk, q3, k3, v3, bias4):
    out, _ = _flash_fwd_call(q3, k3, v3, bias4, n_heads, scale, causal,
                             bq, bk)
    return out


def _flash_core_fwd(n_heads, scale, causal, bq, bk, q3, k3, v3, bias4):
    out, lse = _flash_fwd_call(q3, k3, v3, bias4, n_heads, scale, causal,
                               bq, bk)
    return out, (q3, k3, v3, bias4, out, lse)


def _flash_core_bwd(n_heads, scale, causal, bq, bk, res, do3):
    q3, k3, v3, bias4, out, lse = res
    dq, dk, dv = _flash_bwd_call(q3, k3, v3, bias4, out, lse, do3,
                                 n_heads, scale, causal, bq, bk)
    dbias = None if bias4 is None else jnp.zeros_like(bias4)
    return dq, dk, dv, dbias


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def supports(q_shape, k_shape, bias_shape=None,
             block: int = DEFAULT_BLOCK, causal: bool = False) -> bool:
    """Shape gate: (B,N,S,H) with S multiples of the block and H MXU-friendly.
    Callers fall back to the plain XLA softmax-attention path otherwise."""
    if len(q_shape) != 4 or len(k_shape) != 4:
        return False
    Sq, H = q_shape[-2], q_shape[-1]
    Sk = k_shape[-2]
    if Sq % block or Sk % block:
        return False
    if causal and Sq > Sk:
        # bottom-right alignment would fully mask the top rows; semantics of
        # that corner differ between implementations — use the XLA path
        return False
    if H not in (64, 128, 256):
        return False
    if bias_shape is not None:
        if len(bias_shape) != 4 or bias_shape[-1] != Sk:
            return False
        if bias_shape[-2] not in (1, Sq):
            return False
        if bias_shape[0] not in (1, q_shape[0]):
            return False
        if bias_shape[1] not in (1, q_shape[1]):
            return False
    return True


def flash_attention_fn(q, k, v, bias=None, *, causal=False, scale=None,
                       block_q: int = DEFAULT_BLOCK_Q,
                       block_k: int = DEFAULT_BLOCK_K):
    """Pure-jax flash attention on (B, N, S, H) arrays (bias additive)."""
    B, N, Sq, H = q.shape
    Sk = k.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(H)
    if causal and Sq > Sk:
        raise ValueError(
            f"causal flash attention requires Sq <= Sk, got {Sq} > {Sk} "
            "(use the XLA attention path)")
    bq = _pick_block(Sq, block_q)
    bk = _pick_block(Sk, block_k)
    if causal and bq != bk:
        # equal blocks that divide BOTH lengths (a divisor of gcd), so no
        # trailing q/k block is dropped by the grid floor-division
        bq = bk = _pick_block(math.gcd(Sq, Sk), min(bq, bk))
    q3 = q.reshape(B * N, Sq, H)
    k3 = k.reshape(B * N, Sk, H)
    v3 = v.reshape(B * N, Sk, H)
    bias4 = None
    if bias is not None:
        bias4 = jnp.asarray(bias)
        while bias4.ndim < 4:
            bias4 = bias4[None]
    out = _flash_core(N, float(scale), bool(causal), bq, bk, q3, k3, v3,
                      bias4)
    return out.reshape(B, N, Sq, H)
