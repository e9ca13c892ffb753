"""Attention that keeps its scores on the chip: two Pallas TPU kernel
families (forward + custom-VJP backward) and the rule that picks between
them and the XLA path.

Fills the fused-attention slot of the reference's fused-op family
(/root/reference/paddle/fluid/operators/fused/, e.g.
fused_attention-style kernels): instead of materializing the (Sq, Sk)
probability matrix in HBM, both passes keep a block's float32 scores and
probabilities in VMEM, so HBM traffic is O(S*H) rather than O(S^2) and the
matmuls stay on the MXU.

* The SINGLE-BLOCK form (``packed_attention_fn``): ``[B, S, N*H]``
  operands as the projections write them, two heads of 64 (or one of 128)
  side by side on the 128 lanes, a head's WHOLE row of scores at hand
  (``Sk <= 1024``): no running rescale, ONE backward kernel, the plain
  softmax backward.  What BERT's training step runs at sequence 512.
* The BLOCKED form (``flash_attention_fn``): (B, N, S, H) collapsed to
  (B*N, S, H), K/V streamed through VMEM in blocks with an online softmax,
  two backward kernels; any Sq/Sk that are multiples of 128, head_dim
  64/128/256.  For what the first cannot hold.

Both take causal masking and an additive bias/mask broadcastable over batch
or heads.  The bias input is non-differentiable (its VJP is zero); the
nn.functional dispatch routes trainable masks to the XLA path instead.
``fused_form`` is the dispatch's table, from the shapes alone.

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
# The blocked form's blocks: the larger, the better it feeds the MXU.  At
# S = 512 (16 heads of 64, 8,192 tokens, forward + backward, chained, on
# the v5e; PERF.md section 6, PR 37) bq x bk = 128 x 512 took 2.78 ms,
# 256 x 512 2.32, 512 x 512 2.05.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024
# The dispatch's table (``fused_form``): ms a call of 8,192 tokens, 16
# heads of 64, bf16, forward / forward + backward, chained on the v5e
# (my chip runs, PR 37; the operands' way in and out of each form counted):
#
#      S    XLA             blocked         single block
#    128    0.052 / 0.440   0.765 / 2.255   0.119 / 0.534
#    256    0.393 / 1.135   0.781 / 2.198   0.160 / 0.587
#    512    0.779 / 2.633   0.749 / 2.047   0.244 / 0.795
#   1024    1.529 / 5.188   0.880 / 2.626   0.459 / 1.524
#   2048    3.132 / 10.06   1.430 / 4.494   (a row of scores no longer fits)
#    512 causal / with a key-padding bias:
#           0.774 / 2.710   0.738 / 2.046   0.226 / 0.796
#           0.772 / 2.624   0.754 / 2.078   0.262 / 0.866
#   1024 causal / with a key-padding bias:
#           1.544 / 5.316   1.081 / 3.032   0.352 / 1.087
#           1.528 / 5.188   0.883 / 2.647   0.484 / 1.542
#
# The single-block form wins from S = 256 up, the XLA path below.  The
# blocked form beats XLA from 512 up as a time, but stays where it was:
# at S = 512 in BERT's step its backward's ``rowsum(dO * O)`` failed the
# benchmark's ``delta_norm_rel`` (0.0252 against a limit of 0.0175, the
# last layer's query bias; see ``_packed_bwd_kernel``), so nothing new is
# sent to it.
MIN_SEQ_SINGLE_BLOCK = 256
MAX_SEQ_SINGLE_BLOCK = 1024
MIN_SEQ_BLOCKED = 1024
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


def _no_bias(kernel, at):
    """``kernel`` with ``b_ref=None`` put in at position ``at``."""
    def call(*refs, **kw):
        return kernel(*refs[:at], None, *refs[at:], **kw)
    return call


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
    kernel = functools.partial(
        _fwd_kernel if bias4 is not None else _no_bias(_fwd_kernel, 3),
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
        # (b_ref comes after dd_ref, the sixth input)
        return functools.partial(
            kern if bias4 is not None else _no_bias(kern, 6), **common)

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


# --------------------------------------------------------------------------
# the single-block form: a head's whole rows of scores stay in VMEM, and
# the heads lie side by side on the lanes as the projections wrote them
# --------------------------------------------------------------------------

LANES = 128
PACKED_HEAD_DIMS = (64, 128)
PACKED_VMEM_BYTES = 48 * 2 ** 20


def _own_lanes(hd):
    """Per head of a lane row the ``[1, LANES]`` mask of its own lanes
    (None where one head fills the row)."""
    if hd == LANES:
        return [None]
    lane = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    return [(lane >= h * hd) & (lane < (h + 1) * hd)
            for h in range(LANES // hd)]


def _keep(x, own):
    """``x`` on a head's own lanes, zero on its neighbour's: contracting
    over all the lanes is then the head's own product plus exact zeros."""
    return x if own is None else jnp.where(own, x, jnp.zeros((), x.dtype))


def _by_head(parts, owns):
    """One lane row from per-head results: each head's own lanes."""
    out = parts[-1]
    for part, own in zip(parts[-2::-1], owns[-2::-1]):
        out = jnp.where(own, part, out)
    return out


def _chunk_scores(qh, k, b_ref, h, r0, *, scale, causal, offset,
                  bias_rows, bias_heads):
    """float32 scores of one head's ``cq`` query rows from row ``r0``
    against the columns ``k`` holds (the first ``k.shape[0]``)."""
    cq, hi = qh.shape[0], k.shape[0]
    s = lax.dot_general(qh, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    if b_ref is not None:
        bh = h if bias_heads else 0
        b = b_ref[0, bh, r0:r0 + cq, :hi] if bias_rows \
            else b_ref[0, bh, :, :hi]
        s = s + b.astype(jnp.float32)
    if causal:
        row = lax.broadcasted_iota(jnp.int32, (cq, hi), 0) + r0
        col = lax.broadcasted_iota(jnp.int32, (cq, hi), 1)
        s = jnp.where(row + offset >= col, s, _NEG_INF)
    return s


def _columns_seen(c, cq, Sk, causal, offset):
    """The columns the chunk of query rows ``c`` can see, rounded out to
    whole lane rows: a causal chunk's products stop there."""
    if not causal:
        return Sk
    return min(Sk, -(-((c + 1) * cq + offset) // LANES) * LANES)


def _packed_fwd_kernel(q_ref, k_ref, v_ref, b_ref, o_ref, lse_ref, *,
                       hd, cq, lane_rows, **how):
    Sq, Sk = q_ref.shape[1], k_ref.shape[1]
    owns = _own_lanes(hd)
    g = len(owns)
    for r in range(lane_rows):
        lanes = slice(r * LANES, (r + 1) * LANES)
        for c in range(Sq // cq):
            r0 = c * cq
            hi = _columns_seen(c, cq, Sk, how["causal"], how["offset"])
            q = q_ref[0, r0:r0 + cq, lanes]
            k = k_ref[0, :hi, lanes]
            v = v_ref[0, :hi, lanes]
            outs = []
            for h, own in enumerate(owns):
                s = _chunk_scores(_keep(q, own), k, b_ref, r * g + h, r0,
                                  **how)
                m = jnp.max(s, axis=-1, keepdims=True)
                p = jnp.exp(s - m)
                l = jnp.sum(p, axis=-1, keepdims=True)
                l = jnp.where(l == 0, 1.0, l)
                outs.append(lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32) / l)
                lse = m[:, 0] + jnp.log(l[:, 0])
                lse_ref[0, r * g + h, :, r0:r0 + cq] = jnp.broadcast_to(
                    lse[None, :], (8, cq))
            o_ref[0, r0:r0 + cq, lanes] = _by_head(outs, owns) \
                .astype(o_ref.dtype)


def _packed_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, b_ref,
                       dq_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                       hd, cq, lane_rows, **how):
    """``dq``, ``dk`` and ``dv`` of a lane row's heads from ONE recomputed
    ``p`` a (head, chunk): seven products and one ``exp`` pass where the
    two-kernel backward pays nine and two.  The whole row is at hand, so
    the softmax's backward is the plain one, ``ds = p (dp - sum_j p dp)``
    from the SAME ``p`` and ``dp``: every row of ``ds`` sums to zero
    before it is rounded, as the XLA path's does.  (``rowsum(dO * O)``,
    the blocked form's way to that sum, differs from it by ``O``'s
    rounding, and the difference times the keys' common component is a
    gradient nobody asked for: it cost the last layers' query bias the
    benchmark's ``delta_norm_rel`` on the chip.)"""
    Sq, Sk = q_ref.shape[1], k_ref.shape[1]
    owns = _own_lanes(hd)
    g = len(owns)
    scale = how["scale"]
    for r in range(lane_rows):
        lanes = slice(r * LANES, (r + 1) * LANES)
        for c in range(Sq // cq):
            r0 = c * cq
            hi = _columns_seen(c, cq, Sk, how["causal"], how["offset"])
            q = q_ref[0, r0:r0 + cq, lanes]
            do = do_ref[0, r0:r0 + cq, lanes]
            k = k_ref[0, :hi, lanes]
            v = v_ref[0, :hi, lanes]
            dqs, dks, dvs = [], [], []
            for h, own in enumerate(owns):
                s = _chunk_scores(_keep(q, own), k, b_ref, r * g + h, r0,
                                  **how)
                p = jnp.exp(s - lse_ref[0, r * g + h, 0, r0:r0 + cq][:, None])
                dp = lax.dot_general(_keep(do, own), v,
                                     (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
                pdp = p * dp
                # (the scale waits for the products' [.., LANES] results)
                ds = (pdp - p * jnp.sum(pdp, axis=-1, keepdims=True)) \
                    .astype(q.dtype)
                dqs.append(lax.dot_general(
                    ds, k, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
                dvs.append(lax.dot_general(
                    p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
                dks.append(lax.dot_general(
                    ds, q, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
            dq_ref[0, r0:r0 + cq, lanes] = \
                (_by_head(dqs, owns) * scale).astype(dq_ref.dtype)
            dk, dv = _by_head(dks, owns), _by_head(dvs, owns)
            if c == 0:
                dk_scr[:hi] = dk
                dv_scr[:hi] = dv
                if hi < Sk:
                    dk_scr[hi:] = jnp.zeros((Sk - hi, LANES), jnp.float32)
                    dv_scr[hi:] = jnp.zeros((Sk - hi, LANES), jnp.float32)
            else:
                dk_scr[:hi] = dk_scr[:hi] + dk
                dv_scr[:hi] = dv_scr[:hi] + dv
        dk_ref[0, :, lanes] = (dk_scr[:] * scale).astype(dk_ref.dtype)
        dv_ref[0, :, lanes] = dv_scr[:].astype(dv_ref.dtype)


def _packed_call(kernel, bias_at, name, q, k, bias4, hd, scale, causal, cq,
                 lane_rows, **kw):
    """``pallas_call`` of a single-block kernel, and the block specs its
    callers lay out: q-shaped, k-shaped, the lse's, and the bias's as a
    list (empty without one).  A grid step takes ``lane_rows * 128``
    lanes of one batch row."""
    B, Sq, E = q.shape
    Sk = k.shape[1]
    width = LANES * lane_rows
    heads = width // hd

    def row(S):
        return pl.BlockSpec((1, S, width), lambda b, j: (b, 0, j))

    lse = pl.BlockSpec((1, heads, 8, Sq), lambda b, j: (b, j, 0, 0))
    bias = []
    how = dict(hd=hd, cq=min(cq, Sq), lane_rows=lane_rows, scale=scale,
               causal=causal, offset=Sk - Sq, bias_rows=False,
               bias_heads=False)
    if bias4 is not None:
        Bb, Nb, rows, _ = bias4.shape
        bias = [pl.BlockSpec(
            (1, heads if Nb > 1 else 1, rows, Sk),
            lambda b, j: (b if Bb > 1 else 0, j if Nb > 1 else 0, 0, 0))]
        how.update(bias_rows=rows > 1, bias_heads=Nb > 1)
    else:
        kernel = _no_bias(kernel, bias_at)
    call = functools.partial(
        pl.pallas_call, functools.partial(kernel, **how),
        grid=(B, E // width), name=name,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=PACKED_VMEM_BYTES),
        interpret=_mode.interpret(), **kw)
    return call, row(Sq), row(Sk), lse, bias


def _packed_fwd_call(q, k, v, bias4, hd, *how):
    B, Sq, E = q.shape
    Sk, N = k.shape[1], E // hd
    call, qs, ks, lses, bs = _packed_call(
        _packed_fwd_kernel, 3, "attention_single_block_fwd", q, k, bias4,
        hd, *how,
        cost_estimate=pl.CostEstimate(
            flops=4 * B * N * Sq * Sk * hd,
            bytes_accessed=(2 * q.size + k.size + v.size) * q.dtype.itemsize,
            transcendentals=B * N * Sq * Sk))
    return call(
        in_specs=[qs, ks, ks] + bs,
        out_specs=[qs, lses],
        out_shape=[
            jax.ShapeDtypeStruct((B, Sq, E), q.dtype),
            # lse rows replicated over 8 sublanes, as the blocked form's
            jax.ShapeDtypeStruct((B, N, 8, Sq), jnp.float32),
        ],
    )(q, k, v, *([] if bias4 is None else [bias4]))


def _packed_bwd_call(q, k, v, bias4, lse, do, hd, *how):
    B, Sq, E = q.shape
    Sk, N = k.shape[1], E // hd
    call, qs, ks, lses, bs = _packed_call(
        _packed_bwd_kernel, 5, "attention_single_block_bwd", q, k, bias4,
        hd, *how,
        cost_estimate=pl.CostEstimate(
            flops=10 * B * N * Sq * Sk * hd,
            bytes_accessed=(3 * q.size + 4 * k.size) * q.dtype.itemsize,
            transcendentals=B * N * Sq * Sk))
    return call(
        in_specs=[qs, ks, ks, qs, lses] + bs,
        out_specs=[qs, ks, ks],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((Sk, LANES), jnp.float32),
                        pltpu.VMEM((Sk, LANES), jnp.float32)],
    )(q, k, v, do, lse, *([] if bias4 is None else [bias4]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _packed_core(hd, scale, causal, cq, lane_rows, q, k, v, bias4):
    return _packed_fwd_call(q, k, v, bias4, hd, scale, causal, cq,
                            lane_rows)[0]


def _packed_core_fwd(hd, scale, causal, cq, lane_rows, q, k, v, bias4):
    out, lse = _packed_fwd_call(q, k, v, bias4, hd, scale, causal, cq,
                                lane_rows)
    return out, (q, k, v, bias4, lse)


def _packed_core_bwd(hd, scale, causal, cq, lane_rows, res, do):
    q, k, v, bias4, lse = res
    dq, dk, dv = _packed_bwd_call(q, k, v, bias4, lse, do, hd, scale,
                                  causal, cq, lane_rows)
    return dq, dk, dv, None if bias4 is None else jnp.zeros_like(bias4)


_packed_core.defvjp(_packed_core_fwd, _packed_core_bwd)


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


def _bias4(bias):
    """An additive bias as the kernels take it: four dimensions, leading
    ones added; None stays None."""
    if bias is None:
        return None
    bias = jnp.asarray(bias)
    return bias.reshape((1,) * (4 - bias.ndim) + bias.shape)


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
    out = _flash_core(N, float(scale), bool(causal), bq, bk, q3, k3, v3,
                      _bias4(bias))
    return out.reshape(B, N, Sq, H)


def supports_packed(q_shape, k_shape, bias_shape=None,
                    causal: bool = False) -> bool:
    """Shape gate of the single-block form, on the ``(B, N, S, H)`` shapes
    the blocked form is asked about: what :func:`supports` admits, with
    whole lane rows of heads (``N * H`` a multiple of 128, ``H`` 64 or
    128) and both lengths at most ``MAX_SEQ_SINGLE_BLOCK`` (a chunk's
    rows of float32 scores, four arrays of them backward, in VMEM)."""
    if not supports(q_shape, k_shape, bias_shape, causal=causal):
        return False
    N, Sq, H = q_shape[1], q_shape[2], q_shape[3]
    return H in PACKED_HEAD_DIMS and (N * H) % LANES == 0 \
        and k_shape[1] == N and max(Sq, k_shape[2]) <= MAX_SEQ_SINGLE_BLOCK


def _packed_plan(Sq, Sk, E, hd, causal=False, bias4=None):
    """``(chunk, lane_rows)`` of a program from the shape: query rows in
    chunks of 512 where they divide (256 under a causal mask, whose
    chunks stop at the last column they can see: the measured plans,
    PERF.md section 6, PR 37), and as many lane rows a grid step
    as keep about 2,048 rows of operands in it (at BERT's S = 512, four:
    the per-step overhead of 128 programs a call was 3-5% of the call)
    and a bias's block, if it has one row a query, within 8 MiB: the
    step's VMEM stays far under the limit."""
    chunk = next(c for c in ((256, 128) if causal else (512, 256, 128))
                 if Sq % c == 0)
    per_row = LANES // hd * Sq * Sk * bias4.dtype.itemsize \
        if bias4 is not None and bias4.shape[1] > 1 and bias4.shape[2] > 1 \
        else 0
    lane_rows = 1
    while lane_rows * 2 * max(Sq, Sk) <= 2048 \
            and (E // LANES) % (lane_rows * 2) == 0 \
            and lane_rows * 2 * per_row <= 8 * 2 ** 20:
        lane_rows *= 2
    return chunk, lane_rows


def packed_attention_fn(q, k, v, num_heads, bias=None, *, causal=False,
                        scale=None, chunk=None, lane_rows=None):
    """Attention of ``[B, Sq, N*H]`` queries over ``[B, Sk, N*H]`` keys and
    values, heads side by side on the minor dimension as the projections
    write them; ``bias`` additive, ``(Bb, Nb, Sq|1, Sk)``.  Returns ``[B,
    Sq, N*H]``.  A program holds one batch row's ``lane_rows * 128`` lanes
    (two heads of 64, or one of 128, a lane row) with ALL of their keys,
    so a chunk of ``chunk`` query rows has its whole rows of float32
    scores in VMEM: no running rescale forward, and one backward kernel
    that recomputes ``p`` once for ``dq``, ``dk`` and ``dv``.  No operand
    is transposed or padded on its way in or out.  ``chunk`` and
    ``lane_rows`` default to :func:`_packed_plan`'s."""
    B, Sq, E = q.shape
    hd = E // num_heads
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    bias4 = _bias4(bias)
    plan = _packed_plan(Sq, k.shape[1], E, hd, bool(causal), bias4)
    return _packed_core(hd, float(scale), bool(causal),
                        int(chunk or plan[0]), int(lane_rows or plan[1]),
                        q, k, v, bias4)


def fused_form(q_shape, k_shape, bias_shape=None, causal: bool = False,
               packed: bool = False):
    """Which kernel an un-cached call site of ``(B, N, S, H)`` shapes
    takes, from the shapes alone: ``"single_block"``, ``"blocked"`` or
    None (the one-expression XLA path is the faster, or no kernel
    supports the shape).  ``packed``: the caller holds ``[B, S, N*H]``
    operands (the single-block form reads them as they lie; the blocked
    form and the XLA path want the heads split off and transposed)."""
    if packed and supports_packed(q_shape, k_shape, bias_shape, causal) \
            and k_shape[2] >= MIN_SEQ_SINGLE_BLOCK:
        return "single_block"
    if supports(q_shape, k_shape, bias_shape, causal=causal) \
            and k_shape[2] >= MIN_SEQ_BLOCKED:
        return "blocked"
    return None
