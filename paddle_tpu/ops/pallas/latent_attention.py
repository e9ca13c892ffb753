"""A wide block of queries over a latent plane, per head, in ONE kernel.

The per-head form of the latent family's cached attention
(``nn/functional/attention.py::per_head_products``) expands each block of
cache rows into per-head keys and values, scores them, and weighs the
values with a running softmax.  As an XLA loop every one of those pieces
crosses memory once a column block: the expanded ``k_n`` / ``v``, the
float32 scores, the probabilities and the ``[T, d_v]`` accumulator.  Here
the whole pass over a row's live column blocks is one Pallas TPU kernel: a
program holds ``heads`` heads of one batch row, walks the blocks ``lo <= i
< hi`` (run-time scalars, prefetched: the loop is INSIDE the kernel, so a
dead block costs nothing) with the block's latent rows ``[block,
row_width]`` copied in by hand, two blocks in flight, and keeps everything
else in VMEM.  Only the rows (shared by the program's heads), the queries,
``w_uk`` / ``w_uv``, the mask and the final ``[T, H, d_v]`` output cross
HBM.

The numbers are the loop's: bfloat16 (the plane's dtype) operands, float32
sums, keys and values rounded to the plane's dtype after their expansion,
the scores of ``q_nope ‖ q_rope`` against ``k_n ‖ rotary key`` in one
contraction, probabilities rounded before the weighted sum, the same
absolute column blocks, masked scores exactly ``-1e30``.

The mask is one of two: a ``keep [B, T, S]`` array (a selector's
membership, a window layer's ring mask), read a ``[T, block]`` slice a
block, or, without one, ``start[b] <= column <= pos + t`` from two
prefetched scalars.

``fused_latent_form`` is the rule of what takes the kernel, and the table
it was set from is beside it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _mode

LANES = 128
_NEG = -1e30            # nn/functional/attention.py's mask value
VMEM_BYTES = 64 * 2 ** 20
# a program's heads: the block's rows and its mask are fetched and the
# additive mask is built once a (program, block), so the more heads share
# them the less they cost (4 heads 2.91 ms, 8 heads 2.77 at dots3's width
# over 8 blocks).  VMEM bounds them, and the kernel's text, which holds a
# copy of the head's work a head (``_plan``)
MAX_HEADS = 8
HEADS_VMEM_BYTES = 40 * 2 ** 20
# What takes the kernel (``fused_latent_form``): every shape it supports.
# Two tables, both my chip runs of PR 42 on the v5e (PERF.md section 6).
#
# The attention ALONE, ms a call of 512 queries at batch 1 over column
# blocks of 512, bf16, chained (XLA: ``latent_attend_blocked(
# per_head_products(..))``, for the window layer the one pass
# ``latent_attend``; kernel: 8 heads a program):
#
#   H, d_n/d_r/d_v, r_kv, mask            blocks   XLA     kernel
#   128, 128/64/128,  512, membership        8     5.53     2.77
#     (dots3's full layers)                  2     1.58     0.95
#    64, 192/64/128, 1024, ring mask         2     1.38     0.71
#     (dots3's window layers: ONE pass over the ring's 1,024 columns)
#    64, 128/64/128,  512, causal           16     2.45     2.62
#     (kimi)                                 4     0.75     0.80
#    64, 192/64/256,  512, membership       16     3.63     3.53
#     (glm5)                                 4     1.07     1.10
#
# A head's block costs the kernel 2.4 us at 128/64/128 (65% of the MXU's
# peak; 1.7 us is what its 20 passes of 512 rows take on four MXUs).  The
# XLA loop alone is as fast while the float32 scores of one of its passes
# are 64 MiB (H 64 over 512 x 512) and takes twice as long at 128 MiB (H
# 128; the window's 1,024 columns in one pass).
#
# The SERVED chunk, ``batch_job_s`` of the benchmark's cell, the loop
# against the kernel on one seed (in the program the loop also relays the
# batch-1 row it slices and copies ``w_uk`` / ``w_uv`` in every run,
# tools/kv_layout_check.py; the kernel takes all three as they lie):
#
#   dots3 (2 full + 3 window layers)   23.25 / 23.46 -> 16.04 / 16.57
#   kimi  (5 layers, H 64)             25.48 -> 23.33
#   glm5  (5 layers, H 64, 192/64/256) 26.80 -> 25.13
#
# So no shape the kernel supports is kept from it.


def _dot(a, b, contract):
    return lax.dot_general(a, b, (contract, ((), ())),
                           preferred_element_type=jnp.float32)


def _kernel(meta_ref, q_ref, wuk_ref, wuv_ref, plane_ref, *rest,
            heads, block, r_kv, d_n, d_r, scale, masked):
    if masked:
        keep_ref, o_ref, rows_buf, keep_buf, sems, bias_scr, kcat_scr, \
            m_scr, l_scr, acc_scr = rest
    else:
        o_ref, rows_buf, sems, bias_scr, kcat_scr, m_scr, l_scr, \
            acc_scr = rest
    T, d_v = acc_scr.shape[1:]
    b = pl.program_id(0)
    lo, hi = meta_ref[0], meta_ref[1]

    def copies(i, slot):
        s0 = pl.multiple_of(i * block, block)
        cps = [pltpu.make_async_copy(plane_ref.at[b, pl.ds(s0, block), :],
                                     rows_buf.at[slot], sems.at[0, slot])]
        if masked:
            cps.append(pltpu.make_async_copy(
                keep_ref.at[b, :, pl.ds(s0, block)], keep_buf.at[slot],
                sems.at[1, slot]))
        return cps

    m_scr[...] = jnp.full(m_scr.shape, _NEG, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(lo < hi)
    def _first():
        for c in copies(lo, 0):
            c.start()

    def one_block(i, carry):
        slot = lax.rem(i - lo, 2)
        for c in copies(i, slot):
            c.wait()

        @pl.when(i + 1 < hi)
        def _next():
            for c in copies(i + 1, 1 - slot):
                c.start()

        # the block's mask, additive: 0 where kept, -1e30 where not (a
        # float32 score plus -1e30 IS -1e30, so this is the loop's
        # ``where(keep, s, -1e30)`` to the bit), built once for the heads
        if masked:
            kept = keep_buf[slot].astype(jnp.float32) > 0
        else:
            col = i * block + lax.broadcasted_iota(jnp.int32, (T, block), 1)
            row = meta_ref[2] + lax.broadcasted_iota(jnp.int32, (T, block), 0)
            kept = (col >= meta_ref[3 + b]) & (col <= row)
        bias_scr[...] = jnp.where(kept, 0.0, _NEG)
        # the rotary keys are every head's: beside the head's own
        kcat_scr[:, d_n:] = rows_buf[slot, :, r_kv:r_kv + d_r]

        # the heads one after another in ONE basic block (not a loop): the
        # scheduler lays a head's products under its neighbour's softmax
        # (a loop over the heads took 3.42 ms where this takes 2.93, 512
        # queries of 128 heads over 8 blocks; table below)
        for g in range(heads):
            lat = rows_buf[slot, :, :r_kv]
            dt = lat.dtype
            kcat_scr[:, :d_n] = _dot(lat, wuk_ref[g], ((1,), (0,))).astype(dt)
            v = _dot(lat, wuv_ref[g], ((1,), (0,))).astype(dt)
            s = _dot(q_ref[0, g], kcat_scr[...], ((1,), (1,))) * scale \
                + bias_scr[...]
            m_prev = m_scr[g, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_scr[g, :, :1] * corr + jnp.sum(p, axis=-1,
                                                     keepdims=True)
            acc_scr[g] = acc_scr[g] * corr + _dot(p.astype(dt), v,
                                                  ((1,), (0,)))
            m_scr[g] = jnp.broadcast_to(m_new, (T, LANES))
            l_scr[g] = jnp.broadcast_to(l_new, (T, LANES))
        return carry

    lax.fori_loop(lo, hi, one_block, 0)
    for g in range(heads):
        l = jnp.maximum(l_scr[g, :, :1], 1e-30)
        o_ref[0, :, g * d_v:(g + 1) * d_v] = acc_scr[g] / l


def _plan(H, T, d_n, d_r, d_v, r_kv, itemsize):
    """Heads a program: the most (a power of two that divides ``H``, at
    most ``MAX_HEADS``) whose blocks fit ``HEADS_VMEM_BYTES``, counting
    what the pipeline holds twice (queries, weights, the output block)
    and the running sums; the rows, the mask and a block's float32 scores
    are the rest of ``VMEM_BYTES``."""
    pad = lambda n: -(-n // LANES) * LANES                       # noqa: E731
    per_head = 2 * itemsize * (T * pad(d_n + d_r)
                               + r_kv * (pad(d_n) + pad(d_v))) \
        + 2 * 4 * T * d_v + 4 * T * (2 * LANES + d_v)
    heads = 1
    while heads * 2 <= MAX_HEADS and H % (heads * 2) == 0 \
            and heads * 2 * per_head <= HEADS_VMEM_BYTES:
        heads *= 2
    return heads


def latent_chunk_attention_fn(q, w_uk, w_uv, plane, start, pos, lo, hi, *,
                              r_kv, scale, block, keep=None, heads=None):
    """``sum_s p(s) v(s)`` of per-head latent attention, ``[B, T, H,
    d_v]`` float32: queries ``q [B, H, T, d_n + d_r]`` (``q_nope ‖
    q_rope``) over the column blocks ``lo <= i < hi`` (``block`` columns
    each; int32 scalars, traced or not) of ``plane [B, S, K]`` (rows
    ``latent ‖ rotary key ‖ padding``), expanded by ``w_uk [H, r_kv,
    d_n]`` and ``w_uv [H, r_kv, d_v]``.  ``keep [B, T, S]`` (any dtype
    whose zero means masked) is the mask; without it a query at column
    ``pos + t`` of row ``b`` keeps the columns ``start[b] .. pos + t``.
    ``heads`` a program defaults to :func:`_plan`'s.

    The call is a ``jax.jit`` of its own inside the caller's program:
    layers of one shape share ONE trace of the kernel's body (its text
    holds a copy of a head's work a head; traced once a call site it cost
    a 5-layer chunk program 4.5 s of every start, cache warm or not)."""
    B, H, T, dq = q.shape
    d_n, d_v = w_uk.shape[-1], w_uv.shape[-1]
    G = int(heads or _plan(H, T, d_n, dq - d_n, d_v, r_kv,
                           plane.dtype.itemsize))
    return _call(q, w_uk, w_uv, plane, jnp.asarray(start, jnp.int32),
                 *(jnp.asarray(x, jnp.int32) for x in (pos, lo, hi)), keep,
                 r_kv=int(r_kv), scale=float(scale), block=int(block),
                 heads=G, interpret=_mode.interpret())


@functools.partial(jax.jit, static_argnames=("r_kv", "scale", "block",
                                             "heads", "interpret"))
def _call(q, w_uk, w_uv, plane, start, pos, lo, hi, keep, *, r_kv, scale,
          block, heads, interpret):
    B, H, T, dq = q.shape
    d_n, d_v = w_uk.shape[-1], w_uv.shape[-1]
    d_r, G = dq - d_n, heads
    S, K = plane.shape[1:]
    meta = jnp.concatenate([jnp.stack([lo, hi, pos]), start.reshape(B)])
    masked = keep is not None
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [
        pl.BlockSpec((1, G, T, dq), lambda b, j, meta: (b, j, 0, 0)),
        pl.BlockSpec((G, r_kv, d_n), lambda b, j, meta: (j, 0, 0)),
        pl.BlockSpec((G, r_kv, d_v), lambda b, j, meta: (j, 0, 0)),
        any_space] + [any_space] * masked
    scratch = [pltpu.VMEM((2, block, K), plane.dtype)] \
        + [pltpu.VMEM((2, T, block), jnp.int8)] * masked \
        + [pltpu.SemaphoreType.DMA((2, 2)),
           pltpu.VMEM((T, block), jnp.float32),
           pltpu.VMEM((block, dq), plane.dtype),
           pltpu.VMEM((G, T, LANES), jnp.float32),
           pltpu.VMEM((G, T, LANES), jnp.float32),
           pltpu.VMEM((G, T, d_v), jnp.float32)]
    blocks = S // block                 # (the cost of a plane read whole)
    out = pl.pallas_call(
        functools.partial(_kernel, heads=G, block=block, r_kv=r_kv, d_n=d_n,
                          d_r=d_r, scale=scale, masked=masked),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, H // G), in_specs=in_specs,
            out_specs=pl.BlockSpec((1, T, G * d_v),
                                   lambda b, j, meta: (b, 0, j)),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((B, T, H * d_v), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * B * H * blocks * block
            * (r_kv * (d_n + d_v) + T * (d_n + d_r + d_v)),
            bytes_accessed=B * (H // G) * S * K * plane.dtype.itemsize
            + q.size * 2 + B * T * H * d_v * 4,
            transcendentals=B * H * T * S),
        name="latent_attention_per_head",
        interpret=interpret,
    )(meta, q, w_uk, w_uv, plane,
      *([keep.astype(jnp.int8)] if masked else []))
    return out.reshape(B, T, H, d_v)


def supports_latent(T, block, d_n, d_r, d_v, r_kv, row_width) -> bool:
    """Shape gate: the query block and the column block whole multiples
    of 128, every width on the lane grid (``d_r`` may be half a lane
    row: it is only ever contracted)."""
    return T % LANES == 0 and block % LANES == 0 \
        and d_n % 64 == 0 and d_r % 64 == 0 and d_v % LANES == 0 \
        and r_kv % LANES == 0 and row_width % LANES == 0 \
        and r_kv + d_r <= row_width


def fused_latent_form(T, block, d_n, d_r, d_v, r_kv, row_width,
                      heads) -> bool:
    """Whether a per-head pass of ``T`` queries of ``heads`` heads over
    column blocks of ``block`` takes the kernel, from the shapes alone:
    what :func:`supports_latent` admits and an even number of heads (two
    share a block's rows at least).  The tables above found no supported
    shape at which the served chunk lost."""
    return supports_latent(T, block, d_n, d_r, d_v, r_kv, row_width) \
        and heads % 2 == 0
