"""A decode step's one query a row over the row's OWN columns, in ONE kernel.

The XLA form of the step's attention over ring planes ``[B, G, C, L]``
(``nn/functional/attention.py::_decode_span_loops``) reads, for every row
of the batch, every column block from the oldest generating row's
``start`` to the frontier: a batched contraction cannot skip per row.
Here one Pallas TPU program walks the rows that generate (``start[b] <
end[b]``, two prefetched scalars a row) and, of each, only the blocks
``[start[b] // block, ceil(end[b] / block))``: the planes stay where they
lie in HBM and a block of a row, all ``G`` lane rows of it at once, is
copied in by hand.  A row that generates nothing issues no copy.

The copies are ONE stream over (row, K or V, block), ``buffers`` of them
in flight, so the next row's first K block is on its way while this row's
V blocks are weighed: the kernel is as fast as the planes' bytes arrive.

The numbers are the loops': the queries spread over their lane row
(``_spread_queries``), float32 scores times ``1 / sqrt(head_dim)``, masked
scores exactly ``-1e30``, ONE softmax over the row's scores (they wait in
VMEM between the two passes), probabilities rounded to the query's dtype
before they weigh V, float32 accumulation.  What the loops write
``e / sum`` is ``e * (1 / sum)`` here.  A row that generates nothing reads
zeros.  The caller keeps each head's own lanes (``_own_lanes``).
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
VMEM_BYTES = 48 * 2 ** 20
# copies in flight: as many as hold ``FLIGHT_BYTES`` (a copy is ``G x block
# x L``: 425 KB at GPT-2 XL's 13 lane rows, 131 KB at lfm2's 4, 65 KB at
# nemotron3's 2; the fewer bytes a copy, the more of them hide its latency)
FLIGHT_BYTES = 2 ** 21
MIN_BUFFERS, MAX_BUFFERS = 3, 8
# a row's float32 scores wait in VMEM between the two passes, a tile of
# query rows a lane row: ``G x C x 16 x 4`` bytes of this many at most
# (GPT-2 XL's 13 lane rows x 1,024 columns are 0.85 MB)
SCORE_BYTES = 24 * 2 ** 20


def _sublanes(dtype):
    """Rows of a VMEM tile: 8 of float32, 16 of bfloat16."""
    return 32 // jnp.dtype(dtype).itemsize


def supports_span_decode(plane_shape, dtype, block) -> bool:
    """Shape gate: bf16 / f32 planes ``[B, G, C, L]`` whose lane rows are
    whole (``L`` a multiple of 128) and whose blocks start on a tile's
    edge, the last one of a plane they do not divide included (it starts
    at ``C - block``); a row's scores fit ``SCORE_BYTES``."""
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16),
                                jnp.dtype(jnp.float32)):
        return False
    G, C, L = (int(n) for n in plane_shape[1:])
    tile = _sublanes(dtype)
    return L % LANES == 0 and block <= C and block % tile == 0 \
        and C % tile == 0 and G * C * 16 * 4 <= SCORE_BYTES


def _dot(a, b, contract):
    return lax.dot_general(a, b, (contract, ((), ())),
                           preferred_element_type=jnp.float32)


def _kernel(meta_ref, q_ref, k_ref, v_ref, o_ref, buf, sems, s_scr, m_scr,
            l_scr, acc_scr, *, rows, cols, block, buffers, scale):
    G, Jp, L = acc_scr.shape
    B, C = rows, cols
    ragged = C % block != 0

    def start_of(r):
        return meta_ref[r]

    def end_of(r):
        return jnp.minimum(meta_ref[B + r], C)

    def next_live(r):
        """The first row at or after ``r`` that generates; ``B``: none."""
        def dead(r):
            at = jnp.minimum(r, B - 1)
            return (r < B) & (start_of(at) >= end_of(at))
        return lax.while_loop(dead, lambda r: r + 1, r)

    def span(r):
        at = jnp.minimum(r, B - 1)
        return jnp.maximum(start_of(at), 0) // block, \
            (end_of(at) + (block - 1)) // block

    def col0(i):
        # the last block of a plane that is no multiple of ``block``
        # starts early (and masks what the one before it holds)
        return jnp.minimum(i * block, C - block) if ragged \
            else pl.multiple_of(i * block, block)

    def copy(plane_ref, r, i, slot):
        return pltpu.make_async_copy(
            plane_ref.at[r, :, pl.ds(col0(i), block), :], buf.at[slot],
            sems.at[slot])

    # -- the stream of copies: (row, K then V, block), ``n`` counted ----------
    def fetch(cur):
        """Start the copy the cursor stands at (none past the last row)
        and move it on."""
        r, plane, i, n = cur
        at = jnp.minimum(r, B - 1)
        slot = lax.rem(n, buffers)

        @pl.when((r < B) & (plane == 0))
        def _k():
            copy(k_ref, at, i, slot).start()

        @pl.when((r < B) & (plane == 1))
        def _v():
            copy(v_ref, at, i, slot).start()

        lo, hi = span(r)
        last = i + 1 >= hi
        to_v = last & (plane == 0)
        to_row = last & (plane == 1)
        r2 = lax.cond(to_row, lambda: next_live(r + 1), lambda: r)
        i2 = jnp.where(last, jnp.where(to_v, lo, span(r2)[0]), i + 1)
        return r2, jnp.where(to_v, 1, jnp.where(to_row, 0, plane)), i2, n + 1

    def take(c, cur):
        """Wait for copy ``c``; its slot's neighbour is free: refill it."""
        slot = lax.rem(c, buffers)
        copy(k_ref, 0, 0, slot).wait()
        return slot, fetch(cur)

    def one_row(carry):
        r, c, cur = carry
        st, en = start_of(r), end_of(r)
        lo, hi = span(r)
        dt = q_ref.dtype
        m_scr[...] = jnp.full(m_scr.shape, _NEG, jnp.float32)

        def score(i, carry):
            c, cur = carry
            slot, cur = take(c, cur)
            col = col0(i) + lax.broadcasted_iota(jnp.int32, (1, block), 1)
            valid = (col >= st) & (col < en)
            if ragged:
                valid = valid & (col >= i * block)
            for g in range(G):
                s = _dot(q_ref[r, g], buf[slot, g], ((1,), (1,))) * scale
                s = jnp.where(valid, s, _NEG)
                s_scr[i, g] = s
                m_scr[g] = jnp.maximum(m_scr[g], s)
            return c + 1, cur

        c, cur = lax.fori_loop(lo, hi, score, (c, cur))
        # ONE softmax over the row's scores: the blocks' maxima and sums
        # are kept lane by lane and folded once a row
        m_scr[...] = jnp.broadcast_to(
            jnp.max(m_scr[...], axis=-1, keepdims=True), m_scr.shape)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)

        def exps(i, carry):
            e = jnp.exp(s_scr[i] - m_scr[...])
            s_scr[i] = e
            l_scr[...] += e
            return carry

        lax.fori_loop(lo, hi, exps, 0)
        l_scr[...] = jnp.broadcast_to(
            1.0 / jnp.sum(l_scr[...], axis=-1, keepdims=True), l_scr.shape)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

        def weigh(i, carry):
            c, cur = carry
            slot, cur = take(c, cur)
            for g in range(G):
                p = (s_scr[i, g] * l_scr[g]).astype(dt)
                acc_scr[g] += _dot(p, buf[slot, g], ((1,), (0,)))
            return c + 1, cur

        c, cur = lax.fori_loop(lo, hi, weigh, (c, cur))
        o_ref[r] = acc_scr[...].astype(o_ref.dtype)
        return next_live(r + 1), c, cur

    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
    first = next_live(jnp.int32(0))
    cur = (first, jnp.int32(0), span(first)[0], jnp.int32(0))
    for _ in range(buffers - 1):
        cur = fetch(cur)
    lax.while_loop(lambda carry: carry[0] < B, one_row,
                   (first, jnp.int32(0), cur))


def span_decode_attention_fn(qs, k, v, start, end, *, block, scale):
    """``sum_c p(c) v(c)`` of one query a row over ring planes: ``qs [B,
    G, J, L]`` (the queries of a lane row, spread over its lanes), ``k``,
    ``v`` ``[B, G, C, L]``, row ``b``'s valid columns ``[start[b],
    end[b])``; returns ``[B, G, J, L]`` in the queries' dtype (zeros for
    a row with no valid column).  Only the blocks of ``block`` columns
    that hold a valid column of a row are read, of that row.

    The call is a ``jax.jit`` of its own inside the caller's program, so
    that a model's layers, which all call it at one shape, share one
    trace of the kernel's body."""
    return _call(qs, k, v, jnp.asarray(start, jnp.int32),
                 jnp.asarray(end, jnp.int32), block=int(block),
                 scale=float(scale), interpret=_mode.interpret())


@functools.partial(jax.jit, static_argnames=("block", "scale", "interpret"))
def _call(qs, k, v, start, end, *, block, scale, interpret):
    B, G, J, L = qs.shape
    C = k.shape[2]
    blocks = -(-C // block)
    # a lane row's queries fill whole tiles (zeros below them)
    Jp = -(-J // _sublanes(qs.dtype)) * _sublanes(qs.dtype)
    qp = jnp.pad(qs, ((0, 0), (0, 0), (0, Jp - J), (0, 0)))
    copy_bytes = G * block * L * k.dtype.itemsize
    buffers = max(MIN_BUFFERS, min(MAX_BUFFERS, FLIGHT_BYTES // copy_bytes))
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    f32 = jnp.float32
    out = pl.pallas_call(
        functools.partial(_kernel, rows=B, cols=C, block=block,
                          buffers=buffers, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[whole, any_space, any_space], out_specs=whole,
            scratch_shapes=[
                pltpu.VMEM((buffers, G, block, L), k.dtype),
                pltpu.SemaphoreType.DMA((buffers,)),
                pltpu.VMEM((blocks, G, Jp, block), f32),
                pltpu.VMEM((G, Jp, block), f32),
                pltpu.VMEM((G, Jp, block), f32),
                pltpu.VMEM((G, Jp, L), f32)]),
        out_shape=jax.ShapeDtypeStruct((B, G, Jp, L), qs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_BYTES),
        # (the cost of planes read whole: what is read is the rows' own)
        cost_estimate=pl.CostEstimate(
            flops=4 * B * G * Jp * C * L,
            bytes_accessed=2 * k.size * k.dtype.itemsize
            + 2 * qp.size * qp.dtype.itemsize,
            transcendentals=B * G * Jp * C),
        name="span_decode_attention",
        interpret=interpret,
    )(jnp.concatenate([start.reshape(B), end.reshape(B)]), qp, k, v)
    return out[:, :, :J]
