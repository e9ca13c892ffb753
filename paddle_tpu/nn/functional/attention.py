"""Attention functionals.

Reference parity: the reference era predates fused attention ops (it has only
softmax/matmul composition inside nn/layer/transformer.py); we expose a
first-class ``scaled_dot_product_attention`` because it is THE hot op on TPU.
An un-cached call site takes one of two forms, chosen from its shapes when
the program is traced (``_use_pallas`` -> ops/pallas/flash_attention.py
``fused_form``, a table measured on the chip, forward + backward): a Pallas
kernel that keeps a block's float32 scores and probabilities in VMEM in both
passes, or one XLA expression that writes them out (bf16 products on the MXU,
f32 softmax statistics and accumulation in both).  Off the TPU, with
FLAGS_use_pallas_kernels off, and for a trainable mask, always the latter.
The cached paths (``cached_attention``, ``span_attention``, the latent
family's) never ask.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import math
import threading

import jax
import jax.numpy as jnp
import numpy as np

from ...framework.flags import flag
from ...framework.primitive import Primitive
from ...framework.tensor import Tensor, unwrap

_NEG = -1e30


def _sdpa_fn(q, k, v, scale=None, causal=False):
    # q,k,v: (B, N, S, H) -- batch, heads, seq, head_dim
    hd = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(hd)
    logits = jnp.einsum("bnsh,bnth->bnst", q, k,
                        preferred_element_type=jnp.float32) * s
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(mask, logits, jnp.asarray(-1e30, logits.dtype))
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bnst,bnth->bnsh", probs, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _sdpa_mask_fn(q, k, v, mask, scale=None, causal=False):
    hd = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(hd)
    logits = jnp.einsum("bnsh,bnth->bnst", q, k,
                        preferred_element_type=jnp.float32) * s
    logits = logits + mask.astype(logits.dtype)
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(cm, logits, jnp.asarray(-1e30, logits.dtype))
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bnst,bnth->bnsh", probs, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _sdpa_packed_fn(q, k, v, mask=None):
    """Masked attention of ``(B, N, Tq, H)`` queries over PACKED ring
    planes ``(B, G, C, g*H)`` (``g`` adjacent heads side by side on the
    minor dim, ``G = ceil(N/g)``; nn/layer/transformer.py
    ``gen_ring_cache``).  The planes are never reshaped — splitting
    their lanes would bring back a full-plane copy — only the small
    operands are rearranged: each query is spread over its group's
    ``g*H`` lanes with zeros outside its own head's, so contracting
    over all lanes is the head's own dot product plus exact zeros; the
    probabilities contract with V over the columns and each head keeps
    its own ``H`` lanes of the result (the inverse of ``pack_heads``).
    Same scale, mask, f32 softmax and accumulation as
    :func:`_sdpa_mask_fn`."""
    qs, own = _spread_queries(q, k.shape[1], k.shape[3])
    logits = jnp.einsum("bgjtl,bgcl->bgjtc", qs, k,
                        preferred_element_type=jnp.float32) \
        * (1.0 / math.sqrt(q.shape[-1]))
    if mask is not None:
        logits = logits + mask.astype(logits.dtype)[:, :, None]
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bgjtc,bgcl->bgjtl", probs, v,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    return _own_lanes(out, own, q.shape[1], q.shape[-1])


def _spread_queries(q, groups, lanes, rep=1):
    """``(B, N, T, H)`` queries over their group's ``lanes = g*H`` lanes,
    zeros outside the lanes of the cached head they read: ``[B, groups,
    g * rep, T, lanes]``, and the ``own [1, 1, g * rep, 1, lanes]`` mask
    of those lanes (query ``j`` of a lane row reads cached head ``j //
    rep`` of it)."""
    b, n, t, hd = q.shape
    g = lanes // hd
    qg = jnp.pad(q, ((0, 0), (0, groups * g * rep - n), (0, 0), (0, 0))) \
        .reshape(b, groups, g * rep, t, hd)
    own = (jnp.arange(lanes)[None, :] // hd
           == jnp.arange(g * rep)[:, None] // rep)[None, None, :, None, :]
    return jnp.where(own, jnp.tile(qg, (1, 1, 1, 1, g)),
                     jnp.zeros((), q.dtype)), own


def _own_lanes(out, own, n, hd, rep=1):
    """``[B, groups, g * rep, T, lanes]`` -> ``(B, n, T, hd)``: each head
    keeps the lanes of the cached head it read: select and sum (one term
    is non-zero, so the sum is exact), NOT a stack of out[..., j, :,
    j*H:(j+1)*H] slices — the TPU compiler of jax 0.9.0 miscompiles a
    concatenate of slices taken at a lane offset (wrong values on the
    v5e, right on the CPU; PERF.md section 6, PR 25).  With one query a
    cached head the sum runs over the heads of the lane row; with
    ``rep`` of them, over the row's ``g`` lane segments."""
    b, groups, j, t, lanes = out.shape
    out = jnp.where(own, out, jnp.zeros((), out.dtype))
    if rep == 1:
        out = out.sum(axis=2)
        return out.reshape(b, groups, t, j, hd).transpose(0, 1, 3, 2, 4) \
            .reshape(b, groups * j, t, hd)[:, :n]
    return out.reshape(b, groups * j, t, lanes // hd, hd).sum(axis=3)[:, :n]


# columns a decode step's attention reads at a time: the unit in which
# the live span of the ring is rounded out
DECODE_BLOCK = 128


def decode_block(C):
    """The block width over a cache of ``C`` columns (serving/slots.py
    counts its ``attn_blocks_*`` in the same unit)."""
    return min(DECODE_BLOCK, int(C))


def decode_read_form(plane_shape, dtype, block, keep=None):
    """The form a decode step's read of ring planes ``[B, G, C, L]``
    takes: ``"per_row"``, the kernel of ops/pallas/span_decode.py (of
    each row the blocks that hold its own valid columns, nothing of a row
    that generates nothing), or ``"span"``, the XLA loops over the union
    span (:func:`_decode_span_fn`).  Decided while the program is traced,
    from what the code can see: the backend (the TPU), no mesh of several
    devices (:func:`_partitioned`), no ``keep`` mask, and the planes
    (``supports_span_decode``: bf16 / f32, whole lane rows, blocks on a
    tile's edge)."""
    if keep is not None or not _on_tpu() or _partitioned():
        return "span"
    from ...ops.pallas.span_decode import supports_span_decode
    return "per_row" if supports_span_decode(plane_shape, dtype, block) \
        else "span"


def decode_attention(q, k, v, start, end, block, rep=1, keep=None):
    """One-query attention of ``(B, N, 1, H)`` queries over ring planes
    ``(B, G, C, g*H)`` whose valid columns are each row's ``[start[b],
    end[b])``, in the form :func:`decode_read_form` names: each row's own
    blocks in one kernel (:func:`_decode_rows_fn`) or the union span in
    two XLA loops (:func:`_decode_span_fn`, which says what the operands
    are).  The same numbers either way."""
    if decode_read_form(k.shape, k.dtype, block, keep) == "per_row":
        return _decode_rows_fn(q, k, v, start, end, block=block, rep=rep)
    kw = {} if keep is None else {"keep": keep}
    return _decode_span_fn(q, k, v, start, end, block=block, rep=rep, **kw)


def _decode_rows_fn(q, k, v, start, end, block, rep=1):
    """:func:`_decode_span_fn` as ONE Pallas kernel that reads, of each
    row, the blocks ``[start[b] // block, ceil(end[b] / block))`` and
    nothing of a row with ``start[b] >= end[b]``
    (ops/pallas/span_decode.py): spread queries, float32 scores times ``1
    / sqrt(H)``, ONE softmax over a row's scores, probabilities in the
    queries' dtype, float32 sums; the queries are spread and each head's
    lanes are kept here, as the loops do it.  A row with no valid column
    reads zeros.  The kernel's call is a ``jax.jit`` of its own (a
    model's layers share one trace of its body); its custom call
    ``span_decode_attention`` lies under the caller's scope."""
    from ...ops.pallas.span_decode import span_decode_attention_fn
    n, hd = q.shape[1], q.shape[3]
    qs, own = _spread_queries(q, k.shape[1], k.shape[3], rep)
    out = span_decode_attention_fn(qs[:, :, :, 0], k, v, start, end,
                                   block=block, scale=1.0 / math.sqrt(hd))
    return _own_lanes(out[:, :, :, None], own, n, hd, rep)


@functools.partial(jax.jit, static_argnames=("block", "rep"))
def _decode_span_fn(q, k, v, start, end, block, rep=1, keep=None):
    """One-query attention of ``(B, N, 1, H)`` queries over ring planes
    ``(B, G, C, g*H)`` (packed as for :func:`_sdpa_packed_fn`, with
    ``rep`` query heads a cached head: grouped-query attention over
    planes of ``N / rep`` heads; ``g == 1`` is the plain ``(B, N, C, H)``
    plane) whose valid columns are each row's ``[start[b], end[b])``:
    only the span of ``block``-column
    blocks (``decode_block(C)``; jitted on its own so that a model's
    layers, which all call it at one shape, trace its loops once) from
    the one that holds the lowest ``start`` to the one that
    holds the highest ``end`` is read, in two passes under a traced trip
    count.  The first writes each block's float32 scores into its slot
    of a ``[blocks, .., block]`` slab; then the mask and ONE softmax over
    the slab, as the one-expression path takes it over the whole row
    (a block outside the span holds no valid column, so its slot is
    masked whatever it holds); the second adds up probabilities times V
    block by block.  K and V are read once, the span's blocks only; same
    scale, float32 scores and accumulation, and head select-and-sum as
    :func:`_sdpa_packed_fn`.  Each pass's body is one fused device
    operation a block: a running softmax in one loop costs nine, and a
    profiler capture of a serving window pays for every one (PERF.md
    section 6, PR 28).  A row that is not generating must come with
    ``start >= C`` (the slot loop keeps it there) so that it does not
    widen the span; it, and every row of a step with nothing live, gets
    finite values that nobody uses.  ``keep [B, G, 1, C]`` (bool), where
    given, takes further columns of a lane row away (a layer that chose
    the blocks it reads); they are masked, not skipped."""
    b, n, t, hd = q.shape
    groups, C, lanes = k.shape[1], k.shape[2], k.shape[3]
    blocks = -(-C // block)
    qs, own = _spread_queries(q, groups, lanes, rep)
    # (the barrier keeps the spread queries one array that both loops
    # read, where the compiler would rebuild them in every iteration)
    qs = jax.lax.optimization_barrier(qs)
    lo = jnp.clip(jnp.min(start) // block, 0, blocks)
    hi = jnp.clip((jnp.max(end) + (block - 1)) // block, lo, blocks)
    # slot i holds the columns from base[i]: i * block, but the last
    # block of a cache that is no multiple of ``block`` starts early and
    # does not count again what the slot before it holds
    first = np.arange(blocks) * block
    base = np.minimum(first, C - block)
    cols = base[:, None] + np.arange(block)                # [blocks, block]
    valid = (cols[:, None] >= start[:, None]) & (cols[:, None] < end[:, None]) \
        & (cols >= first[:, None])[:, None]                # [blocks, B, block]
    base = jnp.asarray(base, jnp.int32)

    def cut(plane, i):
        return jax.lax.dynamic_slice_in_dim(plane, base[i], block, 2)

    def score(i, slab):
        s = jnp.einsum("bgjtl,bgcl->bgjtc", qs, cut(k, i),
                       preferred_element_type=jnp.float32)
        return jax.lax.dynamic_update_index_in_dim(slab, s, i, 0)

    slab = jax.lax.fori_loop(
        lo, hi, score, jnp.zeros((blocks,) + qs.shape[:-1] + (block,),
                                 jnp.float32))
    valid = valid[:, :, None, None, None, :]
    if keep is not None:
        # [B, G, 1, C] -> [blocks, B, G, 1, 1, block], slot i as ``cols[i]``
        # (a plain reshape where the blocks tile the plane)
        kb = keep.reshape(b, groups, blocks, block) if C % block == 0 \
            else keep[:, :, 0][:, :, cols]
        valid = valid & jnp.moveaxis(kb, 2, 0)[:, :, :, None, None, :]
    slab = jnp.where(valid, slab * (1.0 / math.sqrt(hd)), _NEG)
    e = jnp.exp(slab - slab.max(axis=(0, -1))[None, ..., None])
    probs = (e / e.sum(axis=(0, -1))[None, ..., None]).astype(q.dtype)

    def weigh(i, acc):
        return acc + jnp.einsum("bgjtc,bgcl->bgjtl", probs[i], cut(v, i),
                                preferred_element_type=jnp.float32)

    out = jax.lax.fori_loop(lo, hi, weigh, jnp.zeros(qs.shape, jnp.float32))
    return _own_lanes(out.astype(q.dtype), own, n, hd, rep)


# columns a wider block's attention reads at a time over the live span
SPAN_BLOCK = 512


@functools.partial(jax.jit, static_argnames=("block", "rep"))
def _block_span_fn(q, k, v, start, first, block, rep=1, keep=None):
    """Attention of a BLOCK of queries ``(B, N, T, H)`` that sit at the
    columns ``first .. first + T - 1`` over ring planes ``(B, G, C, g*H)``
    (packed as for :func:`_sdpa_packed_fn`, ``rep`` queries a cached
    head): query ``t`` of row ``b`` sees the columns ``[start[b], first +
    t]``.  Only the ``block``-column blocks from the one that holds the
    lowest ``start`` to the one that holds the last query's column are
    read, under a traced trip count, with a running softmax (float32
    scores, maxima, sums and accumulation), so a prefill chunk never holds
    ``[heads, T, C]`` scores and the columns nobody can see cost nothing:
    the one-expression form scores every column of the ring whatever the
    context.  A query with no valid column (left padding) gets finite
    values that nobody uses.  ``keep [B, G, T, C]`` (bool), where given,
    takes further columns away from a query of a lane row: masked, not
    skipped."""
    b, n, t, hd = q.shape
    groups, C, lanes = k.shape[1], k.shape[2], k.shape[3]
    blocks = -(-C // block)
    qs, own = _spread_queries(q, groups, lanes, rep)
    rows = first + jnp.arange(t, dtype=jnp.int32)
    lo = jnp.clip(jnp.min(start) // block, 0, blocks)
    hi = jnp.clip((first + t - 1) // block + 1, lo, blocks)
    scale = 1.0 / math.sqrt(hd)

    def body(i, carry):
        m, l, acc = carry
        # the last block of a cache that is no multiple of ``block`` starts
        # early and does not count again what the one before it holds
        s0 = jnp.minimum(i * block, C - block)
        cols = s0 + jnp.arange(block, dtype=jnp.int32)
        valid = (cols[None, None, :] >= start[:, None, None]) \
            & (cols[None, None, :] <= rows[None, :, None]) \
            & (cols >= i * block)[None, None, :]               # [B, T, block]
        s = jnp.einsum("bgjtl,bgcl->bgjtc", qs,
                       jax.lax.dynamic_slice_in_dim(k, s0, block, 2),
                       preferred_element_type=jnp.float32) * scale
        valid = valid[:, None, None]
        if keep is not None:
            valid = valid & jax.lax.dynamic_slice_in_dim(
                keep, s0, block, 3)[:, :, None]
        s = jnp.where(valid, s, _NEG)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        pv = jnp.einsum("bgjtc,bgcl->bgjtl", p.astype(q.dtype),
                        jax.lax.dynamic_slice_in_dim(v, s0, block, 2),
                        preferred_element_type=jnp.float32)
        return m_new, l * corr + p.sum(-1), acc * corr[..., None] + pv

    init = (jnp.full(qs.shape[:-1], _NEG, jnp.float32),
            jnp.zeros(qs.shape[:-1], jnp.float32),
            jnp.zeros(qs.shape, jnp.float32))
    _, l, acc = jax.lax.fori_loop(lo, hi, body, init)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return _own_lanes(out.astype(q.dtype), own, n, hd, rep)


def span_attention(q, k, v, start, first, rep=1, keep=None):
    """Causal attention of the queries ``(B, N, T, H)`` at columns ``first
    .. first + T - 1`` over bf16/f32 ring planes, each row from its
    ``start[B]``, reading the live span of the ring only: a step's one
    query a row through :func:`_decode_span_fn` (two passes, one softmax),
    a wider block through :func:`_block_span_fn` (a running softmax).
    ``keep [B, G, T, C]`` masks further columns of a lane row for a query
    (:func:`block_keep`).  Raw arrays; inference only."""
    B, _, T, _ = q.shape
    C = k.shape[2]
    if T == 1:
        return decode_attention(q, k, v, start,
                                jnp.broadcast_to(first + 1, (B,)),
                                block=decode_block(C), rep=rep, keep=keep)
    kw = {} if keep is None else {"keep": keep}
    return _block_span_fn(q, k, v, start, first,
                          block=min(SPAN_BLOCK, C), rep=rep, **kw)


_sdpa = Primitive("scaled_dot_product_attention", _sdpa_fn)
_sdpa_mask = Primitive("scaled_dot_product_attention_mask", _sdpa_mask_fn)
_sdpa_packed = Primitive("scaled_dot_product_attention_packed",
                         _sdpa_packed_fn)


def _on_tpu():
    return jax.default_backend() == "tpu"


def _partitioned():
    """Whether programs are being traced for a mesh of several devices.
    The kernels carry no partitioning rule: the compiler would gather
    their operands whole onto every device."""
    from ...parallel.mesh import get_mesh, has_mesh
    return has_mesh() and get_mesh().size > 1


_tally = threading.local()


@contextlib.contextmanager
def count_attention_forms():
    """Tally the un-cached attention call sites that are traced inside the
    block by the form each takes: yields ``{"fused": n, "xla": m}``.  The
    choice is made while a program is traced, so the count is a fact of
    the program (``TrainStep`` puts it in its compile-ledger event)."""
    prev = getattr(_tally, "forms", None)
    _tally.forms = forms = {"fused": 0, "xla": 0}
    try:
        yield forms
    finally:
        _tally.forms = prev


def _use_pallas(q_shape, k_shape, mask=None, causal=False, packed=False):
    """The form an un-cached attention call site of ``(B, N, S, H)`` shapes
    takes: ``"single_block"`` / ``"blocked"`` (the Pallas kernels of
    ops/pallas/flash_attention.py) or None (the one-expression XLA path).
    From the shapes alone (``fused_form``: whichever was the faster on
    the chip, forward + backward), after what no shape says: the flag,
    the backend, a mesh of several devices (:func:`_partitioned`), and a
    trainable mask, whose gradient the kernels do not produce.  ``packed``
    says the caller holds ``[B, S, N*H]`` operands, which the single-block
    form takes as they lie.  Each call is one call site in
    :func:`count_attention_forms`'s tally."""
    form = None
    if flag("use_pallas_kernels") and _on_tpu() and not _partitioned() \
            and not (isinstance(mask, Tensor) and not mask.stop_gradient):
        from ...ops.pallas.flash_attention import fused_form
        mk = tuple(unwrap(mask).shape) if mask is not None else None
        form = fused_form(tuple(q_shape), tuple(k_shape), mk,
                          causal=causal, packed=packed)
    forms = getattr(_tally, "forms", None)
    if forms is not None:
        forms["fused" if form else "xla"] += 1
    return form


def _fused_scope():
    # the kernels' custom calls by name in a trace, forward and backward,
    # under the bucket the layer's ``attention`` scope already has
    return jax.named_scope("attention/fused")


def _attend_bnsh(q, k, v, attn_mask, is_causal, form):
    if form:
        from ...ops.pallas import flash_attention
        with _fused_scope():
            return flash_attention(q, k, v, bias=attn_mask, causal=is_causal)
    if attn_mask is not None:
        return _sdpa_mask(q, k, v, attn_mask, causal=bool(is_causal))
    return _sdpa(q, k, v, causal=bool(is_causal))


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None):
    """Inputs (B, S, N, H) per paddle-incubate convention: the heads lie
    side by side as :func:`attention_bse` takes them."""
    from ...ops import reshape
    b, sq, n, h = query.shape
    sk = key.shape[1]
    out = attention_bse(
        reshape(query, [b, sq, n * h]), reshape(key, [b, sk, n * h]),
        reshape(value, [b, sk, n * h]), n, attn_mask=attn_mask,
        is_causal=is_causal, dropout_p=dropout_p, training=training)
    return reshape(out, [b, sq, n, h])


def cached_attention(q, k, v, attn_mask=None, window=None, k_scale=None,
                     v_scale=None, row=None):
    """Incremental attention: (B, N, Tq, H) new-token queries over the
    full KV ring cache — bf16/f32 planes packed ``(B, ceil(N/g), S,
    g*H)`` as ``gen_ring_cache`` builds them (``g`` is read from the
    plane's minor dim; ``g == 1`` is the plain (B, N, S, H) plane), or
    the int8 cache's unpacked (B, N, S, H) rows.  One query head a
    cached head; grouped queries go through :func:`span_attention`.

    ``attn_mask`` is the additive validity+causality mask the caller
    built from cache_position / per-row start offsets.  ``window`` is the
    optional ``(start[B], end[B])`` contiguous form of the same validity
    (decode steps: Tq == 1).  With it, bf16/f32 planes are read in
    column blocks over the live span only (:func:`_decode_span_fn`;
    inference-only like every cached path, nothing is taped); without it
    (prefill, a chunk, a verify block) the one-expression masked
    attention runs, packed or not.

    With ``k_scale``/``v_scale`` given (FLAGS_kv_cache_dtype=int8), k/v
    are int8 row planes and the scales are the per-(token, head) f32
    planes: the cache is dequantized to the query's dtype and attended
    in one expression under ``attn_mask``, window or not (decode is
    inference-only, so the raw read costs no tape).

    With ``row`` (a traced int32 scalar) the queries are ONE row's,
    ``(1, N, Tq, H)``, and the planes are the full ``(S, ...)`` ones: row
    ``row`` of each plane (and of each scale plane) is the operand of the
    same expressions, cut with ``lax.dynamic_slice`` where it is read, so
    scale, mask, softmax and accumulation are what a batch-1 cache gets,
    to the bit, and no other row is touched.
    """
    if row is not None:
        def one(plane):
            return None if plane is None else Tensor(
                jax.lax.dynamic_slice_in_dim(unwrap(plane), unwrap(row), 1, 0))
        k, v, k_scale, v_scale = map(one, (k, v, k_scale, v_scale))
    if k_scale is not None:
        from ..layer.transformer import dequantize_kv_rows
        dt = unwrap(q).dtype
        k = Tensor(dequantize_kv_rows(k, k_scale, dtype=dt))
        v = Tensor(dequantize_kv_rows(v, v_scale, dtype=dt))
    elif window is not None:
        k = unwrap(k)
        return Tensor(decode_attention(
            unwrap(q), k, unwrap(v), unwrap(window[0]), unwrap(window[1]),
            block=decode_block(k.shape[2])))
    if unwrap(k).shape[-1] != unwrap(q).shape[-1]:
        return _sdpa_packed(q, k, v, attn_mask) if attn_mask is not None \
            else _sdpa_packed(q, k, v)
    if attn_mask is not None:
        return _sdpa_mask(q, k, v, attn_mask)
    return _sdpa(q, k, v)


def attention_bnsh(q, k, v, attn_mask=None, is_causal=False):
    """(B, N, S, H) layout path: what a caller that holds split heads (a
    concatenating or static cache) gets."""
    form = _use_pallas(q.shape, k.shape, attn_mask, causal=bool(is_causal))
    return _attend_bnsh(q, k, v, attn_mask, is_causal, form)


def attention_bse(q, k, v, num_heads, attn_mask=None, is_causal=False,
                  dropout_p=0.0, training=True):
    """Un-cached attention of the projections as they are written: ``q [B,
    Sq, N*H]`` over ``k``, ``v`` ``[B, Sk, N*H]``, heads side by side on
    the minor dimension; returns ``[B, Sq, N*H]`` (dropout on the output,
    as ``MultiHeadAttention`` applies it).  Where the single-block kernel
    is the faster form (``_use_pallas``) it reads the operands as they
    lie: no head is split off, transposed or padded.  Every other shape
    splits the heads and takes the ``(B, N, S, H)`` path exactly as
    before."""
    from ...ops import reshape, transpose
    b, sq, e = q.shape
    sk, hd = k.shape[1], e // num_heads
    form = _use_pallas((b, num_heads, sq, hd), (b, num_heads, sk, hd),
                       attn_mask, causal=bool(is_causal), packed=True)

    def drop(out):
        if dropout_p and training:
            from .common import dropout
            return dropout(out, dropout_p, training=training)
        return out

    if form == "single_block":
        from ...ops.pallas import packed_attention
        with _fused_scope():
            out = packed_attention(q, k, v, num_heads, bias=attn_mask,
                                   causal=is_causal)
        return drop(out)

    def split(x, s):
        return transpose(reshape(x, [b, s, num_heads, hd]), [0, 2, 1, 3])

    out = drop(_attend_bnsh(split(q, sq), split(k, sk), split(v, sk),
                            attn_mask, is_causal, form))
    return reshape(transpose(out, [0, 2, 1, 3]), [b, sq, e])


# ---------------------------------------------------------------------------
# latent attention: a token's cache row is ``latent ‖ rotary key``, no
# per-head K or V is ever stored.  Raw-array functions (decode is
# inference-only, nothing is taped); nn/layer/latent_attention.py owns the
# projections and the planes.
# ---------------------------------------------------------------------------

def rotary_frequencies(d, base, scaling=None):
    """The ``d / 2`` rotary frequencies ``base^(-2i/d)``, float32.
    ``scaling`` (a config's ``rope_scaling`` of type ``yarn``) blends each
    with its ``1 / factor`` between the correction dimensions that
    ``beta_fast`` and ``beta_slow`` turns over ``original_max_position_
    embeddings`` positions give: frequency ``i`` keeps ``1 - ramp_i (1 -
    1 / factor)`` of itself, ``ramp`` rising linearly from 0 at the low
    dimension to 1 at the high one (the fast frequencies stay, the slow
    ones are interpolated).  ``factor`` 1 leaves every frequency as it
    is, to the bit."""
    inv = jnp.float32(base) ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if not scaling:
        return inv
    if scaling.get("type", "yarn") != "yarn":
        raise ValueError(f"rope_scaling of type {scaling['type']!r}")
    factor = float(scaling["factor"])
    span = float(scaling["original_max_position_embeddings"])

    def dim_of(turns):
        return d * math.log(span / (turns * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(dim_of(float(scaling.get("beta_fast", 32)))), 0)
    high = min(math.ceil(dim_of(float(scaling.get("beta_slow", 1)))), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0, 1)
    return inv * (1.0 - ramp * jnp.float32(1.0 - 1.0 / factor))


def yarn_attention_factor(scaling=None) -> float:
    """What a ``yarn`` ``rope_scaling`` multiplies the softmax scale by:
    ``m(mscale_all_dim)^2`` with ``m(a) = 0.1 a ln(factor) + 1`` (1 where
    there is no scaling or ``factor`` is 1).  The published form also
    multiplies cos and sin by ``m(mscale) / m(mscale_all_dim)``: 1 for
    the configurations served here, and refused otherwise."""
    if not scaling or float(scaling["factor"]) <= 1:
        return 1.0
    m = lambda a: 0.1 * float(a or 0) * math.log(               # noqa: E731
        float(scaling["factor"])) + 1.0
    if m(scaling.get("mscale", 1)) != m(scaling.get("mscale_all_dim")):
        raise ValueError("rope_scaling with mscale != mscale_all_dim: the "
                         "rotary's cos and sin would carry their ratio")
    return m(scaling.get("mscale_all_dim")) ** 2


def rotary(x, positions, base, dims=None, inv=None):
    """Rotate the first ``dims`` (default all; even) features of ``x``
    ``[B, T, d]`` or ``[B, T, H, d]`` by ``positions [B, T]``; the two
    halves of the rotated part are paired ("rotate-half").  Angles in
    float32 whatever ``x`` is.  ``inv`` replaces the plain frequencies
    of ``base`` (:func:`rotary_frequencies`)."""
    d = x.shape[-1] if dims is None else int(dims)
    if inv is None:
        inv = rotary_frequencies(d, base)
    ang = positions.astype(jnp.float32)[..., None] * inv      # [B, T, d/2]
    if x.ndim == 4:
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1 = x[..., :d // 2].astype(jnp.float32)
    x2 = x[..., d // 2:d].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([out.astype(x.dtype), x[..., d:]], -1)


def selector_scores(q_idx, w_idx, keys):
    """The column selector's score ``I[b, t, s] = sum_j w[b, t, j] *
    relu(q_idx[b, t, j] . keys[b, s])``, float32.  ``q_idx [B, T, J, D]``,
    ``w_idx [B, T, J]``, ``keys [B, S, D]``."""
    dots = jnp.einsum("btjd,bsd->btjs", q_idx, keys,
                      preferred_element_type=jnp.float32)
    return jnp.einsum("btjs,btj->bts", jax.nn.relu(dots),
                      w_idx.astype(jnp.float32))


def _descending_key(x):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    b = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(0x80000000))


def _kth_largest(keys, k, bits=32):
    """Per row of ``keys [..., S]`` (uint32) the ``k``-th largest
    (``k [..., 1]`` or an int), found two bits at a time from the top: a
    pass counts, for each of the 3 next-digit candidates, the keys at or
    above it, so the whole search reads the keys ``bits / 2`` times where
    a sort moves them ``log^2 S`` times (on the chip a ``top_k`` of [512,
    12288] is a 5.5 ms sort).  Two bits and not four: a pass costs by its
    candidates, and 16 passes of 3 take 0.4-0.7 of the time of 8 passes of
    15 at every width from 4,096 to 24,576 (PERF.md section 6, PR 39).
    The passes are ONE loop in the program's text, not ``bits / 2``
    copies of its body: the search is traced once for every width a
    selecting layer may take."""
    u = jnp.uint32
    digits = jnp.arange(1, 4, dtype=u)
    passes = -(-bits // 2)

    def one(i, prefix):
        shift = u(2) * (u(passes - 1) - i.astype(u))
        cands = prefix[..., None] | (digits << shift)          # [..., 1, 3]
        counts = (keys[..., None] >= cands).sum(-2)             # [..., 3]
        digit = (counts >= k).sum(-1, keepdims=True)
        return prefix | (digit.astype(u) << shift)

    return jax.lax.fori_loop(0, passes, one,
                             jnp.zeros(keys.shape[:-1] + (1,), u))


def select_columns(scores, valid, k):
    """``Sel``: of the ``valid`` columns of each query, the ``k`` of
    largest selector score (the lower column first among equals, as
    ``lax.top_k`` orders them); all of them while fewer than ``k`` exist.
    ``scores``, ``valid`` ``[..., S]``; returns the boolean membership
    ``[..., S]``.  No sort: the ``k``-th largest score by a radix search
    on its bits, then, among the columns that tie with it, the lowest by
    the same search on their indices."""
    S = scores.shape[-1]
    if S <= k:
        return valid
    # (-0.0 + 0.0 is +0.0: the two zeros are one score)
    keys = _descending_key(jnp.where(valid, scores + 0.0, -jnp.inf))
    kth = _kth_largest(keys, k)
    above, tied = keys > kth, keys == kth
    room = k - above.sum(-1, keepdims=True)
    # among the tied, the ``room`` of lowest index = of largest S - index
    back = jnp.where(tied, jnp.uint32(S) - jnp.arange(S, dtype=jnp.uint32),
                     jnp.uint32(0))
    last = _kth_largest(back, room, bits=S.bit_length())
    return valid & (above | (tied & (back >= last)))


def search_widths(C, k):
    """The widths :func:`select_columns_span` may search in a plane of
    ``C`` columns of which ``k`` are chosen: ``2 k``, ``4 k``, ... below
    ``C``, then ``C`` itself (a search costs by its width, so each step
    at most doubles it); none where the plane holds no more than ``k``
    columns (nothing is ever searched there).  24,576 columns, 2,048
    chosen: 4,096, 8,192, 16,384, 24,576."""
    if C <= k:
        return ()
    widths, w = [], 2 * k
    while w < C:
        widths.append(w)
        w *= 2
    return tuple(widths) + (C,)


def span_branch(widths, k, first, last):
    """Which of ``widths`` (:func:`search_widths`) the search of a dispatch
    takes whose rows' lowest first valid column is ``first`` and whose last
    query sits at column ``last``: ``i`` for ``widths[i - 1]``, the
    narrowest that holds the widest context ``last - first + 1``; 0 where
    that context is no more than ``k`` columns, so that every valid column
    is chosen and nothing is searched (or scored).  Plain arithmetic: the
    program calls it on traced values and the slot loop on its own
    integers (:func:`searched_columns`), so the two cannot disagree."""
    widest = last - first + 1
    return sum(widest > w for w in ((k,) + tuple(widths))[:-1])


def searched_columns(widths, k, first, last):
    """Columns the search of that dispatch goes over (host integers)."""
    return ((0,) + tuple(widths))[int(span_branch(widths, k, first, last))]


def select_columns_span(scores, valid, k, widths, branch, first):
    """:func:`select_columns` of ``scores [B, T, C]`` at the cost of the
    dispatch's live span and not of the plane: the scores are ``-inf``
    outside the columns ``first .. last`` (traced), so the search runs
    over a slice of ``widths[branch - 1]`` columns that holds them all
    (``branch`` is :func:`span_branch`'s; the slice starts at ``first``,
    or as far below it as the plane's end demands) and the membership it
    finds is laid back into ``[B, T, C]``; columns outside the slice take
    no part in a search of the whole plane either, and the tie rule is an
    order on column indices, which an offset keeps: the same membership to
    the bit.  ``branch`` 0 takes ``valid()`` (``[B, T, C]``) as it stands.
    One conditional; the widths are static, so nothing compiles at run
    time."""
    B, T, C = scores.shape

    def search(W):
        def run(_):
            off = jnp.minimum(first, C - W)
            sc = jax.lax.dynamic_slice(scores, (0, 0, off), (B, T, W))
            return jax.lax.dynamic_update_slice(
                jnp.zeros((B, T, C), bool),
                select_columns(sc, jnp.isfinite(sc), k), (0, 0, off))
        return run

    return jax.lax.switch(branch, [lambda _: valid()]
                          + [search(W) for W in widths], None)


# -- block-sparse attention over a pooled-key plane ------------------------------
# A layer that reads, past ``dense_len`` tokens of context, only ``top``
# blocks of ``block`` columns, chosen for all the query heads of a cached
# head at once from scores over mean-pooled keys: window ``j`` of a request is
# the mean of its keys at positions ``[stride j, stride j + kernel)``.  Block
# and window edges count from the REQUEST's first token (``column - start``),
# so two rows of one session have them at different columns.
BlockSparse = collections.namedtuple(
    "BlockSparse", ["kernel", "stride", "block", "top", "init_blocks",
                    "window", "dense_len"])


def pooled_entries(C, stride):
    """Entries of the pooled-key plane beside a K plane of ``C`` columns:
    one for every ``stride`` columns."""
    return -(-int(C) // int(stride))


def pool_keys_write(pooled, k, pos, T, start, sp):
    """Write the pooled keys that the block of ``T`` columns from ``pos``
    completes.  ``pooled [B, G, E, L]``, ``k [B, G, C, L]`` (the K plane
    AFTER the block's write), ``start [B]``.  Entry ``e`` of a row holds
    the row's one window whose LAST key lies in the columns ``[stride e,
    stride e + stride)``: window ``j`` ends at column ``start + stride j +
    kernel - 1``, so there is exactly one, at the in-group offset ``(start
    + kernel - 1) % stride``, and ``e = j + (start + kernel - 1) //
    stride``.  Every row writes at the same entries (the slot loop's
    lockstep column), each under its own mask: a row whose window does not
    end inside the block, or begins before its ``start``, keeps what the
    entry held.  The mean is a product with a 0/1 matrix over the slice
    of ``k`` that holds the windows, accumulated in float32."""
    B, G, E, L = pooled.shape
    C = k.shape[2]
    st, kn = sp.stride, sp.kernel
    n = min((T + st - 2) // st + 1, E)          # groups the block may touch
    a = jnp.clip(pos // st, 0, E - n)
    ends = (a + jnp.arange(n, dtype=jnp.int32))[None, :] * st \
        + ((start + (kn - 1)) % st)[:, None]                      # [B, n]
    ok = (ends >= pos) & (ends <= pos + (T - 1)) \
        & (ends - (kn - 1) >= start[:, None])
    W = min(n * st + kn, C)
    w0 = jnp.clip(a * st - kn, 0, C - W)
    cols = w0 + jnp.arange(W, dtype=jnp.int32)
    inside = ok[..., None] & (cols >= ends[..., None] - (kn - 1)) \
        & (cols <= ends[..., None])                               # [B, n, W]
    new = jnp.einsum("bew,bgwl->bgel", inside.astype(k.dtype),
                     jax.lax.dynamic_slice_in_dim(k, w0, W, 2),
                     preferred_element_type=jnp.float32) * (1.0 / kn)
    old = jax.lax.dynamic_slice_in_dim(pooled, a, n, 2)
    return jax.lax.dynamic_update_slice_in_dim(
        pooled, jnp.where(ok[:, None, :, None], new.astype(pooled.dtype),
                          old), a, 2)


def choose_blocks(q, pooled, pos, start, sp, rep):
    """Which blocks of its request each query reads: ``[B, G, T, nb]``
    bool over the ``nb = ceil(C / block)`` blocks of request positions.
    ``q [B, G * rep, T, d]`` (the queries at columns ``pos .. pos + T -
    1``), ``pooled [B, G, E, d]`` (:func:`pool_keys_write`'s plane, a
    cached head a lane row).  For a query with context ``n = column -
    start + 1``: every valid block while ``n <= dense_len``; else ``a_j =
    sum_{h in group} softmax_j(q_h . c_j / sqrt(d))`` over the windows
    that lie whole inside the context, a block's score the largest
    ``a_j`` among the windows that overlap it, block(s) ``< init_blocks``
    and the blocks that hold the last ``window`` tokens always, the rest
    of ``top`` by score (:func:`select_columns`: the lower block first
    among equals)."""
    B, N, T, d = q.shape
    G, E = pooled.shape[1], pooled.shape[2]
    st, kn, bk = sp.stride, sp.kernel, sp.block
    r, kk = bk // st, kn // st
    if bk % st or kn % st:
        raise ValueError(f"block {bk} and kernel {kn} over stride {st}")
    C = E * st
    nb = -(-C // bk)
    cols = pos + jnp.arange(T, dtype=jnp.int32)
    ctx = cols[None, :] - start[:, None] + 1                      # [B, T]
    # a row's window ``j`` is its entry ``j + off`` and ends ``lag`` columns
    # into that entry's group (:func:`pool_keys_write`)
    off, lag = jnp.divmod(start + (kn - 1), st)                   # [B]
    e = jnp.arange(E, dtype=jnp.int32)
    # a window is scored iff it begins at or after ``start`` and ends at
    # or before the query's column
    seen = (e[None, None, :] >= off[:, None, None]) \
        & (e[None, None, :] * st + lag[:, None, None]
           <= cols[None, :, None])                                # [B, T, E]
    s = jnp.einsum("bgrtd,bged->bgrte", q.reshape(B, G, rep, T, d), pooled,
                   preferred_element_type=jnp.float32) * d ** -0.5
    s = jnp.where(seen[:, None, None], s, _NEG)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = jnp.where(seen[:, None, None], p / p.sum(-1, keepdims=True), 0.0)
    a = p.sum(2)                                              # [B, G, T, E]
    # entry -> window
    a = jax.vmap(lambda x, o: jax.lax.dynamic_slice_in_dim(x, o, E, 2))(
        jnp.pad(a, ((0, 0),) * 3 + ((0, E),)), off)
    # block ``b`` overlaps the windows ``r b - kk + 1 .. r b + r - 1``
    a = jnp.pad(a, ((0, 0),) * 3 + ((kk - 1, r * nb - E),))
    score = functools.reduce(jnp.maximum, (
        a[..., i:i + r * (nb - 1) + 1:r] for i in range(r + kk - 1)))
    blk = jnp.arange(nb, dtype=jnp.int32)
    n = ctx[:, None, :, None]                                 # [B, 1, T, 1]
    valid = blk * bk < n
    forced = (blk < sp.init_blocks) | (blk >= jnp.maximum(n - sp.window, 0)
                                       // bk)
    chosen = select_columns(
        jnp.where(forced, jnp.inf, score),
        jnp.broadcast_to(valid, score.shape), sp.top)
    return jnp.where(n <= sp.dense_len, valid, chosen)


def block_keep(member, start, block, C):
    """:func:`choose_blocks`' membership ``[B, G, T, nb]`` over a request's
    blocks as a mask over the plane's columns ``[B, G, T, C]``: column
    ``c`` of a row is position ``c - start`` of its request (``keep`` of
    :func:`span_attention`; columns below ``start`` read false)."""
    wide = jnp.pad(jnp.repeat(member, block, axis=-1),
                   ((0, 0),) * 3 + ((C, 0),))
    return jax.vmap(lambda x, o: jax.lax.dynamic_slice_in_dim(x, o, C, 2))(
        wide, C - start)


# The two cached forms differ in a PAIR of products and in nothing else:
# ``scores(rows) -> (s [B, H, T, S] float32, scaled; ctx)`` and
# ``weigh(p, ctx, out) -> sum_s p(s) value(s)`` (float32, laid out as the
# einsum subscript ``out`` says), ``ctx`` being what the second needs of
# the first's work; ``acc_shape`` is ``[B, H, T, width of a value]``;
# ``scope`` names the attention's instructions in a trace (None: no name
# of their own).
LatentProducts = collections.namedtuple(
    "LatentProducts", ["scores", "weigh", "acc_shape", "scope"])


def _scope(products):
    return jax.named_scope(products.scope) if products.scope \
        else contextlib.nullcontext()


def absorbed_products(q_cat, r_kv, scale):
    """The ABSORBED pair: queries already carried into the latent,
    ``q_cat [B, T, H, r_kv + d_r]`` (``W_uk^T q_nope ‖ q_rope``), scored
    against whole cache rows ``[B, S, r_kv + d_r]`` (``latent ‖ rotary
    key``); the probabilities weigh the rows' latent ``[.., :r_kv]`` (the
    caller applies ``W_uv``).  No key is ever expanded: ``2 r_kv + d_r``
    multiply-adds a head for each (query, column) pair, the form for a
    step's one query a row."""
    B, T, H, _ = q_cat.shape

    def scores(rows):
        return jnp.einsum("bthk,bsk->bhts", q_cat, rows,
                          preferred_element_type=jnp.float32) * scale, rows

    def weigh(p, rows, out):
        return jnp.einsum("bhts,bsr->" + out, p.astype(q_cat.dtype),
                          rows[..., :r_kv],
                          preferred_element_type=jnp.float32)

    return LatentProducts(scores, weigh, (B, H, T, r_kv), None)


def expand_latent(latent, w_uk, w_uv):
    """Per-head keys and values of latent rows: ``latent [B, S, r_kv]``
    times ``w_uk [H, r_kv, d_n]`` and ``w_uv [H, r_kv, d_v]`` gives ``k_n
    [B, H, S, d_n]`` and ``v [B, H, S, d_v]`` in the latent's dtype
    (float32 sums)."""
    k_n = jnp.einsum("bsr,hrd->bhsd", latent, w_uk,
                     preferred_element_type=jnp.float32)
    v = jnp.einsum("bsr,hrv->bhsv", latent, w_uv,
                   preferred_element_type=jnp.float32)
    return k_n.astype(latent.dtype), v.astype(latent.dtype)


def per_head_products(q_n, q_r, w_uk, w_uv, r_kv, scale):
    """The PER-HEAD pair: the rows' keys and values are expanded once
    (:func:`expand_latent`, ``r_kv (d_n + d_v)`` a column a head, shared
    by all ``T`` queries), the queries ``q_n [B, T, H, d_n]``, ``q_r [B,
    T, H, d_r]`` score ``k_n ‖ rotary key`` and the probabilities weigh
    ``v``: ``d_n + d_r + d_v`` a pair a head, and a value ``d_v`` wide
    where the absorbed form's is ``r_kv``.  The form for a wide block of
    queries; the rows may be padded past ``r_kv + d_r``.  Its instructions
    lie under a named scope of their own, ``per_head``."""
    B, T, H, _ = q_n.shape
    d_r = q_r.shape[-1]
    q = jnp.transpose(jnp.concatenate([q_n, q_r], -1), (0, 2, 1, 3))

    def scores(rows):
        k_n, v = expand_latent(rows[..., :r_kv], w_uk, w_uv)
        k_r = jnp.broadcast_to(rows[:, None, :, r_kv:r_kv + d_r],
                               k_n.shape[:-1] + (d_r,))
        s = jnp.einsum("bhtd,bhsd->bhts", q, jnp.concatenate([k_n, k_r], -1),
                       preferred_element_type=jnp.float32) * scale
        return s, v

    def weigh(p, v, out):
        return jnp.einsum("bhts,bhsr->" + out, p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32)

    return LatentProducts(scores, weigh, (B, H, T, w_uv.shape[-1]),
                          "per_head")


# The operands of the per-head pair as the one-kernel form takes them
# (:func:`latent_attend_fused`): the pair's arguments, nothing computed.
PerHeadOperands = collections.namedtuple(
    "PerHeadOperands", ["q_n", "q_r", "w_uk", "w_uv", "r_kv", "scale"])


def fused_latent(T, block, d_n, d_r, d_v, r_kv, row_width, heads):
    """Whether a per-head pass of ``T`` queries over column blocks of
    ``block`` takes the one-kernel form (ops/pallas/latent_attention.py):
    after what no shape says (the flag, the backend, a mesh of several
    devices: :func:`_partitioned`), from the shapes alone
    (``fused_latent_form``: what the kernel supports; the chip's timings
    are beside it).  Decided while a program is traced, like
    :func:`_use_pallas`."""
    if not (flag("use_pallas_kernels") and _on_tpu() and not _partitioned()):
        return False
    from ...ops.pallas.latent_attention import fused_latent_form
    return fused_latent_form(T, block, d_n, d_r, d_v, r_kv, row_width, heads)


def latent_attend_fused(operands, plane, lo, hi, block, start, pos,
                        keep=None):
    """:func:`latent_attend_blocked` of :func:`per_head_products` as ONE
    Pallas kernel: the column blocks ``lo <= i < hi`` of ``plane [B, S,
    K]`` with the expanded keys and values, the float32 scores and the
    running sums in VMEM.  ``keep [B, T, S]`` is the mask (a selector's
    membership, a window's ring mask); without one a query at column
    ``pos + t`` keeps ``start[b] .. pos + t``.  Returns ``[B, T, H, d_v]``
    float32; the custom call lies under the per-head form's scope."""
    from ...ops.pallas.latent_attention import latent_chunk_attention_fn
    q_n, q_r, w_uk, w_uv, r_kv, scale = operands
    with jax.named_scope("per_head"):
        q = jnp.transpose(jnp.concatenate([q_n, q_r], -1), (0, 2, 1, 3))
        return latent_chunk_attention_fn(
            q, w_uk, w_uv, plane, start, pos, lo, hi, r_kv=r_kv, scale=scale,
            block=block, keep=keep)


def latent_attend(products, rows, keep):
    """One pass of latent attention in the form ``products`` gives
    (:func:`absorbed_products`, :func:`per_head_products`) over cache
    rows ``rows [B, S, K]`` under ``keep [B, T, S]``; returns ``sum_s p(s)
    value(s)`` ``[B, T, H, width]`` float32.  A query with nothing kept (a
    dead slot row) reads the plain mean of the rows: finite, never
    used."""
    with _scope(products):
        s, ctx = products.scores(rows)
        s = jnp.where(keep[:, None], s, _NEG)
        return products.weigh(jax.nn.softmax(s, axis=-1), ctx, "bthr")


def latent_attend_blocked(products, plane, keep_of, lo, hi, block):
    """:func:`latent_attend` over column blocks ``lo <= i < hi`` (traced)
    of ``plane [B, S, K]``, ``block`` columns each, with a running
    softmax, so that a wide query block never holds ``[H, T, S]`` scores
    and columns no query can see cost nothing.  ``keep_of(s0)`` gives the
    mask ``[B, T, block]`` of the block that starts at column ``s0``.
    The one blocked loop of the latent family: the form is the pair of
    products it is handed."""
    B, H, T, _ = products.acc_shape
    K = plane.shape[-1]

    def body(i, carry):
        m, l, acc = carry
        s0 = i * block
        rows = jax.lax.dynamic_slice(plane, (0, s0, 0), (B, block, K))
        s, ctx = products.scores(rows)
        s = jnp.where(keep_of(s0)[:, None], s, _NEG)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(-1)
        pv = products.weigh(p, ctx, "bhtr")
        return m_new, l, acc * corr[..., None] + pv

    init = (jnp.full((B, H, T), _NEG, jnp.float32),
            jnp.zeros((B, H, T), jnp.float32),
            jnp.zeros(products.acc_shape, jnp.float32))
    with _scope(products):
        m, l, acc = jax.lax.fori_loop(lo, hi, body, init)
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.transpose(out, (0, 2, 1, 3))
