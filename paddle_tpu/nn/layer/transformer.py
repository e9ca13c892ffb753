"""Transformer layers.

Reference parity: python/paddle/nn/layer/transformer.py (MultiHeadAttention,
TransformerEncoderLayer/Encoder, TransformerDecoderLayer/Decoder,
Transformer). TPU-first: un-cached attention runs through
functional.attention.attention_bse on the projections as they are written
(a Pallas kernel that keeps the scores on the chip, or one XLA expression:
the functional picks from the shapes), bf16 matmuls with f32 softmax; the
cache API (gen_cache/StaticCache) is kept for decoding parity.
"""
from __future__ import annotations

import collections

import jax

from ...framework.tensor import Tensor, unwrap
from ...ops import concat, reshape, transpose
from .. import functional as F
from ..functional.attention import attention_bnsh, attention_bse
from .common import Dropout, Linear
from .layers import Layer
from .norm import LayerNorm


def _static_int(x):
    """Concrete scalar value of ``x`` or None when traced."""
    try:
        return int(x)
    except Exception:                      # jax tracer: value unknown
        return None


def ring_block_write(plane, new, pos, axis=None, row=None):
    """Write a ``T``-wide token block into a ``C``-long ring-buffer plane
    at the (already wrapped, possibly traced) position ``pos``.

    With ``row`` (a traced int32 scalar) the block is ONE row's: ``new
    [1, ..., T, L]`` lands in row ``row`` of ``plane [S, ..., C, L]``, at
    ``(row, 0, .., pos, 0)``, and every other row of the plane is left
    as it lies (a prefill chunk writes into the full donated plane in
    place; no row is cut out of the plane and none is spliced back).
    Both legs below then read and write that row's ``T`` columns only.
    ``row=None`` is the write of all rows at once, lowered as before.

    A plain ``lax.dynamic_update_slice`` CLAMPS its start to ``C - T``,
    so a multi-token block landing near the ring boundary would silently
    shift instead of wrapping — correct for the single-token decode
    write (width 1 never crosses), wrong for the γ-wide speculative
    verify write.  The wrap-aware form splits the write into TWO
    dynamic_update_slice legs of static width ``T`` each:

      * leg 1 at ``min(pos, C - T)``: the tail run ``[pos, C)``, with
        the columns below ``pos`` (only touched when wrapping forces the
        clamped start) re-written with their own current contents;
      * leg 2 at static 0: the wrapped head run ``[0, pos + T - C)``,
        a no-op rewrite of current contents when nothing wrapped.

    Shapes: ``plane [..., C, L]``, ``new [..., T, L]``; ``axis``
    defaults to ``ndim - 2``.  Both legs put the traced start on the
    second-minor LOGICAL dim and span ``L``.  Whether that is the
    SUBLANE dim on the chip is the compiler's choice, made from the
    shape: for a bf16/f32 plane whose ``L`` is a multiple of 128 the
    TPU compiler keeps the row-major ``{..., C, L}`` device layout, the
    start lands on the sublanes and a one-column write is one masked
    tile row per ``[..., :, L]`` slab; for ``L`` < 128 it avoids
    padding ``L`` by putting ``C`` on the LANES (``{2,3,1,0}`` for a
    ``[B, N, C, 64]`` plane), and the same write becomes a lane-masked
    read-modify-write of every slab's tiles.  ``gen_ring_cache`` packs
    heads along ``L`` so that its planes are of the first kind
    (:func:`kv_heads_per_lane_row`); ``tools/kv_layout_check.py`` reads
    the layout back from the compiled program.
    """
    with jax.named_scope("cache_write"):
        out = _ring_block_write(unwrap(plane), unwrap(new), unwrap(pos), axis,
                                None if row is None else unwrap(row))
    return Tensor(out) if isinstance(plane, Tensor) \
        or isinstance(new, Tensor) else out


def _ring_block_write(p, n, pos, axis, row=None):
    import jax.numpy as jnp
    from jax import lax
    ax = p.ndim - 2 if axis is None else int(axis)
    C, T = p.shape[ax], n.shape[ax]
    if T > C:
        raise ValueError(
            f"ring block of {T} tokens cannot fit a cache of length {C}")
    if row is None:
        def cut(plane, at):
            return lax.dynamic_slice_in_dim(plane, at, T, ax)

        def put(plane, block, at):
            return lax.dynamic_update_slice_in_dim(plane, block, at, ax)
    else:
        # one row's block: the same two operations at (row, 0, .., at, 0)
        if n.shape[0] != 1 or ax == 0:
            raise ValueError(
                f"a row's block is [1, ..., T, L]; got {tuple(n.shape)} "
                f"for the column axis {ax}")
        row = jnp.asarray(row, jnp.int32)

        def at_row(at):
            idx = [jnp.int32(0)] * p.ndim
            idx[0], idx[ax] = row, jnp.asarray(at, jnp.int32)
            return idx

        def cut(plane, at):
            return lax.dynamic_slice(plane, at_row(at), n.shape)

        def put(plane, block, at):
            return lax.dynamic_update_slice(plane, block, at_row(at))
    sp = _static_int(pos)
    if T == 1 or (sp is not None and sp + T <= C):
        # width-1 writes never cross the boundary (pos is pre-wrapped),
        # and a statically in-range block (the prefill fill at pos 0)
        # needs no second leg — the existing single-store lowering
        return put(p, n.astype(p.dtype), pos)
    pos = jnp.asarray(pos, jnp.int32)
    n = n.astype(p.dtype)
    idx_shape = [1] * p.ndim
    idx_shape[ax] = T
    idx = jnp.arange(T, dtype=jnp.int32).reshape(idx_shape)
    pad = jnp.zeros_like(n)
    # leg 1: tail run [pos, C) — blend the clamped window's leading
    # columns back to their current values so clamping never corrupts
    s1 = jnp.minimum(pos, jnp.int32(C - T))
    off = pos - s1                                  # 0 unless wrapping
    cur1 = cut(p, s1)
    v1 = lax.dynamic_slice_in_dim(jnp.concatenate([pad, n], axis=ax),
                                  jnp.int32(T) - off, T, ax)
    out = put(p, jnp.where(idx < off, cur1, v1), s1)
    # leg 2: wrapped head run [0, pos + T - C) at a STATIC start
    w = pos + jnp.int32(T - C)                      # <= 0: nothing wrapped
    cur2 = lax.slice_in_dim(out, 0, T, axis=ax) if row is None \
        else cut(out, 0)
    v2 = lax.dynamic_slice_in_dim(jnp.concatenate([n, pad], axis=ax),
                                  jnp.minimum(jnp.int32(C) - pos,
                                              jnp.int32(T)), T, ax)
    return put(out, jnp.where(idx < w, v2, cur2), 0)


_LANES = 128     # minor-dimension tile width of the TPU's device layouts


def kv_heads_per_lane_row(head_dim):
    """``g``: how many heads of ``head_dim`` a ring plane packs side by
    side along its minor dimension so that it spans whole 128-lane
    rows.  1 when ``head_dim`` already fills a row (>= 128) or does
    not divide one (packing would not reach a multiple of 128)."""
    head_dim = int(head_dim)
    return _LANES // head_dim if _LANES % head_dim == 0 else 1


def pack_heads(x, g):
    """``[B, N, T, H]`` per-head rows -> ``[B, ceil(N/g), T, g*H]``
    with ``g`` adjacent heads side by side on the minor dimension (the
    ring plane's layout, column dim still at axis 2).  ``N`` pads to a
    multiple of ``g`` with zero heads; ``g == 1`` returns ``x``."""
    import jax.numpy as jnp
    xv = unwrap(x)
    if g == 1:
        return xv
    b, n, t, h = xv.shape
    groups = -(-n // g)
    xv = jnp.pad(xv, ((0, 0), (0, groups * g - n), (0, 0), (0, 0)))
    return xv.reshape(b, groups, g, t, h).transpose(0, 1, 3, 2, 4) \
        .reshape(b, groups, t, g * h)


def quantize_kv_rows(x):
    """Per-(token, head) symmetric int8 quantization of a K/V block
    ``[B, N, T, H]``: one f32 scale per head-row (the dequant is a
    rank-1 broadcast).  Returns (int8 rows ``[B, N, T, H]``, f32 scales
    ``[B, N, T, 1]``)."""
    import jax.numpy as jnp
    xv = unwrap(x)
    scale = jnp.max(jnp.abs(xv).astype(jnp.float32), axis=-1,
                    keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-9)
    q = jnp.clip(jnp.round(xv.astype(jnp.float32) / scale), -127, 127) \
        .astype(jnp.int8)
    return q, scale


def dequantize_kv_rows(q, scale, dtype=None):
    """Inverse of :func:`quantize_kv_rows` (the int8 cache's
    dequantize-then-attend read)."""
    import jax.numpy as jnp
    out = unwrap(q).astype(jnp.float32) * unwrap(scale)
    if dtype is not None:
        out = out.astype(dtype)
    return out


class MultiHeadAttention(Layer):
    Cache = collections.namedtuple("Cache", ["k", "v"])
    StaticCache = collections.namedtuple("StaticCache", ["k", "v"])
    # static-shape decoding cache: (B, ceil(N/g), max_len, g*H) ring
    # buffers, g = kv_heads_per_lane_row(H) adjacent heads side by side
    # on the minor dim (g == 1, i.e. (B, N, max_len, H), for H >= 128),
    # written in place with lax.dynamic_update_slice at an explicit
    # (possibly traced) cache_position — unlike Cache's concat, the
    # shape never grows, so one decode executable serves every step
    # (zero per-token recompiles; single-token writes wrap modulo
    # max_len and wider blocks split into two legs at the boundary via
    # ring_block_write)
    RingCache = collections.namedtuple("RingCache", ["k", "v"])
    # int8-quantized ring cache (FLAGS_kv_cache_dtype=int8): k/v hold
    # int8 rows, k_scale/v_scale the per-(token, head) f32 scales as
    # extra (B, N, max_len, 1) cache planes written at the SAME traced
    # position — cached-context HBM halves (plus the scale overhead).
    # Keeps the UNPACKED (B, N, max_len, H) contract: one scale per
    # (token, head), heads at axis 1
    QuantRingCache = collections.namedtuple(
        "QuantRingCache", ["k", "v", "k_scale", "v_scale"])
    # what kind of planes a cache class holds, for whoever must cut or
    # move them (prefix cache, sessions, handoff)
    RingCache.kind = "kv"
    QuantRingCache.kind = "kv_int8"

    def __init__(self, embed_dim, num_heads, dropout=0.0, kdim=None,
                 vdim=None, need_weights=False, weight_attr=None,
                 bias_attr=None):
        super().__init__()
        self.embed_dim = embed_dim
        self.kdim = kdim or embed_dim
        self.vdim = vdim or embed_dim
        self.num_heads = num_heads
        self.head_dim = embed_dim // num_heads
        assert self.head_dim * num_heads == embed_dim
        self.dropout = dropout
        self.need_weights = need_weights
        self.q_proj = Linear(embed_dim, embed_dim, weight_attr, bias_attr)
        self.k_proj = Linear(self.kdim, embed_dim, weight_attr, bias_attr)
        self.v_proj = Linear(self.vdim, embed_dim, weight_attr, bias_attr)
        self.out_proj = Linear(embed_dim, embed_dim, weight_attr, bias_attr)

    def _split_heads(self, x):
        b, s = x.shape[0], x.shape[1]
        x = reshape(x, [b, s, self.num_heads, self.head_dim])
        return transpose(x, [0, 2, 1, 3])  # B N S H

    def _merge_heads(self, x):
        b, n, s, h = x.shape
        x = transpose(x, [0, 2, 1, 3])
        return reshape(x, [b, s, n * h])

    def gen_cache(self, key, value=None, type=None):
        if type == MultiHeadAttention.StaticCache:
            k = self._split_heads(self.k_proj(key))
            v = self._split_heads(self.v_proj(value if value is not None else key))
            return self.StaticCache(k, v)
        from ...ops import zeros
        b = key.shape[0]
        k = zeros([b, self.num_heads, 0, self.head_dim], dtype=str(key.dtype))
        v = zeros([b, self.num_heads, 0, self.head_dim], dtype=str(key.dtype))
        return self.Cache(k, v)

    def gen_ring_cache(self, batch, max_len, dtype="float32"):
        """Zero-initialized static-shape KV ring cache
        ``(B, ceil(N/g), max_len, g*H)``: ``g`` adjacent heads share a
        row of the minor dimension (:func:`kv_heads_per_lane_row`; the
        heads pad to a multiple of ``g`` and the padded head's lanes
        stay zero), so a token's K/V for a row is contiguous on the
        device and the step's one-column write at the traced position
        lands on the sublanes (see :func:`ring_block_write`).  ``g`` is
        computed from ``head_dim``: 2 for 64, 8 for 16, and 1 — the
        plain ``(B, N, max_len, H)`` planes — for 128 and more.  This
        is the ONE place that decides the layout; ``_forward_ring``
        and ``cached_attention`` read ``g`` back from the plane's minor
        dimension.  ``max_len`` is a compile-time constant; validity is
        tracked by the caller's cache_position/window, not by the
        shape.  Under ``FLAGS_kv_cache_dtype=int8`` the planes are
        UNPACKED int8 rows ``(B, N, max_len, H)`` plus per-(token,
        head) f32 scale planes (QuantRingCache) — one Python branch
        here, zero graph change on the default path."""
        from ...framework import flags as _flags
        from ...ops import zeros
        if str(_flags.flag("kv_cache_dtype")).lower() == "int8":
            rows = [batch, self.num_heads, max_len, self.head_dim]
            scales = [batch, self.num_heads, max_len, 1]
            return self.QuantRingCache(
                zeros(rows, dtype="int8"), zeros(rows, dtype="int8"),
                zeros(scales, dtype="float32"),
                zeros(scales, dtype="float32"))
        g = kv_heads_per_lane_row(self.head_dim)
        plane = [batch, -(-self.num_heads // g), max_len, g * self.head_dim]
        return self.RingCache(zeros(plane, dtype=dtype),
                              zeros(plane, dtype=dtype))

    def ring_cache_spec(self, max_len):
        """What :meth:`gen_ring_cache` builds, described (the Generator's
        ``cache_spec``): uniform K/V planes as long as the session."""
        from ...framework import flags as _flags
        int8 = str(_flags.flag("kv_cache_dtype")).lower() == "int8"
        cls = self.QuantRingCache if int8 else self.RingCache
        return {"kind": cls.kind,
                "heads_per_lane_row":
                    1 if int8 else kv_heads_per_lane_row(self.head_dim),
                "columns": int(max_len), "wraps": False, "window": None,
                "select_top": None}

    def _forward_ring(self, query, attn_mask, cache, cache_position,
                      decode_window, row=None):
        """Incremental attention over the ring cache: project the new
        tokens, pack their K/V the way the planes are packed
        (``pack_heads``; ``g`` read from the plane's minor dim), write
        them at cache_position (ring_block_write on the column dim —
        two legs at the ring boundary for multi-token blocks), and
        attend the new queries over the cache under the caller's
        validity mask: the whole of it, or, for a decode step's one
        query with its ``decode_window``, the column blocks the window
        spans (``cached_attention``).  Quantized caches keep unpacked
        planes, additionally write int8 rows + scale planes at the same
        position and dequantize at the attention read.  With ``row``
        (a traced int32 scalar; a prefill chunk's joining row) the
        batch-1 block is written into row ``row`` of the FULL planes in
        place and the batch-1 queries attend that row of the written
        planes: the other rows are neither read nor written.  Returns
        (out, updated RingCache/QuantRingCache)."""
        from ..functional.attention import cached_attention
        q = self._split_heads(self.q_proj(query))
        k_new = self._split_heads(self.k_proj(query))
        v_new = self._split_heads(self.v_proj(query))
        if isinstance(cache, self.QuantRingCache):
            kq, ks = quantize_kv_rows(k_new)
            vq, vs = quantize_kv_rows(v_new)
            cache = self.QuantRingCache(*(
                ring_block_write(plane, Tensor(new), cache_position, row=row)
                for plane, new in zip(cache, (kq, vq, ks, vs))))
            out = cached_attention(q, cache.k, cache.v, attn_mask=attn_mask,
                                   window=decode_window,
                                   k_scale=cache.k_scale,
                                   v_scale=cache.v_scale, row=row)
        else:
            g = cache.k.shape[3] // self.head_dim
            k = ring_block_write(cache.k, Tensor(pack_heads(k_new, g)),
                                 cache_position, row=row)
            v = ring_block_write(cache.v, Tensor(pack_heads(v_new, g)),
                                 cache_position, row=row)
            cache = self.RingCache(k, v)
            out = cached_attention(q, k, v, attn_mask=attn_mask,
                                   window=decode_window, row=row)
        if self.dropout:
            out = F.dropout(out, self.dropout, training=self.training)
        return self.out_proj(self._merge_heads(out)), cache

    def forward(self, query, key=None, value=None, attn_mask=None, cache=None,
                cache_position=None, decode_window=None, row=None):
        if isinstance(cache, (self.RingCache, self.QuantRingCache)):
            return self._forward_ring(query, attn_mask, cache,
                                      cache_position, decode_window, row)
        key = query if key is None else key
        value = key if value is None else value
        if cache is None:
            # un-cached: the functional takes the projections as they are
            # written and splits the heads only where its form wants them
            out = attention_bse(
                self.q_proj(query), self.k_proj(key), self.v_proj(value),
                self.num_heads, attn_mask=attn_mask, dropout_p=self.dropout,
                training=self.training)
            return self.out_proj(out)
        q = self._split_heads(self.q_proj(query))
        if isinstance(cache, self.StaticCache):
            k, v = cache.k, cache.v
        else:
            k = self._split_heads(self.k_proj(key))
            v = self._split_heads(self.v_proj(value))
            if isinstance(cache, self.Cache):
                k = concat([cache.k, k], axis=2)
                v = concat([cache.v, v], axis=2)
                cache = self.Cache(k, v)
        out = attention_bnsh(q, k, v, attn_mask=attn_mask)
        if self.dropout:
            out = F.dropout(out, self.dropout, training=self.training)
        out = self.out_proj(self._merge_heads(out))
        if not isinstance(cache, self.StaticCache):
            return out, cache
        return out


class TransformerEncoderLayer(Layer):
    def __init__(self, d_model, nhead, dim_feedforward, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None):
        super().__init__()
        attn_dropout = dropout if attn_dropout is None else attn_dropout
        act_dropout = dropout if act_dropout is None else act_dropout
        self.normalize_before = normalize_before
        self.self_attn = MultiHeadAttention(d_model, nhead, attn_dropout,
                                            weight_attr=weight_attr,
                                            bias_attr=bias_attr)
        self.linear1 = Linear(d_model, dim_feedforward, weight_attr, bias_attr)
        self.dropout = Dropout(act_dropout)
        self.linear2 = Linear(dim_feedforward, d_model, weight_attr, bias_attr)
        self.norm1 = LayerNorm(d_model)
        self.norm2 = LayerNorm(d_model)
        self.dropout1 = Dropout(dropout)
        self.dropout2 = Dropout(dropout)
        self.activation = getattr(F, activation)

    def forward(self, src, src_mask=None, cache=None, cache_position=None,
                decode_window=None, row=None):
        # the layer's two halves, each with its norm and its residual add,
        # under the names a trace is read by (docs/METRICS.md)
        with jax.named_scope("attention"):
            residual = src
            if self.normalize_before:
                src = self.norm1(src)
            if cache is None:
                src = self.self_attn(src, src, src, src_mask)
            else:
                src, cache = self.self_attn(src, src, src, src_mask, cache,
                                            cache_position=cache_position,
                                            decode_window=decode_window,
                                            row=row)
            src = residual + self.dropout1(src)
            if not self.normalize_before:
                src = self.norm1(src)
        with jax.named_scope("mlp"):
            residual = src
            if self.normalize_before:
                src = self.norm2(src)
            src = self.linear2(
                self.dropout(self.activation(self.linear1(src))))
            src = residual + self.dropout2(src)
            if not self.normalize_before:
                src = self.norm2(src)
        return src if cache is None else (src, cache)

    def gen_cache(self, src):
        return self.self_attn.gen_cache(src)

    def gen_ring_cache(self, batch, max_len, dtype="float32"):
        return self.self_attn.gen_ring_cache(batch, max_len, dtype)

    def ring_cache_spec(self, max_len):
        return self.self_attn.ring_cache_spec(max_len)


class TransformerEncoder(Layer):
    def __init__(self, encoder_layer, num_layers=None, norm=None):
        super().__init__()
        from .container import LayerList
        import copy
        if isinstance(encoder_layer, (list, tuple)):
            # pre-built heterogeneous stack (e.g. alternating dense/MoE
            # blocks — text.models.GPTMoEModel); each entry keeps its
            # own parameters, no cloning
            layers = list(encoder_layer)
            if num_layers is not None and int(num_layers) != len(layers):
                raise ValueError(
                    f"TransformerEncoder got {len(layers)} layers but "
                    f"num_layers={num_layers}")
            self.layers = LayerList(layers)
            self.num_layers = len(layers)
        else:
            self.layers = LayerList(
                [encoder_layer if i == 0 else _clone_layer(encoder_layer)
                 for i in range(num_layers)])
            self.num_layers = num_layers
        self.norm = norm

    def forward(self, src, src_mask=None, cache=None, cache_position=None,
                decode_window=None, row=None):
        output = src
        new_caches = []
        for i, mod in enumerate(self.layers):
            if cache is None:
                output = mod(output, src_mask)
            else:
                output, new_cache = mod(output, src_mask, cache[i],
                                        cache_position=cache_position,
                                        decode_window=decode_window,
                                        row=row)
                new_caches.append(new_cache)
        if self.norm is not None:
            output = self.norm(output)
        return output if cache is None else (output, new_caches)

    def gen_cache(self, src):
        return [layer.gen_cache(src) for layer in self.layers]

    def gen_ring_cache(self, batch, max_len, dtype="float32"):
        """Per-layer static-shape KV ring caches for incremental decode."""
        return [layer.gen_ring_cache(batch, max_len, dtype)
                for layer in self.layers]

    def ring_cache_spec(self, max_len):
        """Per layer, what its ring cache is (each layer's own word)."""
        return [layer.ring_cache_spec(max_len) for layer in self.layers]


class TransformerDecoderLayer(Layer):
    def __init__(self, d_model, nhead, dim_feedforward, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None):
        super().__init__()
        attn_dropout = dropout if attn_dropout is None else attn_dropout
        act_dropout = dropout if act_dropout is None else act_dropout
        self.normalize_before = normalize_before
        self.self_attn = MultiHeadAttention(d_model, nhead, attn_dropout,
                                            weight_attr=weight_attr,
                                            bias_attr=bias_attr)
        self.cross_attn = MultiHeadAttention(d_model, nhead, attn_dropout,
                                             weight_attr=weight_attr,
                                             bias_attr=bias_attr)
        self.linear1 = Linear(d_model, dim_feedforward, weight_attr, bias_attr)
        self.dropout = Dropout(act_dropout)
        self.linear2 = Linear(dim_feedforward, d_model, weight_attr, bias_attr)
        self.norm1 = LayerNorm(d_model)
        self.norm2 = LayerNorm(d_model)
        self.norm3 = LayerNorm(d_model)
        self.dropout1 = Dropout(dropout)
        self.dropout2 = Dropout(dropout)
        self.dropout3 = Dropout(dropout)
        self.activation = getattr(F, activation)

    def forward(self, tgt, memory, tgt_mask=None, memory_mask=None,
                cache=None):
        residual = tgt
        if self.normalize_before:
            tgt = self.norm1(tgt)
        if cache is None:
            tgt = self.self_attn(tgt, tgt, tgt, tgt_mask)
            incremental_cache = None
        else:
            tgt, incremental_cache = self.self_attn(tgt, tgt, tgt, tgt_mask,
                                                    cache[0])
        tgt = residual + self.dropout1(tgt)
        if not self.normalize_before:
            tgt = self.norm1(tgt)
        residual = tgt
        if self.normalize_before:
            tgt = self.norm2(tgt)
        if cache is None:
            tgt = self.cross_attn(tgt, memory, memory, memory_mask)
            static_cache = None
        else:
            tgt = self.cross_attn(tgt, memory, memory, memory_mask, cache[1])
            static_cache = cache[1]
        tgt = residual + self.dropout2(tgt)
        if not self.normalize_before:
            tgt = self.norm2(tgt)
        residual = tgt
        if self.normalize_before:
            tgt = self.norm3(tgt)
        tgt = self.linear2(self.dropout(self.activation(self.linear1(tgt))))
        tgt = residual + self.dropout3(tgt)
        if not self.normalize_before:
            tgt = self.norm3(tgt)
        if cache is None:
            return tgt
        return tgt, (incremental_cache, static_cache)

    def gen_cache(self, memory):
        incremental = self.self_attn.gen_cache(memory)
        static = self.cross_attn.gen_cache(memory, memory,
                                           MultiHeadAttention.StaticCache)
        return incremental, static


class TransformerDecoder(Layer):
    def __init__(self, decoder_layer, num_layers, norm=None):
        super().__init__()
        from .container import LayerList
        self.layers = LayerList(
            [decoder_layer if i == 0 else _clone_layer(decoder_layer)
             for i in range(num_layers)])
        self.num_layers = num_layers
        self.norm = norm

    def forward(self, tgt, memory, tgt_mask=None, memory_mask=None,
                cache=None):
        output = tgt
        new_caches = []
        for i, mod in enumerate(self.layers):
            if cache is None:
                output = mod(output, memory, tgt_mask, memory_mask)
            else:
                output, new_cache = mod(output, memory, tgt_mask, memory_mask,
                                        cache[i])
                new_caches.append(new_cache)
        if self.norm is not None:
            output = self.norm(output)
        return output if cache is None else (output, new_caches)

    def gen_cache(self, memory, do_zip=False):
        cache = [layer.gen_cache(memory) for layer in self.layers]
        if do_zip:
            cache = list(zip(*cache))
        return cache


def _clone_layer(layer):
    """Fresh re-init clone (paddle deep-copies; we rebuild with new params)."""
    import copy
    new = copy.copy(layer)
    new.__init__(**_ctor_args(layer))
    return new


def _ctor_args(layer):
    if isinstance(layer, TransformerEncoderLayer):
        return dict(d_model=layer.self_attn.embed_dim,
                    nhead=layer.self_attn.num_heads,
                    dim_feedforward=layer.linear1.out_features,
                    dropout=layer.dropout1.p,
                    activation=layer.activation.__name__,
                    attn_dropout=layer.self_attn.dropout,
                    act_dropout=layer.dropout.p,
                    normalize_before=layer.normalize_before)
    if isinstance(layer, TransformerDecoderLayer):
        return dict(d_model=layer.self_attn.embed_dim,
                    nhead=layer.self_attn.num_heads,
                    dim_feedforward=layer.linear1.out_features,
                    dropout=layer.dropout1.p,
                    activation=layer.activation.__name__,
                    attn_dropout=layer.self_attn.dropout,
                    act_dropout=layer.dropout.p,
                    normalize_before=layer.normalize_before)
    raise TypeError(type(layer))


class Transformer(Layer):
    def __init__(self, d_model=512, nhead=8, num_encoder_layers=6,
                 num_decoder_layers=6, dim_feedforward=2048, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None,
                 custom_encoder=None, custom_decoder=None):
        super().__init__()
        if custom_encoder is not None:
            self.encoder = custom_encoder
        else:
            enc_layer = TransformerEncoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation,
                attn_dropout, act_dropout, normalize_before)
            enc_norm = LayerNorm(d_model) if normalize_before else None
            self.encoder = TransformerEncoder(enc_layer, num_encoder_layers,
                                              enc_norm)
        if custom_decoder is not None:
            self.decoder = custom_decoder
        else:
            dec_layer = TransformerDecoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation,
                attn_dropout, act_dropout, normalize_before)
            dec_norm = LayerNorm(d_model) if normalize_before else None
            self.decoder = TransformerDecoder(dec_layer, num_decoder_layers,
                                              dec_norm)
        self.d_model = d_model
        self.nhead = nhead

    def forward(self, src, tgt, src_mask=None, tgt_mask=None,
                memory_mask=None):
        memory = self.encoder(src, src_mask)
        return self.decoder(tgt, memory, tgt_mask, memory_mask)

    def generate_square_subsequent_mask(self, length):
        import jax.numpy as jnp
        mask = jnp.where(jnp.tril(jnp.ones((length, length), bool)), 0.0,
                         -1e30).astype(jnp.float32)
        return Tensor(mask)
