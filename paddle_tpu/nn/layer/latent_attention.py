"""Latent (low-rank) attention with a latent KV plane, serving form.

A token's cache row is ``latent ‖ rotary key`` (``r_kv + d_r`` numbers for
all heads together); per-head keys and values are never stored.  The cached
attention has two forms over the same rows, and ``forward_cached`` takes
the one that costs less for the width ``T`` of the query block it is traced
for (:meth:`LatentAttention.cached_form`, from the layer's own dimensions):

  * **absorbed**, for a step's one query a row and any narrow block: the
    query is carried into the latent (``q_hat_h = W_uk,h^T q_nope,h``),
    scored against the cache rows directly, and ``W_uv,h`` is applied to
    the attention-weighted latent.  No key is expanded; a (query, column)
    pair costs ``2 r_kv + d_r`` multiply-adds a head;
  * **per head**, for a wide block (a prefill chunk): each block of columns
    is expanded once, ``k_n = latent W_uk``, ``v = latent W_uv`` for all
    heads, shared by the ``T`` queries; a pair costs ``d_n + d_r + d_v`` a
    head and the running sum is ``d_v`` wide, not ``r_kv``.  Cheaper from
    ``T (2 r_kv - d_n - d_v) > r_kv (d_n + d_v)`` on.  It runs as an XLA
    loop over the column blocks (``"per_head"``) or, where the program is
    traced for a TPU at shapes on the lane grid, as ONE Pallas kernel over
    the row's live blocks (``"per_head_fused"``,
    ops/pallas/latent_attention.py: expanded keys and values, float32
    scores and the accumulator stay in VMEM), the same numbers to float32
    rounding.

The masks, the running softmax and the row that is written are the same in
both.  Two kinds of layer share the code:

  * **full** layers keep a plane as long as the session and, beside it, a
    plane of selector keys; a learned selector (``index_n_heads`` small
    heads over the query's low-rank latent) scores every cached column and
    the attention reads the ``index_topk`` best of the causal ones (the
    search among the scores goes over the dispatch's live span, not the
    plane, and over nothing while no context passes ``index_topk``).  A
    full layer WITHOUT a selector (``index_topk`` 0) keeps the latent
    plane alone and reads every valid column of it;
  * **window** layers keep ONE ring plane of ``window + cache_block - 1``
    columns, shorter than the session, written at ``column mod length``
    and masked by absolute column.

The planes are what :meth:`LatentAttention.gen_ring_cache` builds; the
namedtuple classes carry what the Generator and the slot loop need to know
about them (``kind``, whether a plane wraps inside a session).  A sigmoid
gate, from the layer's normed input, multiplies the heads' outputs before
the output projection: one number a HEAD (``gate=True``, ``W_g`` ``hidden
-> H``) or one a FEATURE (``gate="feature"``, ``W_g`` ``hidden -> H x
d_v``: each of a head's ``d_v`` outputs has a gate of its own);
``gate=False`` builds none.  The two latents' norms are ``norm_layer``'s
(RMSNorm with a learned gain unless the model's norm has another form).  The
rotary positions take a config's ``rope_scaling`` (YaRN: blended
frequencies, and ``m^2`` on the softmax scale), in all three forms alike.

Everything here runs on raw arrays under ``no_grad`` (decode is
inference-only); ``forward`` is the cache-less per-head form in one pass
over a whole sequence, the same numbers by another route.
"""
from __future__ import annotations

import collections
import math

import jax
import jax.numpy as jnp
from jax import lax

from ...framework.tensor import Tensor, unwrap
from .. import initializer as I
from ..functional.attention import (PerHeadOperands, absorbed_products,
                                    fused_latent, latent_attend,
                                    latent_attend_blocked,
                                    latent_attend_fused, per_head_products,
                                    rotary, rotary_frequencies,
                                    search_widths, select_columns,
                                    select_columns_span, selector_scores,
                                    span_branch, yarn_attention_factor)
from .layers import Layer
from .transformer import ring_block_write

__all__ = ["RMSNorm", "SigmoidGainRMSNorm", "LatentAttention",
           "LatentCache", "LatentPlane", "LatentWindowCache"]

# full layers: ``latent [B, 1, C, r_kv + d_r]`` and the selector's keys
# ``index_key [B, 1, C, d_i]``; columns at axis 2 like every ring plane
LatentCache = collections.namedtuple("LatentCache", ["latent", "index_key"])
LatentCache.kind = "latent+selector_key"
LatentCache.wraps = False
# full layers without a selector: the latent plane alone.  Every column is
# a token's, written once, its content a function of the token prefix and
# of ``column - start`` only: a plane the prefix cache can cut
LatentPlane = collections.namedtuple("LatentPlane", ["latent"])
LatentPlane.kind = "latent"
LatentPlane.wraps = False
# window layers: one ring plane shorter than the session
LatentWindowCache = collections.namedtuple("LatentWindowCache", ["latent"])
LatentWindowCache.kind = "latent_window"
LatentWindowCache.wraps = True


def _rms(x, eps):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


class RMSNorm(Layer):
    """``x / rms(x) * weight``, statistics in float32."""

    def __init__(self, size, epsilon=1e-5, weight_attr=None, dtype=None):
        super().__init__()
        self._epsilon = float(epsilon)
        self.weight = self.create_parameter(
            [size], attr=weight_attr, dtype=dtype,
            default_initializer=I.Constant(1.0))

    def forward(self, x):
        raw = unwrap(x)
        out = (_rms(raw, self._epsilon)
               * unwrap(self.weight).astype(jnp.float32)).astype(raw.dtype)
        return Tensor(out) if isinstance(x, Tensor) else out


class SigmoidGainRMSNorm(Layer):
    """``x / rms(x) * (scale * sigmoid(weight))``, statistics in float32:
    a gain held inside ``(0, scale)``, and ``weight = 0`` gives ``scale /
    2`` (1 at ``scale`` 2: zero-centred)."""

    def __init__(self, size, epsilon=1e-5, scale=2.0, weight_attr=None,
                 dtype=None):
        super().__init__()
        self._epsilon, self._scale = float(epsilon), float(scale)
        self.weight = self.create_parameter(
            [size], attr=weight_attr, dtype=dtype,
            default_initializer=I.Constant(0.0))

    def forward(self, x):
        raw = unwrap(x)
        gain = self._scale * jax.nn.sigmoid(
            unwrap(self.weight).astype(jnp.float32))
        out = (_rms(raw, self._epsilon) * gain).astype(raw.dtype)
        return Tensor(out) if isinstance(x, Tensor) else out


def _layer_norm(x, g, b, eps):
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * g.astype(jnp.float32) \
        + b.astype(jnp.float32)


class LatentAttention(Layer):
    """One latent-attention layer.  ``window=None`` with ``index_topk``
    set is a full layer with the selector, with ``index_topk`` 0 a full
    layer that reads every valid column; ``window=w`` is a window layer
    (no selector).  ``cache_block`` is the widest token block one cached
    call may append; it sizes the window plane.  ``gate`` builds the
    output gate, a number a head (True) or a feature (``"feature"``);
    ``norm_layer(size)`` builds the latents' norms; ``rope_scaling`` is a
    config's (``yarn``)."""

    def __init__(self, hidden, num_heads, nope_dim, rope_dim, v_dim,
                 q_rank, kv_rank, rope_base, *, window=None,
                 index_heads=0, index_dim=0, index_topk=0, cache_block=512,
                 attn_block=512, epsilon=1e-5, rescale=True, gate=True,
                 rope_scaling=None, norm_layer=None, weight_attr=None,
                 dtype=None):
        super().__init__()
        if gate not in (False, True, "feature"):
            raise ValueError(f"gate {gate!r}: False, True (a head) or "
                             f"'feature'")
        if norm_layer is None:
            norm_layer = lambda size: RMSNorm(                  # noqa: E731
                size, epsilon, dtype=dtype)
        self.hidden, self.H = int(hidden), int(num_heads)
        self.dn, self.dr, self.dv = int(nope_dim), int(rope_dim), int(v_dim)
        self.rq, self.rkv = int(q_rank), int(kv_rank)
        self.base = float(rope_base)
        self.window = None if window is None else int(window)
        self.J, self.D = int(index_heads), int(index_dim)
        self.topk = int(index_topk)
        self.selects = self.window is None and self.topk > 0
        self.cache_block, self.attn_block = int(cache_block), int(attn_block)
        self.eps = float(epsilon)
        # the config's ``apply_mla_qkv_lora_rescale``: constants on the
        # normed latents, sqrt(hidden / rank)
        self.s_q = math.sqrt(hidden / q_rank) if rescale else 1.0
        self.s_kv = math.sqrt(hidden / kv_rank) if rescale else 1.0
        self.scale = yarn_attention_factor(rope_scaling) \
            / math.sqrt(self.dn + self.dr)
        # None: the plain frequencies of ``base`` (the selector's rotary
        # keeps those: a selector under YaRN is no published model's)
        self.inv = rotary_frequencies(self.dr, self.base, rope_scaling) \
            if rope_scaling else None

        def mat(*shape):
            return self.create_parameter(
                list(shape), attr=weight_attr, dtype=dtype,
                default_initializer=I.Normal(0.0, 0.02))

        H = self.H
        self.q_a = mat(hidden, self.rq)
        self.q_a_norm = norm_layer(self.rq)
        self.q_b = mat(self.rq, H * (self.dn + self.dr))
        self.kv_a = mat(hidden, self.rkv + self.dr)
        self.kv_a_norm = norm_layer(self.rkv)
        self.w_uk = mat(H, self.rkv, self.dn)
        self.w_uv = mat(H, self.rkv, self.dv)
        self.gate_features = gate == "feature"
        self.gate = mat(hidden, H * self.dv if self.gate_features else H) \
            if gate else None
        self.o_proj = mat(H * self.dv, hidden)
        if self.selects:
            self.idx_q = mat(self.rq, self.J * self.D)
            self.idx_k = mat(hidden, self.D)
            self.idx_k_norm_g = self.create_parameter(
                [self.D], dtype=dtype, default_initializer=I.Constant(1.0))
            self.idx_k_norm_b = self.create_parameter(
                [self.D], dtype=dtype, is_bias=True)
            self.idx_w = mat(hidden, self.J)

    # -- the planes ----------------------------------------------------------
    def ring_len(self, max_len):
        """Columns of this layer's latent plane in a session of
        ``max_len``: the session's own for a full layer, ``window +
        cache_block - 1`` (never more than the session) for a window."""
        if self.window is None:
            return int(max_len)
        return min(int(max_len), self.window + self.cache_block - 1)

    def ring_cache_spec(self, max_len):
        """What the Generator and the slot loop may know of this layer's
        planes (text/generation.py ``cache_spec``; ``cache_spec`` /
        ``gen_cache`` are the names a decoder of mixed layers asks its
        mixers by)."""
        cls = self._cache_class()
        return {"kind": cls.kind, "heads_per_lane_row": 1,
                "columns": self.ring_len(max_len), "wraps": cls.wraps,
                "window": self.window,
                "select_top": self.topk if self.selects else None,
                "select_widths": self.search_widths(max_len)}

    def search_widths(self, max_len):
        """The widths the selector's search may take in a session of
        ``max_len`` columns (``functional.attention.search_widths``),
        narrowest first; None for a layer that selects nothing."""
        return search_widths(int(max_len), self.topk) if self.selects \
            else None

    def _cache_class(self):
        if self.window is not None:
            return LatentWindowCache
        return LatentCache if self.selects else LatentPlane

    @property
    def row_width(self):
        """Numbers a cache row takes in the plane: ``r_kv + d_r`` padded
        to a multiple of 128.  A plane whose minor dimension is not a
        multiple of the lane count gets its COLUMNS put on the lanes by
        the TPU compiler, and the step's one-column write then carries
        its traced index there (``ring_block_write``;
        tools/kv_layout_check.py found it for 576 and 1088)."""
        return -(-(self.rkv + self.dr) // 128) * 128

    def gen_ring_cache(self, batch, max_len, dtype="float32"):
        from ...ops import zeros
        n = self.ring_len(max_len)
        lat = zeros([batch, 1, n, self.row_width], dtype=dtype)
        if not self.selects:
            return self._cache_class()(lat)
        return LatentCache(lat, zeros([batch, 1, n, self.D], dtype=dtype))

    cache_spec, gen_cache = ring_cache_spec, gen_ring_cache

    # -- projections shared by both forms --------------------------------------
    def _project(self, x, pos_ids):
        """From the normed input ``x [B, T, hidden]``: per-head queries
        ``q_n [B, T, H, dn]``, ``q_r [B, T, H, dr]`` (rotated), the cache
        row ``latent ‖ rotary key [B, T, rkv + dr]``, the gate ``[B, T,
        H]`` or ``[B, T, H x dv]`` (None without one) and the selector's
        query latent ``c_q``."""
        B, T, _ = x.shape
        dt = x.dtype
        w = lambda p: unwrap(p)                                # noqa: E731
        c_q = (self.s_q * unwrap(self.q_a_norm(
            jnp.einsum("bth,hr->btr", x, w(self.q_a),
                       preferred_element_type=jnp.float32)))).astype(dt)
        q = jnp.einsum("btr,rk->btk", c_q, w(self.q_b),
                       preferred_element_type=jnp.float32).astype(dt)
        q = q.reshape(B, T, self.H, self.dn + self.dr)
        q_n, q_r = q[..., :self.dn], rotary(q[..., self.dn:], pos_ids,
                                            self.base, inv=self.inv)
        kv = jnp.einsum("bth,hk->btk", x, w(self.kv_a),
                        preferred_element_type=jnp.float32)
        c_kv = (self.s_kv * unwrap(self.kv_a_norm(kv[..., :self.rkv]))
                ).astype(dt)
        k_r = rotary(kv[..., self.rkv:].astype(dt), pos_ids, self.base,
                     inv=self.inv)
        row = jnp.concatenate([c_kv, k_r], -1)
        gate = None if self.gate is None else jax.nn.sigmoid(jnp.einsum(
            "bth,hn->btn", x, w(self.gate),
            preferred_element_type=jnp.float32))
        return q_n, q_r, row, gate, c_q

    def _selector(self, x, c_q, pos_ids):
        """The selector's per-token pieces: queries ``[B, T, J, D]``,
        head weights ``[B, T, J]`` and the key ``[B, T, D]`` that is
        cached beside the latent."""
        B, T, _ = x.shape
        dt = x.dtype
        qi = jnp.einsum("btr,rk->btk", c_q, unwrap(self.idx_q),
                        preferred_element_type=jnp.float32).astype(dt)
        qi = rotary(qi.reshape(B, T, self.J, self.D), pos_ids, self.base,
                    dims=self.dr)
        ki = _layer_norm(jnp.einsum("bth,hd->btd", x, unwrap(self.idx_k),
                                    preferred_element_type=jnp.float32),
                         unwrap(self.idx_k_norm_g), unwrap(self.idx_k_norm_b),
                         self.eps).astype(dt)
        ki = rotary(ki, pos_ids, self.base, dims=self.dr)
        wi = jnp.einsum("bth,hj->btj", x, unwrap(self.idx_w),
                        preferred_element_type=jnp.float32) \
            * (self.J ** -0.5) * (self.D ** -0.5)
        return qi, wi, ki

    def _absorb_out(self, out_lat):
        """``W_uv`` on the attention-weighted latent (absorbed form)."""
        return jnp.einsum("bthr,hrv->bthv", out_lat, unwrap(self.w_uv),
                          preferred_element_type=jnp.float32)

    def _project_out(self, o, gate, dt):
        """The gate (a head or a feature) on the heads' outputs ``o [B,
        T, H, d_v]`` (float32), then the output projection."""
        B, T = o.shape[:2]
        if gate is not None:
            o = o * (gate.reshape(o.shape) if self.gate_features
                     else gate[..., None])
        o = o.astype(dt).reshape(B, T, self.H * self.dv)
        return jnp.einsum("btk,kh->bth", o, unwrap(self.o_proj),
                          preferred_element_type=jnp.float32).astype(dt)

    # -- cached: absorbed for a narrow block of queries, per head for a wide ---
    def cached_form(self, T, columns=None):
        """The form ``forward_cached`` is traced in for a block of ``T``
        queries, from the layer's own dimensions.  A (query, column) pair
        costs a head ``2 r_kv + d_r`` multiply-adds absorbed and ``d_n +
        d_r + d_v`` per head, where a column's keys and values must first
        be expanded, ``r_kv (d_n + d_v)`` a head, once for all ``T``
        queries: per head is the cheaper from ``T (2 r_kv - d_n - d_v) >
        r_kv (d_n + d_v)`` on (``T`` > 170 at 512 / 128 / 128, > 189 at
        1024 / 192 / 128; never where ``2 r_kv <= d_n + d_v``).

        The per-head form is one of two programs for the same numbers:
        ``"per_head"``, an XLA loop over the column blocks, and
        ``"per_head_fused"``, ONE Pallas kernel over them with the
        expanded keys and values, the float32 scores and the running sums
        in VMEM (ops/pallas/latent_attention.py).  Which, is decided when
        the program is traced, from what the code can see: the backend
        (the TPU), no mesh of several devices, and the shapes
        (``functional.attention.fused_latent``: ``T`` and ``attn_block``
        whole multiples of 128, the widths on the lane grid, an even
        number of heads; the chip's own timings are beside
        ``fused_latent_form``: alone the loop is as fast at 64 heads,
        served the kernel won at every published width set).
        ``columns`` is the plane's length where the caller holds the
        plane: the kernel walks whole blocks of ``attn_block``, so a plane
        they do not divide keeps the loop."""
        saved = 2 * self.rkv - self.dn - self.dv
        if T * saved <= self.rkv * (self.dn + self.dv):
            return "absorbed"
        whole = columns is None or columns % self.attn_block == 0
        fused = whole and fused_latent(
            T, self.attn_block, self.dn, self.dr, self.dv, self.rkv,
            self.row_width, self.H)
        return "per_head_fused" if fused else "per_head"

    def _per_head(self, q_n, q_r, fused=False):
        """The per-head pair of products, or, for the one-kernel form,
        just what they are made of."""
        operands = PerHeadOperands(q_n, q_r, unwrap(self.w_uk),
                                   unwrap(self.w_uv), self.rkv, self.scale)
        return operands if fused else per_head_products(*operands)

    def forward_cached(self, x, cache, pos, start, write_rows=None):
        """Append the block ``x [B, T, hidden]`` (normed) at column
        ``pos`` and attend, in the form :meth:`cached_form` names for
        ``T``; the cache row written is the same in both.  ``start [B]``
        is each row's first valid column; ``write_rows [B]`` (step
        programs of a slot loop) keeps dead rows from writing into a plane
        that wraps."""
        B, T, _ = x.shape
        cols = pos + jnp.arange(T, dtype=jnp.int32)
        pos_ids = jnp.maximum(cols[None, :] - start[:, None], 0)
        q_n, q_r, row, gate, c_q = self._project(x, pos_ids)
        form = self.cached_form(T, unwrap(cache.latent).shape[2])
        per_head = form != "absorbed"
        pad = self.row_width - self.rkv - self.dr
        if per_head:
            products = self._per_head(q_n, q_r, form == "per_head_fused")
        else:
            q_hat = jnp.einsum("bthd,hrd->bthr", q_n, unwrap(self.w_uk),
                               preferred_element_type=jnp.float32
                               ).astype(x.dtype)
            # the row's padding: zeros in the row and in the query, so the
            # scores over the padded width are the scores
            q_cat = jnp.concatenate(
                [q_hat, q_r, jnp.zeros(q_r.shape[:-1] + (pad,), q_r.dtype)],
                -1)
            products = absorbed_products(q_cat, self.rkv, self.scale)
        row = jnp.concatenate(
            [row, jnp.zeros(row.shape[:-1] + (pad,), row.dtype)], -1)
        with jax.named_scope("latent_attention"):
            if self.window is not None:
                out, cache = self._window(products, row, unwrap(cache.latent),
                                          cols, start, write_rows)
            else:
                out, cache = self._full(x, c_q, products, row, cache, cols,
                                        pos_ids, start)
        if not per_head:
            out = self._absorb_out(out.astype(x.dtype))
        return self._project_out(out, gate, x.dtype), cache

    def _window(self, products, row, lat, cols, start, write_rows):
        B, T = row.shape[:2]
        n = lat.shape[2]
        if n == self.window + self.cache_block - 1 and T > self.cache_block:
            # (a shorter plane is a whole session: it never wraps)
            raise ValueError(
                f"a block of {T} tokens does not fit a window plane of {n} "
                f"columns (window {self.window}, cache_block "
                f"{self.cache_block})")
        slot = cols[0] % n
        new = row[:, None].astype(lat.dtype)
        if write_rows is not None:
            # a dead slot row must not write: the column it would garble
            # may be one its own earlier chunk wrote, a ring length back
            old = lax.dynamic_slice(lat, (0, 0, slot, 0),
                                    (B, 1, T, lat.shape[3]))
            new = jnp.where(write_rows[:, None, None, None], new, old)
        lat = unwrap(ring_block_write(lat, new, slot))
        # ring slot j holds, for the query at column t, the newest column
        # c <= t with c = j (mod n)
        j = jnp.arange(n, dtype=jnp.int32)
        c = cols[:, None] - (cols[:, None] - j[None, :]) % n      # [T, n]
        keep = (c[None] > cols[None, :, None] - self.window) \
            & (c[None] >= start[:, None, None])
        if isinstance(products, PerHeadOperands):
            # the ring mask handed in, every block of the ring read
            out = latent_attend_fused(products, lat[:, 0], 0,
                                      n // self.attn_block, self.attn_block,
                                      start, cols[0], keep=keep)
        else:
            out = latent_attend(products, lat[:, 0], keep)
        return out, LatentWindowCache(Tensor(lat))

    def _full(self, x, c_q, products, row, cache, cols, pos_ids, start):
        B, T = row.shape[:2]
        lat = unwrap(cache.latent)
        C = lat.shape[2]
        pos = cols[0] % C
        lat = unwrap(ring_block_write(lat, row[:, None].astype(lat.dtype),
                                      pos))
        valid_of = lambda s: (                                  # noqa: E731
            (s[None, None, :] >= start[:, None, None])
            & (s[None, None, :] <= cols[None, :, None]))
        # column blocks with a running softmax, over the blocks some query
        # can see, for a chunk's block of queries and a step's one query a
        # row alike.  (A step could gather its index_topk chosen rows
        # instead of masking the others; on the v5e that gather moves 76
        # GB/s, 3.2 ms a layer at 64 rows, where reading every valid
        # column takes 1.2: PERF.md section 6, PR 27.)
        blk = self.attn_block if C % self.attn_block == 0 else C
        first = jnp.min(start)
        lo = first // blk
        hi = cols[-1] // blk + 1
        keep_of = lambda s0: valid_of(                           # noqa: E731
            s0 + jnp.arange(blk, dtype=jnp.int32))
        sel = None      # (no membership: the valid columns are the mask)
        if self.selects:
            with jax.named_scope("selector"):
                qi, wi, ki = self._selector(x, c_q, pos_ids)
                keys = unwrap(cache.index_key)
                keys = unwrap(ring_block_write(
                    keys, ki[:, None].astype(keys.dtype), pos))
                if C > self.topk:       # else every valid column is chosen
                    valid_blk = keep_of
                    # the search covers the dispatch's live span, not the
                    # plane; where no context passes index_topk (branch
                    # 0) every valid column is chosen: no search, and no
                    # scores either (the loop goes over no block)
                    widths = self.search_widths(C)
                    branch = span_branch(widths, self.topk, first, cols[-1])

                    def score(i, sc):
                        s0 = i * blk
                        kb = lax.dynamic_slice(
                            keys, (0, 0, s0, 0),
                            (B, 1, blk, keys.shape[3]))[:, 0]
                        sb = jnp.where(valid_blk(s0),
                                       selector_scores(qi, wi, kb), -jnp.inf)
                        return lax.dynamic_update_slice(sc, sb, (0, 0, s0))
                    # the scores apart from the search among them, by name
                    with jax.named_scope("score"):
                        sc = lax.fori_loop(
                            jnp.where(branch > 0, lo, hi), hi, score,
                            jnp.full((B, T, C), -jnp.inf, jnp.float32))
                    with jax.named_scope("select"):
                        sel = select_columns_span(
                            sc, lambda: valid_of(
                                jnp.arange(C, dtype=jnp.int32)),
                            self.topk, widths, branch, first)
                    keep_of = lambda s0: lax.dynamic_slice(     # noqa: E731
                        sel, (0, 0, s0), (B, T, blk))
        if isinstance(products, PerHeadOperands):
            out = latent_attend_fused(products, lat[:, 0], lo, hi, blk,
                                      start, cols[0], keep=sel)
        else:
            out = latent_attend_blocked(products, lat[:, 0], keep_of, lo, hi,
                                        blk)
        if not self.selects:
            return out, LatentPlane(Tensor(lat))
        return out, LatentCache(Tensor(lat), Tensor(keys))

    # -- cache-less, per-head form over a whole sequence -----------------------
    def forward(self, x):
        """``x [B, T, hidden]`` (normed), causal, every token from
        position 0: the per-head form in ONE pass over the sequence's own
        rows, the same selection and window rules.  No plane, no blocks:
        the plain route."""
        raw = unwrap(x)
        B, T, _ = raw.shape
        t = jnp.arange(T, dtype=jnp.int32)
        pos_ids = jnp.broadcast_to(t[None], (B, T))
        q_n, q_r, row, gate, c_q = self._project(raw, pos_ids)
        keep = jnp.broadcast_to((t[None, :] <= t[:, None])[None], (B, T, T))
        if self.window is not None:
            keep = keep & (t[None, None, :] > t[None, :, None] - self.window)
        elif self.selects:
            qi, wi, ki = self._selector(raw, c_q, pos_ids)
            keep = select_columns(selector_scores(qi, wi, ki), keep,
                                  self.topk)
        out = self._project_out(
            latent_attend(self._per_head(q_n, q_r), row, keep), gate,
            raw.dtype)
        return Tensor(out) if isinstance(x, Tensor) else out
