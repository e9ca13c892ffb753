"""Decoder-only LM of pre-norm RMSNorm blocks with latent attention and a
dropless sigmoid-routed MoE: the serving form of the DeepSeek-V3 lineage
with a learned column selector on its full layers and short window planes
on the rest.

Block: ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; final
RMSNorm; untied output head.  ``layer_types[i]`` says whether layer ``i``
is a full layer (latent plane as long as the session, selector-key plane,
top-``index_topk`` selection; with ``index_topk`` 0 the latent plane alone,
every valid column read: the DeepSeek-V3 form itself) or a window layer
(one ring plane of ``window + cache_block - 1`` columns); the first
``dense_layers`` FFNs are
dense SwiGLU, the rest :class:`~paddle_tpu.nn.layer.moe.DroplessMoE` told
which experts live here.  Rotary positions, one base per kind of layer;
``rope_scaling`` (YaRN) reaches the full layers.

The incremental-decoding contract of text/generation.py (``init_cache`` +
``forward_cached``) is implemented here; each layer's cache is what that
layer says it is (:meth:`cache_spec`), so a model's planes need not be
alike.  ``forward`` is the cache-less route over a whole sequence in the
per-head form.  Inference only: nothing here is taped.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ... import nn
from ...framework.tensor import Tensor, unwrap
from ...nn.layer.latent_attention import LatentAttention, RMSNorm
from ...nn.layer.moe import DroplessMoE, SwiGLU

__all__ = ["LatentMoEConfig", "LatentMoEDecoder"]

FULL, WINDOW = "full_attention", "sliding_attention"


@dataclasses.dataclass
class LatentMoEConfig:
    vocab_size: int = 1024              # rows of the embedding and the head
    hidden_size: int = 256
    layer_types: Sequence[str] = (FULL, WINDOW)
    dense_layers: int = 1               # leading layers with a dense FFN
    intermediate_size: int = 512
    moe_intermediate_size: int = 128
    num_experts: int = 16               # the router's width
    experts_held: Optional[Tuple[int, int]] = None   # [lo, hi) held here
    experts_per_token: int = 2
    shared_experts: int = 1
    routed_scaling: float = 1.0
    norm_topk: bool = True
    # full layers
    num_heads: int = 4
    nope_dim: int = 32
    rope_dim: int = 16
    v_dim: int = 32
    q_rank: int = 64
    kv_rank: int = 32
    rope_base: float = 8e7
    index_heads: int = 4
    index_dim: int = 32
    index_topk: int = 64                # 0: no selector, no selector keys
    # window layers
    window: int = 65
    window_heads: int = 2
    window_nope_dim: int = 32
    window_rope_dim: int = 16
    window_v_dim: int = 32
    window_q_rank: int = 64
    window_kv_rank: int = 64
    window_rope_base: float = 5e4
    rms_eps: float = 1e-5
    rescale_latents: bool = True
    attention_gate: bool = True         # the headwise output gate
    rope_scaling: Optional[dict] = None  # a config's, type "yarn"
    cache_block: int = 64               # widest block one cached call appends
    attn_block: int = 512               # column block of the blocked attention
    dtype: str = "float32"

    @classmethod
    def tiny(cls, **over):
        """A CPU-test size: 3 layers (full, window, full... as given),
        a selector that binds past 6 columns and a 5-column window."""
        base = dict(vocab_size=96, hidden_size=32,
                    layer_types=(FULL, FULL, WINDOW), dense_layers=1,
                    intermediate_size=64, moe_intermediate_size=16,
                    num_experts=8, experts_held=(0, 4), experts_per_token=2,
                    num_heads=2, nope_dim=8, rope_dim=4, v_dim=8, q_rank=16,
                    kv_rank=12, index_heads=2, index_dim=8, index_topk=6,
                    window=5, window_heads=2, window_nope_dim=8,
                    window_rope_dim=4, window_v_dim=8, window_q_rank=16,
                    window_kv_rank=16, cache_block=4, attn_block=8)
        base.update(over)
        return cls(**base)


def latent_attention_of(cfg: LatentMoEConfig, index: int, weight_attr=None):
    """Layer ``index``'s attention as ``cfg.layer_types`` says: a full
    layer (selector by ``index_topk``) or a window layer."""
    kind = cfg.layer_types[index]
    if kind not in (FULL, WINDOW):
        raise ValueError(f"layer_types[{index}] = {kind!r}")
    kw = dict(cache_block=cfg.cache_block, attn_block=cfg.attn_block,
              epsilon=cfg.rms_eps, rescale=cfg.rescale_latents,
              gate=cfg.attention_gate, weight_attr=weight_attr,
              dtype=cfg.dtype)
    if kind == FULL:
        return LatentAttention(
            cfg.hidden_size, cfg.num_heads, cfg.nope_dim, cfg.rope_dim,
            cfg.v_dim, cfg.q_rank, cfg.kv_rank, cfg.rope_base,
            index_heads=cfg.index_heads, index_dim=cfg.index_dim,
            index_topk=cfg.index_topk, rope_scaling=cfg.rope_scaling, **kw)
    return LatentAttention(
        cfg.hidden_size, cfg.window_heads, cfg.window_nope_dim,
        cfg.window_rope_dim, cfg.window_v_dim, cfg.window_q_rank,
        cfg.window_kv_rank, cfg.window_rope_base, window=cfg.window, **kw)


class LatentDecoderLayer(nn.Layer):
    def __init__(self, cfg: LatentMoEConfig, index: int, weight_attr=None):
        super().__init__()
        self.attn = latent_attention_of(cfg, index, weight_attr)
        self.input_norm = RMSNorm(cfg.hidden_size, cfg.rms_eps,
                                  dtype=cfg.dtype)
        self.post_norm = RMSNorm(cfg.hidden_size, cfg.rms_eps,
                                 dtype=cfg.dtype)
        if index < cfg.dense_layers:
            self.ffn = SwiGLU(cfg.hidden_size, cfg.intermediate_size,
                              weight_attr, cfg.dtype)
        else:
            self.ffn = DroplessMoE(
                cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
                cfg.experts_per_token, held=cfg.experts_held,
                shared=cfg.shared_experts, scaling=cfg.routed_scaling,
                norm_topk=cfg.norm_topk, weight_attr=weight_attr,
                dtype=cfg.dtype)

    # The residual stream is float32 whatever the weights are: every
    # layer's normed input is rounded to the weights' dtype as a product's
    # operand, the stream itself never is.  With the stream in bfloat16
    # the logits of the served model lay 0.008 (rms, of a spread of 1)
    # from the float32 reference at EVERY position, six times what
    # rounding the operands alone gives, and the router's top-k flipped
    # accordingly (PERF.md section 6, PR 27); the stream is [tokens,
    # hidden], so float32 costs nothing that shows.
    # Each half of the layer, its norm and its residual add included, lies
    # under the name a trace is read by (docs/METRICS.md).
    def _ffn(self, h, live):
        moe = isinstance(self.ffn, DroplessMoE)
        with jax.named_scope("experts" if moe else "mlp"):
            dt = unwrap(self.ffn.w_gate).dtype
            u = unwrap(self.post_norm(h)).astype(dt)
            y = self.ffn(u, live) if moe else self.ffn(u)
            return h + unwrap(y).astype(jnp.float32)

    def forward_cached(self, x, cache, pos, start, write_rows, live):
        with jax.named_scope("attention"):
            xn = unwrap(self.input_norm(x)).astype(
                unwrap(self.attn.q_a).dtype)
            a, cache = self.attn.forward_cached(xn, cache, pos, start,
                                                write_rows)
            h = x + a.astype(jnp.float32)
        return self._ffn(h, live), cache

    def forward(self, x):
        with jax.named_scope("attention"):
            xn = unwrap(self.input_norm(x)).astype(
                unwrap(self.attn.q_a).dtype)
            h = x + unwrap(self.attn(xn)).astype(jnp.float32)
        return self._ffn(h, None)


# what ``decode_counts`` returns, in order; the slot loop sums the first
# two over its dispatches and keeps the largest of the third
DECODE_COUNT_NAMES = ("moe_assignments", "moe_assignments_held",
                      "moe_expert_tokens_max")


class LatentMoEDecoder(nn.Layer):
    """``weight_attr`` (a ``ParamAttr``) reaches every matrix: a server
    that installs its own weights builds with a constant initializer."""

    # step programs of a slot loop pass the live rows, so that planes
    # which wrap inside a session are never written by a dead row
    cached_forward_takes_rows = True

    def __init__(self, cfg: LatentMoEConfig = None, weight_attr=None,
                 **kwargs):
        super().__init__()
        cfg = cfg or LatentMoEConfig(**kwargs)
        self.config = cfg
        self.embed = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                  weight_attr=weight_attr)
        if str(self.embed.weight.dtype) != cfg.dtype:
            # nn.Embedding builds in the default dtype
            w = self.embed.weight
            w._value = w._value.astype(cfg.dtype)
        self.layers = nn.LayerList([
            LatentDecoderLayer(cfg, i, weight_attr)
            for i in range(len(cfg.layer_types))])
        self.norm = RMSNorm(cfg.hidden_size, cfg.rms_eps, dtype=cfg.dtype)
        self.head = self.create_parameter(
            [cfg.hidden_size, cfg.vocab_size], attr=weight_attr,
            dtype=cfg.dtype,
            default_initializer=nn.initializer.Normal(0.0, 0.02))
        self._counts = None

    # -- whole sequence, per-head form -----------------------------------------
    def forward(self, input_ids):
        with jax.named_scope("embed"):
            h = unwrap(self.embed(input_ids)).astype(jnp.float32)
        for layer in self.layers:
            h = layer(h)
        return Tensor(self._logits(h))

    def _logits(self, h):
        head = unwrap(self.head)
        with jax.named_scope("head"):
            return jnp.einsum("bth,hv->btv",
                              unwrap(self.norm(h)).astype(head.dtype), head,
                              preferred_element_type=jnp.float32)

    # -- incremental decoding --------------------------------------------------
    def cache_spec(self, max_len):
        """Per layer, what its planes are (``LatentAttention.
        ring_cache_spec``)."""
        return [l.attn.ring_cache_spec(max_len) for l in self.layers]

    def latent_form(self, T):
        """The form the layers' cached attention is traced in for a block
        of ``T`` tokens (``LatentAttention.cached_form``; a wider block
        is fed ``cache_block`` at a time): ``"absorbed"``, ``"per_head"``,
        or ``"mixed"`` where the layers' dimensions decide differently."""
        forms = {l.attn.cached_form(min(int(T), self.config.cache_block))
                 for l in self.layers}
        return forms.pop() if len(forms) == 1 else "mixed"

    def init_cache(self, batch, max_len, dtype=None):
        if dtype is None:
            dtype = str(self.head.dtype)
        return [l.attn.gen_ring_cache(batch, max_len, dtype)
                for l in self.layers]

    def forward_cached(self, input_ids, cache, cache_position,
                       start_positions, write_rows=None):
        """Append ``input_ids [B, T]`` at column ``cache_position`` (the
        LEFT-padded prompt or a chunk of it, or one token a row) and
        return (logits ``[B, T, V]`` float32, the updated caches).  A
        block wider than ``cache_block`` is fed in pieces, so that the
        window planes never see more than they hold.  ``write_rows [B]``
        marks the rows whose writes count (a slot loop's live rows)."""
        ids = unwrap(input_ids)
        B, T = ids.shape
        pos = unwrap(cache_position)
        pos = jnp.int32(pos) if isinstance(pos, int) \
            else jnp.asarray(pos, jnp.int32)
        start = jnp.asarray(unwrap(start_positions), jnp.int32)
        rows = None if write_rows is None else unwrap(write_rows)
        step = self.config.cache_block
        counts, logits = None, []
        for t0 in range(0, T, step):
            part, cache, c = self._cached_block(
                ids[:, t0:t0 + step], cache, pos + t0, start, rows)
            logits.append(part)
            counts = c if counts is None else (
                counts[0] + c[0], counts[1] + c[1],
                jnp.maximum(counts[2], c[2]))
        self._counts = counts
        return Tensor(jnp.concatenate(logits, 1) if len(logits) > 1
                      else logits[0]), cache

    def _cached_block(self, ids, cache, pos, start, rows):
        B, T = ids.shape
        with jax.named_scope("embed"):
            h = unwrap(self.embed(Tensor(ids))).astype(jnp.float32)
            cols = pos + jnp.arange(T, dtype=jnp.int32)
            live = cols[None, :] >= start[:, None]
            if rows is not None:
                live = live & rows[:, None]
        zero = jnp.int32(0)
        counts, new = (zero, zero, zero), []
        for layer, c in zip(self.layers, cache):
            h, c = layer.forward_cached(h, c, pos, start, rows, live)
            new.append(c)
            if isinstance(layer.ffn, DroplessMoE):
                a, b, m = layer.ffn.last_counts
                counts = (counts[0] + a, counts[1] + b,
                          jnp.maximum(counts[2], m))
        return self._logits(h), new, counts

    def decode_counts(self):
        """int32 ``[3]`` of the last ``forward_cached`` (same trace):
        ``DECODE_COUNT_NAMES``, over the live tokens of all MoE layers."""
        return jnp.stack(self._counts) if self._counts is not None else None

    decode_count_names = DECODE_COUNT_NAMES

    def generate(self, input_ids, lengths=None, max_new_tokens=32, **kw):
        from ..generation import generate as _generate
        return _generate(self, input_ids, lengths=lengths,
                         max_new_tokens=max_new_tokens, **kw)
