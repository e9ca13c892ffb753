"""GPT-style decoder-only LM (causal transformer).

Not present in the 2.0-rc reference model zoo, but the natural second
transformer workload for the TPU framework (the scaling/pipeline strategies
need a decoder-only config). Shares TP annotation logic with bert.py.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax

from ... import nn
from ...nn import functional as F
from ...ops import manipulation as M


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 1024
    dropout: float = 0.1

    @classmethod
    def tiny(cls, vocab_size=128, hidden_size=32, layers=2, heads=2, seq=64):
        return cls(vocab_size=vocab_size, hidden_size=hidden_size,
                   num_layers=layers, num_heads=heads,
                   intermediate_size=hidden_size * 4,
                   max_position_embeddings=seq)


class GPTModel(nn.Layer):
    def __init__(self, cfg: GPTConfig = None, **kwargs):
        super().__init__()
        cfg = cfg or GPTConfig(**kwargs)
        self.config = cfg
        self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.wpe = nn.Embedding(cfg.max_position_embeddings, cfg.hidden_size)
        self.drop = nn.Dropout(cfg.dropout)
        layer = nn.TransformerEncoderLayer(
            cfg.hidden_size, cfg.num_heads, cfg.intermediate_size,
            dropout=cfg.dropout, activation="gelu", normalize_before=True)
        self.encoder = nn.TransformerEncoder(layer, cfg.num_layers,
                                             norm=nn.LayerNorm(cfg.hidden_size))

    def forward(self, input_ids, labels=None):
        from ... import ops
        b, s = input_ids.shape
        with jax.named_scope("embed"):
            pos = M.unsqueeze(ops.arange(s, dtype="int64"), 0)
            h = self.drop(self.wte(input_ids) + self.wpe(pos))
            causal = ops.triu(ops.full([s, s], -1e4, dtype="float32"),
                              diagonal=1)
        h = self.encoder(h, M.unsqueeze(causal, [0, 1]))
        with jax.named_scope("head"):
            logits = ops.matmul(h, self.wte.weight, transpose_y=True)
        if labels is None:
            return logits
        with jax.named_scope("loss"):
            return F.cross_entropy(
                logits[:, :-1].reshape([-1, self.config.vocab_size]),
                labels[:, 1:].reshape([-1]))

    # -- incremental decoding (static-shape KV ring cache) -------------------
    def init_cache(self, batch, max_len, dtype=None):
        """Per-layer zero ring caches ``[batch, ceil(heads/g), max_len,
        g*head_dim]`` (``g`` adjacent heads per row of the minor dim,
        ``MultiHeadAttention.gen_ring_cache``; ``g = 1``, i.e. ``[batch,
        heads, max_len, head_dim]``, for head_dim >= 128 and for the
        int8 cache); ``max_len`` is the compile-time cache length, at
        axis 2 in every plane."""
        if dtype is None:
            dtype = str(self.wte.weight.dtype)
        return self.encoder.gen_ring_cache(batch, max_len, dtype)

    def cache_spec(self, max_len):
        """Per layer, what its cache planes are (the attention layers'
        own ``ring_cache_spec``): the Generator and the slot loop read
        the layout from here, not from the planes' count."""
        return self.encoder.ring_cache_spec(max_len)

    # forward_cached takes ``row``: a batch-1 block goes into one row of
    # the full planes in place (text/generation.py, the prefill chunk)
    cached_forward_takes_row = True

    def forward_cached(self, input_ids, cache, cache_position,
                       start_positions, row=None):
        """One incremental step over the ring cache.

        input_ids [B, T] — the tokens to append (the LEFT-padded prompt
        at prefill, one token per row at decode); ``cache_position`` is
        the cache column the first new token writes (int or traced int32
        scalar — the write wraps modulo the static cache length);
        ``start_positions`` [B] is each row's first valid cache column
        (its left-pad offset).  Token positions and the additive
        validity+causality mask are derived from those two, so batch and
        cache length stay compile-time constants.  With ``row`` (a
        traced int32 scalar) ``input_ids`` is ``[1, T]``, ``start_positions``
        ``[1]`` and ``cache`` the FULL ``S``-row planes: the block is
        written into row ``row`` in place and attends that row only
        (``MultiHeadAttention._forward_ring``).  Returns
        (logits [B, T, V], updated cache).
        """
        import jax.numpy as jnp
        from ... import ops
        from ...framework.tensor import Tensor, unwrap
        b, t = input_ids.shape
        C = cache[0].k.shape[2]
        pos = unwrap(cache_position)
        pos = jnp.asarray(pos, jnp.int32) if not isinstance(pos, int) \
            else jnp.int32(pos)
        start = jnp.asarray(unwrap(start_positions), jnp.int32)
        with jax.named_scope("embed"):
            qcol = pos + jnp.arange(t, dtype=jnp.int32)  # global cache cols
            pos_ids = jnp.clip(qcol[None, :] - start[:, None], 0,
                               self.config.max_position_embeddings - 1)
            h = self.drop(self.wte(input_ids) + self.wpe(Tensor(pos_ids)))
            # valid key col j for query row i: start_b <= j <= pos + i
            col = jnp.arange(C, dtype=jnp.int32)
            valid = ((col[None, None, None, :] <= qcol[None, None, :, None])
                     & (col[None, None, None, :]
                        >= start[:, None, None, None]))
            mask = Tensor(jnp.where(valid, 0.0, -1e30).astype(jnp.float32))
        window = None
        if t == 1:
            # decode step: the mask is a contiguous [start, pos+1) window,
            # which is what the blocked read of the live span goes by
            window = (Tensor(start), Tensor(jnp.broadcast_to(pos + 1, (b,))))
        h, new_cache = self.encoder(
            h, mask, cache=cache,
            cache_position=Tensor(pos % jnp.int32(C)),
            decode_window=window, row=row)
        with jax.named_scope("head"):
            logits = ops.matmul(h, self.wte.weight, transpose_y=True)
        return logits, new_cache

    def generate(self, input_ids, lengths=None, max_new_tokens=32,
                 beam_size=1, eos_token_id=None, draft_model=None, **kw):
        """Autoregressive decoding compiled as exactly two executables
        (text.generation: one prefill jit + one scanned decode step).
        With ``draft_model`` (a smaller GPT over the same vocab) the two
        executables become the joint prefill + the speculative
        propose/verify scan (text.speculative) — same greedy output, up
        to gamma+1 tokens per target forward."""
        from ..generation import generate as _generate
        return _generate(self, input_ids, draft_model=draft_model,
                         lengths=lengths, max_new_tokens=max_new_tokens,
                         beam_size=beam_size, eos_token_id=eos_token_id,
                         **kw)


@dataclasses.dataclass
class GPTMoEConfig(GPTConfig):
    """GPT config with every ``moe_every``-th block's FFN replaced by an
    expert-parallel MoE layer (nn.layer.moe).  ``moe_top_k`` /
    ``moe_capacity_factor`` default to the FLAGS_moe_* values and are
    RESOLVED at model construction, so the config (and therefore the
    persistent executable cache's program-identity key, which hashes
    these fields) always names the concrete gating program."""

    moe_num_experts: int = 8
    moe_top_k: Optional[int] = None           # None -> FLAGS_moe_top_k
    moe_capacity_factor: Optional[float] = None  # None -> FLAGS value
    moe_every: int = 2                        # every other block is MoE
    moe_aux_weight: float = 1e-2

    @classmethod
    def tiny(cls, vocab_size=128, hidden_size=32, layers=2, heads=2,
             seq=64, experts=8, top_k=None, capacity_factor=None,
             moe_every=2):
        return cls(vocab_size=vocab_size, hidden_size=hidden_size,
                   num_layers=layers, num_heads=heads,
                   intermediate_size=hidden_size * 4,
                   max_position_embeddings=seq, moe_num_experts=experts,
                   moe_top_k=top_k, moe_capacity_factor=capacity_factor,
                   moe_every=moe_every)


class GPTMoEModel(GPTModel):
    """Decoder-only LM with alternating dense / Mixture-of-Experts
    blocks: block ``i`` is MoE when ``(i + 1) % moe_every == 0`` (so
    ``moe_every=2`` replaces every other block's FFN), expert FFNs are
    stacked ``[E, ...]`` parameters sharded over the expert-parallel
    axis, and the training loss carries the gates' load-balance aux
    term.  Shares GPTModel's incremental-decoding contract verbatim —
    ``generate()`` and the serving decode grid run
    unchanged (the MoE dispatch is just more ops inside the same two
    executables).

    ``dispatch="dense"`` builds the bit-match control: identical
    parameters and gating, GShard dense-dispatch instead of the
    all-to-all movers.
    """

    def __init__(self, cfg: GPTMoEConfig = None, *, mesh=None,
                 dispatch: str = "routed", annotate: bool = True,
                 **kwargs):
        from ... import nn
        from ...nn.layer.moe import (MoEEncoderLayer, moe_capacity_factor,
                                     moe_top_k)
        nn.Layer.__init__(self)
        cfg = cfg or GPTMoEConfig(**kwargs)
        # resolve flag-defaulted gating knobs NOW: the config is the
        # program identity (persistent cache) and must be concrete
        if cfg.moe_top_k is None:
            cfg.moe_top_k = moe_top_k()
        if cfg.moe_capacity_factor is None:
            cfg.moe_capacity_factor = moe_capacity_factor()
        if cfg.moe_every < 1:
            raise ValueError(f"moe_every must be >= 1, got {cfg.moe_every}")
        self.config = cfg
        self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.wpe = nn.Embedding(cfg.max_position_embeddings,
                                cfg.hidden_size)
        self.drop = nn.Dropout(cfg.dropout)
        blocks = []
        for i in range(cfg.num_layers):
            if (i + 1) % cfg.moe_every == 0:
                blocks.append(MoEEncoderLayer(
                    cfg.hidden_size, cfg.num_heads, cfg.intermediate_size,
                    cfg.moe_num_experts, dropout=cfg.dropout,
                    activation="gelu", normalize_before=True,
                    top_k=cfg.moe_top_k,
                    capacity_factor=cfg.moe_capacity_factor, mesh=mesh,
                    dispatch=dispatch, annotate=annotate))
            else:
                blocks.append(nn.TransformerEncoderLayer(
                    cfg.hidden_size, cfg.num_heads, cfg.intermediate_size,
                    dropout=cfg.dropout, activation="gelu",
                    normalize_before=True))
        self.encoder = nn.TransformerEncoder(
            blocks, norm=nn.LayerNorm(cfg.hidden_size))

    def forward(self, input_ids, labels=None):
        from ...nn.layer.moe import total_aux_loss
        from ...framework.tensor import Tensor
        out = GPTModel.forward(self, input_ids, labels)
        if labels is None:
            return out
        # loss plumbing: the gates train through the aux term riding the
        # same scalar TrainStep already consumes
        aux = total_aux_loss(self)
        return out + Tensor(aux) * self.config.moe_aux_weight

    def moe_aux_loss(self):
        """Summed load-balance loss of the last forward (traced inside
        a step; concrete after an eager call — the bench probe)."""
        from ...nn.layer.moe import total_aux_loss
        return total_aux_loss(self)


def apply_tensor_parallel(model: GPTModel):
    """Megatron-style TP over ``mp`` for the decoder-only stack — the
    SAME ``analysis.autoshard.transformer_rules()`` table BERT shards
    from (vocab-sharded ``wte``, column-parallel QKV/FFN-in,
    row-parallel attn-out/FFN-out; ``wpe`` replicated).  GPT never had a
    hand annotation list: the table covered it from day one — the tied
    ``wte`` output projection rides the embedding's vocab shard."""
    from ...analysis.autoshard import apply as _autoshard_apply
    from ...analysis.autoshard import transformer_rules
    _autoshard_apply(model, rules=transformer_rules())
    return model
