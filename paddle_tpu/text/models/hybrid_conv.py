"""Decoder-only LM whose layers differ in MIXER and in FFN independently:
gated short convolutions, grouped-query attention (dense, or block-sparse
over a pooled-key plane), latent attention, Mamba-2 state-space, lightning
linear-attention or gated delta-rule mixers; dense SwiGLU or a
sigmoid-routed MoE that holds its experts or a share of them (the
``lfm2_moe``, ``nemotron_h``, ``minicpm_sala`` and ``gigachat3_5``
lineages).

Block (``block_norms`` ``"pre"``): ``h = x + r Mix(N(x))``, ``y = h + r
FFN(N(h))``, where a layer may have a mixer alone or an FFN alone
(``layer_types[i]`` / ``ffn_types[i]`` ``"none"``: the other half is the
whole layer); with ``block_norms`` ``"pre_post"`` each half is normed
after as well as before, inside its residual branch: ``h = x + r N(Mix(N(x)))``,
``y = h + r N(FFN(N(h)))``, four norm vectors a layer.  ``N`` is RMSNorm
with a learned gain (``norm_gain`` ``"plain"``) or with the gain
``norm_gain_scale x sigmoid(w)`` (``"sigmoid"``:
:class:`~paddle_tpu.nn.layer.latent_attention.SigmoidGainRMSNorm`), every
norm of the model alike; final ``N``; the output head is the embedding
(tied) or a matrix of its own.  ``ffn_limit`` clamps every SwiGLU's two
halves (``nn.layer.moe.clamped``).
Three scalings, each 1 unless the configuration says otherwise (muP): the
embedding times ``embed_scale``, each residual branch times ``r =
residual_scale``, the final normed state divided by ``logit_divisor``
before the head.  ``layer_types[i]`` is

  * ``"conv"``: ``[B | C | X] = W_in x``, ``u = B * X``, ``c(t) = sum_j
    w_j * u(t - (L-1) + j)`` (depthwise, ``L`` taps, ``u = 0`` before the
    request's first token), ``Mix = W_out (C * c)``.  What such a layer
    keeps of a row is ``u`` at the ``L - 1`` columns before the block being
    fed: a plane ``[B, 1, L-1, hidden]`` WITHOUT columns (kind
    ``conv_state``), overwritten in place;
  * ``"full_attention"``: ``rep = heads / kv_heads`` query heads a cached
    head, RMSNorm over each head's features of q and k (one learned vector
    each), rotary positions, K (after norm and rotary) and V in the GPT
    family's packed ring planes (``gen_ring_cache``'s layout, kind ``kv``);
    ``qk_norm`` false leaves the per-head norms out, ``rope_base`` None the
    rotary positions (the ``nemotron_h`` attention has neither);
  * ``"ssm"``: :class:`~paddle_tpu.nn.layer.mamba2.Mamba2Mixer`, which
    keeps a float32 state ``[B, heads, head_dim, state]`` and the last
    ``taps - 1`` inputs of its convolution (kind ``ssm_state``), both
    WITHOUT columns;
  * ``"linear_attention"``: :class:`~paddle_tpu.nn.layer.linear_attention.
    LightningAttention`, a float32 matrix state a head fed by outer
    products (kind ``ssm_state`` too: the same summed-state rule), with
    per-head norms and rotary positions before it, a norm and a sigmoid
    gate after it;
  * ``"sparse_attention"``: :class:`BlockSparseAttention`, grouped-query
    attention with a sigmoid output gate that, past ``dense_len`` tokens
    of context, reads ``top`` blocks of ``block`` columns chosen from
    scores over mean-pooled keys, which it keeps in a third plane beside
    K and V (kind ``kv+pooled_key``: an entry every ``stride`` columns);
  * ``"gated_delta"``: :class:`~paddle_tpu.nn.layer.gated_delta.
    GatedDeltaNet`, a float32 matrix state a value head that each token
    decays by a rate of its own and corrects by what the state already
    gives for its key (the delta rule), behind a short convolution over
    ``[q | k | v]``: the state and the convolution's inputs, kind
    ``ssm_state``, WITHOUT columns;
  * ``"latent_attention"``: :class:`~paddle_tpu.nn.layer.latent_attention.
    LatentAttention` without selector or window: ONE latent plane as long
    as the session (kind ``latent``: ``kv_rank + rope_dim`` numbers a
    token for all heads), YaRN positions (``rope_scaling``), a sigmoid
    output gate a head or a feature (``latent_gate``).

``ffn_types[i]`` is ``"dense"``, ``"moe"``
(:class:`~paddle_tpu.nn.layer.moe.DroplessMoE`) or ``"none"``; left out,
the first ``dense_layers`` FFNs are dense and the rest MoE.

**Liveness of a state without columns: two rules, side by side.**

  * *A short convolution's inputs are positional*, like everything else
    in a slot loop (``conv`` layers, and the convolution inside an ``ssm``
    or a ``gated_delta`` layer): the entries ARE the inputs at the columns
    ``pos - (L-1) .. pos - 1``, and an entry counts iff its column is at
    or after the row's ``start``.
  * *A summed state cannot be* (an ``ssm`` layer's, a ``linear_attention``
    layer's, a ``gated_delta`` layer's matrix state): it stands for every
    column before the block.  The state handed to a block whose first
    column is ``pos`` counts iff ``pos > start`` (else the request begins
    inside the block: zeros), and a token before ``start`` inside the
    block passes it through unchanged (a delta-rule token with decay 1 and
    ``beta`` 0 neither reads nor writes).

A ``latent_attention`` layer's plane follows neither: it HAS columns, a
row a token, masked by ``start`` like K/V (a dead row's write lands in a
dead column).

Either covers a slot's previous occupant (its leftovers lie before the new
``start``), left padding inside a chunk and a ring restart, with no reset
program.  What neither can cover is a row that is not being fed by a step
(it waits for the frontier between its chunks, or is done): a step must
leave that row's state as it is, so the model takes the step's live rows
(``write_rows``, ``cached_forward_takes_rows``).

Inference only: nothing here is taped.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp

from ... import nn
from ...framework.tensor import Tensor, unwrap
from ...nn import initializer as I
from ...nn.functional.attention import (BlockSparse, block_keep,
                                        choose_blocks, pool_keys_write,
                                        pooled_entries, rotary,
                                        span_attention)
from ...nn.layer.gated_delta import GatedDeltaNet
from ...nn.layer.latent_attention import (LatentAttention, RMSNorm,
                                          SigmoidGainRMSNorm)
from ...nn.layer.linear_attention import LightningAttention, decay_slopes
from ...nn.layer.mamba2 import Mamba2Mixer, _product
from ...nn.layer.moe import DroplessMoE, SwiGLU
from ...nn.layer.transformer import (MultiHeadAttention,
                                     kv_heads_per_lane_row, pack_heads,
                                     ring_block_write)

__all__ = ["HybridConvConfig", "HybridConvDecoder", "ConvStateCache",
           "ShortConv", "GroupedQueryAttention", "BlockSparseAttention",
           "PooledKeyCache"]

CONV, ATTN, SSM, NONE = "conv", "full_attention", "ssm", "none"
LINEAR, SPARSE = "linear_attention", "sparse_attention"
DELTA, LATENT = "gated_delta", "latent_attention"
DENSE, MOE = "dense", "moe"

# a conv layer's cache: ``state [B, 1, L-1, hidden]``, row first like every
# plane (so the slot programs cut a row out of it as out of any other),
# the hidden features on the lanes, oldest entry first; no columns, nothing
# to wrap.  (The step computes on ``[rows, hidden]`` operands in another
# device layout than a plane of this shape, or of ``[B, (L-1) * hidden]``,
# lies in, and relays each 1 MB plane there and back: tools/
# kv_layout_check.py counts it, PERF.md section 7.)
ConvStateCache = collections.namedtuple("ConvStateCache", ["state"])
ConvStateCache.kind = "conv_state"
ConvStateCache.wraps = False
RingCache = MultiHeadAttention.RingCache
# a block-sparse layer's cache: K and V ring planes with a cached head a
# lane row (``[B, KV, C, d]``: a block is chosen a cached head), and the
# pooled keys ``[B, KV, ceil(C / stride), d]``, whose entry ``col // stride``
# holds the row's window that ends in that group of columns
PooledKeyCache = collections.namedtuple("PooledKeyCache",
                                        ["k", "v", "pooled"])
PooledKeyCache.kind = "kv+pooled_key"
PooledKeyCache.wraps = False


@dataclasses.dataclass
class HybridConvConfig:
    vocab_size: int = 1024
    hidden_size: int = 256
    layer_types: Sequence[str] = (CONV, ATTN)
    ffn_types: Optional[Sequence[str]] = None   # None: by ``dense_layers``
    dense_layers: int = 1               # leading layers with a dense FFN
    intermediate_size: int = 512
    moe_intermediate_size: int = 128
    num_experts: int = 8
    held_experts: Optional[Sequence[int]] = None    # (lo, hi); None: all
    shared_experts: int = 0
    expert_activation: str = "swiglu"
    experts_per_token: int = 2
    routed_scaling: float = 1.0
    norm_topk: bool = True
    # w_i = s_i / (sum_chosen s + eps); None: / max(sum_chosen s, 1e-20)
    routing_norm_eps: Optional[float] = 1e-6
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 64
    qk_norm: bool = True
    rope_base: Optional[float] = 1e6    # None: no rotary positions
    conv_taps: int = 3                  # the config's ``conv_L_cache``
    ssm_heads: int = 4
    ssm_head_dim: int = 16
    ssm_state: int = 16
    ssm_groups: int = 2
    ssm_taps: int = 4
    ssm_chunk: int = 8
    linear_heads: int = 4
    linear_head_dim: int = 16
    linear_rope_base: Optional[float] = 10000.0
    linear_chunk: int = 8
    # a linear layer's decay falls with its relative depth ``l / (L - 1)``
    # in the model its slopes are taken from: that depth for each layer of
    # ``layer_types`` (a stage of a deeper model says where it lay there);
    # None: the layers' own indices over their count
    linear_decay_depth: Optional[Sequence[float]] = None
    sparse: Optional[BlockSparse] = None    # the sparse layers' rule
    sparse_gate: bool = False       # the sparse layers' sigmoid output gate
    # the gated delta-rule layers
    delta_key_heads: int = 2
    delta_value_heads: int = 4
    delta_key_dim: int = 16
    delta_value_dim: int = 16
    delta_taps: int = 4
    delta_chunk: int = 8
    delta_gate_scale: float = 2.0
    # the latent layers (``num_heads`` heads)
    nope_dim: int = 16
    latent_rope_dim: int = 8
    v_dim: int = 16
    q_rank: int = 32
    kv_rank: int = 24
    latent_rope_base: float = 1e4
    rope_scaling: Optional[dict] = None     # a config's, type "yarn"
    latent_gate: Union[bool, str] = False   # True: a head; "feature"
    latent_block: int = 512     # widest block fed at once (the chunk)
    attn_block: int = 512       # column block of the latent read
    block_norms: str = "pre"            # or "pre_post"
    norm_gain: str = "plain"            # or "sigmoid"
    norm_gain_scale: float = 2.0
    ffn_limit: Optional[float] = None   # the SwiGLUs' clamp
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logit_divisor: float = 1.0
    tie_embeddings: bool = True
    rms_eps: float = 1e-5
    dtype: str = "float32"

    def ffn_type(self, index: int) -> str:
        if self.ffn_types is not None:
            return self.ffn_types[index]
        return DENSE if index < self.dense_layers else MOE

    def norm(self, size: int):
        """The model's norm over ``size`` features (``norm_gain``)."""
        if self.norm_gain == "sigmoid":
            return SigmoidGainRMSNorm(size, self.rms_eps,
                                      self.norm_gain_scale, dtype=self.dtype)
        if self.norm_gain != "plain":
            raise ValueError(f"norm_gain {self.norm_gain!r}")
        return RMSNorm(size, self.rms_eps, dtype=self.dtype)

    @classmethod
    def tiny(cls, **over):
        """A CPU-test size: hidden 64, 6 layers that cover dense + conv,
        MoE + attention and MoE + conv, 8 experts of which 2 a token."""
        base = dict(vocab_size=96, hidden_size=64,
                    layer_types=(CONV, CONV, ATTN, CONV, CONV, ATTN),
                    dense_layers=2, intermediate_size=96,
                    moe_intermediate_size=32, num_experts=8,
                    experts_per_token=2, num_heads=4, num_kv_heads=2,
                    head_dim=16)
        base.update(over)
        return cls(**base)

    @classmethod
    def tiny_ssm(cls, **over):
        """A CPU-test size of the one-part-a-layer form: state-space,
        expert and attention layers, each a mixer or an FFN alone; 8
        relu^2 experts of which 3 a token and one shared; an untied head;
        attention without per-head norm and rotary positions."""
        pattern = "MEMEM*EME"
        base = dict(vocab_size=96, hidden_size=64,
                    layer_types=tuple({"M": SSM, "*": ATTN}.get(c, NONE)
                                      for c in pattern),
                    ffn_types=tuple(MOE if c == "E" else NONE
                                    for c in pattern),
                    moe_intermediate_size=32, num_experts=8,
                    experts_per_token=3, shared_experts=2,
                    expert_activation="relu2", routed_scaling=2.5,
                    routing_norm_eps=1e-20, num_heads=4, num_kv_heads=2,
                    head_dim=16, qk_norm=False, rope_base=None,
                    ssm_heads=4, ssm_head_dim=16, ssm_state=16,
                    ssm_groups=2, ssm_taps=4, ssm_chunk=8,
                    tie_embeddings=False)
        base.update(over)
        return cls(**base)


def _mat(layer, shape, weight_attr, dtype):
    return layer.create_parameter(
        list(shape), attr=weight_attr, dtype=dtype,
        default_initializer=I.Normal(0.0, 0.02))


class ShortConv(nn.Layer):
    """The gated short-convolution mixer (no bias)."""

    def __init__(self, hidden, taps=3, weight_attr=None, dtype=None):
        super().__init__()
        self.hidden, self.taps = int(hidden), int(taps)
        self.in_proj = _mat(self, (hidden, 3 * hidden), weight_attr, dtype)
        # tap j weighs u(t - (taps-1) + j): the last tap is the token's own
        self.conv = _mat(self, (hidden, self.taps), weight_attr, dtype)
        self.out_proj = _mat(self, (hidden, hidden), weight_attr, dtype)

    def cache_spec(self, max_len):
        return {"kind": ConvStateCache.kind, "heads_per_lane_row": 1,
                "columns": 0, "wraps": False, "window": None,
                "select_top": None}

    def gen_cache(self, batch, max_len, dtype="float32"):
        from ...ops import zeros
        return ConvStateCache(zeros(
            [batch, 1, self.taps - 1, self.hidden], dtype=dtype))

    def _gated(self, x, before, live):
        """``x [B, T, hidden]`` (normed), ``before [B, L-1, hidden]`` the
        values of ``u`` at the ``L - 1`` columns before the block (zeros
        where there are none), ``live [B, T]`` the block's columns that
        belong to the request.  Returns (the mixer's output, ``u`` over
        ``before`` and the block ``[B, L-1+T, hidden]``)."""
        T = x.shape[1]
        b, c, xx = jnp.split(_product(x, self.in_proj), 3, axis=-1)
        # ``u`` is rounded to the dtype the state keeps it in, so that a
        # token fed in a chunk and a token fed by a step see the same past
        u = jnp.where(live[..., None], b * xx, jnp.zeros((), x.dtype))
        full = jnp.concatenate([before.astype(x.dtype), u], axis=1)
        w = unwrap(self.conv).astype(jnp.float32)
        conv = sum(w[:, j] * full[:, j:j + T].astype(jnp.float32)
                   for j in range(self.taps))
        y = (c.astype(jnp.float32) * conv).astype(x.dtype)
        return _product(y, self.out_proj), full

    def forward_cached(self, x, cache, pos, start, write_rows=None):
        """Feed the block ``x [B, T, hidden]`` (normed) whose first column
        is ``pos``; ``start [B]`` is each row's first valid column.  The
        state's entries count iff their column is at or after ``start``;
        a row outside ``write_rows [B]`` keeps its state as it was."""
        T, n = x.shape[1], self.taps - 1
        state = unwrap(cache.state)[:, 0]                     # [B, n, hidden]
        cols = pos + jnp.arange(-n, T, dtype=jnp.int32)
        live = cols[None, :] >= start[:, None]                # [B, n + T]
        before = jnp.where(live[:, :n, None], state,
                           jnp.zeros((), state.dtype))
        with jax.named_scope("short_conv"):
            y, full = self._gated(x, before, live[:, n:])
            with jax.named_scope("cache_write"):
                new = full[:, T:].astype(state.dtype)
                if write_rows is not None:
                    new = jnp.where(write_rows[:, None, None], new, state)
        return y, ConvStateCache(Tensor(new[:, None]))

    def forward(self, x):
        """Cache-less over a whole sequence from position 0."""
        raw = unwrap(x)
        B, T, h = raw.shape
        y, _ = self._gated(raw, jnp.zeros((B, self.taps - 1, h), raw.dtype),
                           jnp.ones((B, T), bool))
        return y


class GroupedQueryAttention(nn.Layer):
    """``heads`` query heads over ``kv_heads`` cached heads; per-head
    RMSNorm on q and k unless ``qk_norm`` is false, rotary positions unless
    ``rope_base`` is None; no bias."""

    def __init__(self, hidden, heads, kv_heads, head_dim, rope_base,
                 epsilon=1e-5, weight_attr=None, dtype=None, qk_norm=True):
        super().__init__()
        if heads % kv_heads:
            raise ValueError(f"{heads} query heads over {kv_heads} cached")
        self.H, self.KV, self.d = int(heads), int(kv_heads), int(head_dim)
        self.rep = self.H // self.KV
        self.base = None if rope_base is None else float(rope_base)
        self.eps = float(epsilon)
        self.q_proj = _mat(self, (hidden, self.H * self.d), weight_attr, dtype)
        self.k_proj = _mat(self, (hidden, self.KV * self.d), weight_attr,
                           dtype)
        self.v_proj = _mat(self, (hidden, self.KV * self.d), weight_attr,
                           dtype)
        self.o_proj = _mat(self, (self.H * self.d, hidden), weight_attr,
                           dtype)
        self.q_norm = RMSNorm(self.d, epsilon, dtype=dtype) \
            if qk_norm else None
        self.k_norm = RMSNorm(self.d, epsilon, dtype=dtype) \
            if qk_norm else None

    def cache_spec(self, max_len):
        return {"kind": RingCache.kind,
                "heads_per_lane_row": kv_heads_per_lane_row(self.d),
                "columns": int(max_len), "wraps": False, "window": None,
                "select_top": None}

    def gen_cache(self, batch, max_len, dtype="float32"):
        """The GPT family's packed ring planes over the CACHED heads:
        ``[B, ceil(KV/g), max_len, g*d]``."""
        from ...ops import zeros
        g = kv_heads_per_lane_row(self.d)
        plane = [batch, -(-self.KV // g), max_len, g * self.d]
        return RingCache(zeros(plane, dtype=dtype), zeros(plane, dtype=dtype))

    def _heads(self, x, pos_ids):
        """q ``[B, H, T, d]``, k and v ``[B, KV, T, d]`` of the normed
        block ``x``, q and k normed per head and rotated where the layer
        does either."""
        B, T, _ = x.shape

        def split(w, n, norm=None, turn=False):
            y = _product(x, w).reshape(B, T, n, self.d)
            if norm is not None:
                y = unwrap(norm(y))
            if turn and self.base is not None:
                y = rotary(y, pos_ids, self.base)
            return jnp.swapaxes(y, 1, 2)
        return (split(self.q_proj, self.H, self.q_norm, True),
                split(self.k_proj, self.KV, self.k_norm, True),
                split(self.v_proj, self.KV))

    def _out(self, o):
        B, _, T, _ = o.shape
        return _product(jnp.swapaxes(o, 1, 2).reshape(B, T, self.H * self.d),
                        self.o_proj)

    def forward_cached(self, x, cache, pos, start, write_rows=None):
        """Append the block's K and V at column ``pos`` (a dead row's
        write lands in a dead column: the slot loop's own discipline, so
        ``write_rows`` is not needed here) and attend over the live span
        of the ring in column blocks (``span_attention``): a step's one
        query a row, or a chunk's block of them."""
        B, T, _ = x.shape
        kp, vp = unwrap(cache.k), unwrap(cache.v)
        C = kp.shape[2]
        cols = pos + jnp.arange(T, dtype=jnp.int32)
        pos_ids = jnp.maximum(cols[None, :] - start[:, None], 0)
        q, k, v = self._heads(x, pos_ids)
        g = kp.shape[3] // self.d
        at = pos % jnp.int32(C)
        kp = ring_block_write(kp, pack_heads(k, g), at)
        vp = ring_block_write(vp, pack_heads(v, g), at)
        with jax.named_scope("gqa_attention"):
            o = span_attention(q, kp, vp, start, pos, rep=self.rep)
        return self._out(o), RingCache(Tensor(kp), Tensor(vp))

    def forward(self, x):
        """Cache-less, causal, every token from position 0, one query head
        group at a time by plain products."""
        raw = unwrap(x)
        B, T, _ = raw.shape
        t = jnp.arange(T, dtype=jnp.int32)
        q, k, v = self._heads(raw, jnp.broadcast_to(t[None], (B, T)))
        k, v = (jnp.repeat(a, self.rep, axis=1) for a in (k, v))
        s = jnp.einsum("bhtd,bhsd->bhts", q, k,
                       preferred_element_type=jnp.float32) * self.d ** -0.5
        p = jax.nn.softmax(jnp.where(t[None, :] <= t[:, None], s, -1e30), -1)
        o = jnp.einsum("bhts,bhsd->bhtd", p.astype(raw.dtype), v,
                       preferred_element_type=jnp.float32).astype(raw.dtype)
        return self._out(o)


class BlockSparseAttention(GroupedQueryAttention):
    """Grouped-query attention that, for a query with more than
    ``sparse.dense_len`` tokens of context, reads ``sparse.top`` blocks of
    ``sparse.block`` columns (``choose_blocks``: scores over the pooled
    keys, chosen a cached head).  Its planes keep a cached head a lane row
    whatever the head's width, and a third plane the pooled keys.

    Both programs score every valid column of the span and MASK the
    blocks that were not chosen (``block_keep``).  A prefill chunk's 512
    queries between them choose nearly every block, so for it that is the
    read.  For a step it is the faster of two forms measured on the chip
    (PERF.md section 6, PR 44): XLA's gather of the 64 chosen 16 KB blocks
    a row a cached head ran at 35 GB/s, 2.9 ms a layer at 24 rows of 12k
    context, against 1.2 ms for the masked read of three times the bytes;
    a kernel that takes the block offsets as prefetched scalars is what
    would read the chosen blocks only (ROADMAP R12)."""

    def __init__(self, hidden, heads, kv_heads, head_dim, rope_base,
                 epsilon=1e-5, weight_attr=None, dtype=None, qk_norm=True,
                 *, sparse: BlockSparse, gate=False):
        super().__init__(hidden, heads, kv_heads, head_dim, rope_base,
                         epsilon, weight_attr, dtype, qk_norm)
        self.sparse = sparse
        self.gate_proj = _mat(self, (hidden, self.H * self.d), weight_attr,
                              dtype) if gate else None

    def _out(self, o, x):
        """``W_o`` over the heads' outputs ``o [B, H, T, d]``, gated by the
        layer's input ``x`` where the layer has a gate."""
        if self.gate_proj is None:
            return super()._out(o)
        B, _, T, _ = o.shape
        o = jnp.swapaxes(o, 1, 2).reshape(B, T, self.H * self.d)
        o = (o.astype(jnp.float32) * jax.nn.sigmoid(
            _product(x, self.gate_proj).astype(jnp.float32))).astype(o.dtype)
        return _product(o, self.o_proj)

    def cache_spec(self, max_len):
        sp = self.sparse
        return {"kind": PooledKeyCache.kind, "heads_per_lane_row": 1,
                "columns": int(max_len), "wraps": False, "window": None,
                "select_top": None,
                # the third plane is not a column a token, and what the
                # layer reads of its K/V is counted in blocks
                "pooled_stride": sp.stride, "select_blocks": sp._asdict()}

    def gen_cache(self, batch, max_len, dtype="float32"):
        from ...ops import zeros
        plane = [batch, self.KV, max_len, self.d]
        return PooledKeyCache(
            zeros(plane, dtype=dtype), zeros(plane, dtype=dtype),
            zeros([batch, self.KV, pooled_entries(max_len, self.sparse.stride),
                   self.d], dtype=dtype))

    def forward_cached(self, x, cache, pos, start, write_rows=None):
        """As the dense layer's, with the pooled keys written behind the
        block's K and the chosen blocks' mask over the span's read."""
        T, sp = x.shape[1], self.sparse
        kp, vp, pk = unwrap(cache.k), unwrap(cache.v), unwrap(cache.pooled)
        C = kp.shape[2]
        cols = pos + jnp.arange(T, dtype=jnp.int32)
        q, k, v = self._heads(x, jnp.maximum(cols[None, :] - start[:, None],
                                             0))
        kp = ring_block_write(kp, k, pos)
        vp = ring_block_write(vp, v, pos)
        with jax.named_scope("sparse_attention"):
            with jax.named_scope("pool"):
                pk = pool_keys_write(pk, kp, pos, T, start, sp)
            with jax.named_scope("select"):
                member = choose_blocks(q, pk, pos, start, sp, self.rep)
            with jax.named_scope("read"):
                o = span_attention(
                    q, kp, vp, start, pos, rep=self.rep,
                    keep=block_keep(member, start, sp.block, C))
        return self._out(o, x), PooledKeyCache(Tensor(kp), Tensor(vp),
                                               Tensor(pk))

    def forward(self, x):
        raise NotImplementedError(
            "a block-sparse layer is served through forward_cached")


class HybridDecoderLayer(nn.Layer):
    def __init__(self, cfg: HybridConvConfig, index: int, weight_attr=None):
        super().__init__()
        kind, ffn = cfg.layer_types[index], cfg.ffn_type(index)
        if kind == CONV:
            self.mixer = ShortConv(cfg.hidden_size, cfg.conv_taps,
                                   weight_attr, cfg.dtype)
        elif kind == ATTN:
            self.mixer = GroupedQueryAttention(
                cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                cfg.head_dim, cfg.rope_base, cfg.rms_eps, weight_attr,
                cfg.dtype, qk_norm=cfg.qk_norm)
        elif kind == SPARSE:
            self.mixer = BlockSparseAttention(
                cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                cfg.head_dim, cfg.rope_base, cfg.rms_eps, weight_attr,
                cfg.dtype, qk_norm=cfg.qk_norm, sparse=cfg.sparse,
                gate=cfg.sparse_gate)
        elif kind == LINEAR:
            depth = index / max(len(cfg.layer_types) - 1, 1) \
                if cfg.linear_decay_depth is None \
                else cfg.linear_decay_depth[index]
            self.mixer = LightningAttention(
                cfg.hidden_size, cfg.linear_heads, cfg.linear_head_dim,
                decay_slopes(cfg.linear_heads, depth), cfg.linear_rope_base,
                cfg.linear_chunk, cfg.rms_eps, weight_attr, cfg.dtype)
        elif kind == DELTA:
            self.mixer = GatedDeltaNet(
                cfg.hidden_size, cfg.delta_key_heads, cfg.delta_value_heads,
                cfg.delta_key_dim, cfg.delta_value_dim, cfg.delta_taps,
                cfg.delta_chunk, cfg.rms_eps, cfg.delta_gate_scale,
                weight_attr, cfg.dtype)
        elif kind == LATENT:
            self.mixer = LatentAttention(
                cfg.hidden_size, cfg.num_heads, cfg.nope_dim,
                cfg.latent_rope_dim, cfg.v_dim, cfg.q_rank, cfg.kv_rank,
                cfg.latent_rope_base, cache_block=cfg.latent_block,
                attn_block=cfg.attn_block, epsilon=cfg.rms_eps,
                rescale=False, gate=cfg.latent_gate,
                rope_scaling=cfg.rope_scaling, norm_layer=cfg.norm,
                weight_attr=weight_attr, dtype=cfg.dtype)
        elif kind == SSM:
            self.mixer = Mamba2Mixer(
                cfg.hidden_size, cfg.ssm_heads, cfg.ssm_head_dim,
                cfg.ssm_state, cfg.ssm_groups, cfg.ssm_taps, cfg.ssm_chunk,
                cfg.rms_eps, weight_attr, cfg.dtype)
        elif kind == NONE and ffn != NONE:
            self.mixer = None
        else:
            raise ValueError(f"layer_types[{index}] = {kind!r} with the "
                             f"FFN {ffn!r}")
        if cfg.block_norms not in ("pre", "pre_post"):
            raise ValueError(f"block_norms {cfg.block_norms!r}")
        post = cfg.block_norms == "pre_post"
        if self.mixer is not None:
            self.operator_norm = cfg.norm(cfg.hidden_size)
            self.operator_post_norm = cfg.norm(cfg.hidden_size) \
                if post else None
        if ffn == DENSE:
            self.ffn = SwiGLU(cfg.hidden_size, cfg.intermediate_size,
                              weight_attr, cfg.dtype, cfg.ffn_limit)
        elif ffn == MOE:
            self.ffn = DroplessMoE(
                cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
                cfg.experts_per_token, held=cfg.held_experts,
                shared=cfg.shared_experts, scaling=cfg.routed_scaling,
                norm_topk=cfg.norm_topk, norm_eps=cfg.routing_norm_eps,
                activation=cfg.expert_activation, weight_attr=weight_attr,
                dtype=cfg.dtype, limit=cfg.ffn_limit)
        elif ffn == NONE:
            self.ffn = None
        else:
            raise ValueError(f"ffn_types[{index}] = {ffn!r}")
        if self.ffn is not None:
            self.ffn_norm = cfg.norm(cfg.hidden_size)
            self.ffn_post_norm = cfg.norm(cfg.hidden_size) if post else None
        self.residual_scale = float(cfg.residual_scale)

    # the residual stream is float32 whatever the weights are (as in the
    # latent family: text/models/latent_moe.py says why)
    @staticmethod
    def _operand(norm, x):
        """``norm(x)`` rounded to the weights' dtype (the norm's own gain
        is one of them), as a product's operand."""
        return unwrap(norm(x)).astype(unwrap(norm.weight).dtype)

    def _add(self, x, branch, post=None):
        """``x + r branch`` in float32 (``r`` 1 adds as it stands), the
        branch normed first where the block norms its halves after
        too."""
        if post is not None:
            branch = post(unwrap(branch))
        branch = unwrap(branch).astype(jnp.float32)
        if self.residual_scale != 1.0:
            branch = branch * self.residual_scale
        return x + branch

    # Each half of the layer, its norm and its residual add included, lies
    # under the name a trace is read by (docs/METRICS.md).
    def _ffn(self, h, live):
        if self.ffn is None:
            return h
        moe = isinstance(self.ffn, DroplessMoE)
        with jax.named_scope("experts" if moe else "mlp"):
            u = self._operand(self.ffn_norm, h)
            y = self.ffn(u, live) if moe else self.ffn(u)
            return self._add(h, y, self.ffn_post_norm)

    def _mixer_scope(self):
        return jax.named_scope(
            {ShortConv: "short_conv", Mamba2Mixer: "state_space"}.get(
                type(self.mixer), "attention"))

    def forward_cached(self, x, cache, pos, start, write_rows, live):
        """``cache`` is None for a layer without a mixer, and comes back
        so."""
        if self.mixer is not None:
            with self._mixer_scope():
                a, cache = self.mixer.forward_cached(
                    self._operand(self.operator_norm, x), cache, pos, start,
                    write_rows)
                x = self._add(x, a, self.operator_post_norm)
        return self._ffn(x, live), cache

    def forward(self, x):
        if self.mixer is not None:
            with self._mixer_scope():
                x = self._add(
                    x, self.mixer(self._operand(self.operator_norm, x)),
                    self.operator_post_norm)
        return self._ffn(x, None)


# what ``decode_counts`` returns, in order (the latent family's names: the
# slot loop sums the first two and keeps the largest of the third)
DECODE_COUNT_NAMES = ("moe_assignments", "moe_assignments_held",
                      "moe_expert_tokens_max")


class HybridConvDecoder(nn.Layer):
    """``weight_attr`` (a ``ParamAttr``) reaches every matrix: a server
    that installs its own weights builds with a constant initializer."""

    # a slot loop's step hands over its live rows: a row that is not fed
    # keeps its state (a convolution's inputs, a state-space layer's sum)
    cached_forward_takes_rows = True
    decode_count_names = DECODE_COUNT_NAMES

    def __init__(self, cfg: HybridConvConfig = None, weight_attr=None,
                 **kwargs):
        super().__init__()
        cfg = cfg or HybridConvConfig(**kwargs)
        self.config = cfg
        self.embed = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                  weight_attr=weight_attr)
        if str(self.embed.weight.dtype) != cfg.dtype:
            # nn.Embedding builds in the default dtype
            w = self.embed.weight
            w._value = w._value.astype(cfg.dtype)
        self.layers = nn.LayerList([
            HybridDecoderLayer(cfg, i, weight_attr)
            for i in range(len(cfg.layer_types))])
        self.norm = cfg.norm(cfg.hidden_size)
        # an untied head lies as the table does, ``[vocab, hidden]``
        self.lm_head = None if cfg.tie_embeddings else _mat(
            self, (cfg.vocab_size, cfg.hidden_size), weight_attr, cfg.dtype)
        self._counts = None

    def _logits(self, h):
        table = unwrap(self.embed.weight if self.lm_head is None
                       else self.lm_head)
        with jax.named_scope("head"):
            h = unwrap(self.norm(h))
            if self.config.logit_divisor != 1.0:
                h = h * (1.0 / self.config.logit_divisor)
            return jnp.einsum("bth,vh->btv", h.astype(table.dtype), table,
                              preferred_element_type=jnp.float32)

    def _embedded(self, ids):
        """The stream's first state, float32 (inside scope ``embed``)."""
        h = unwrap(self.embed(ids)).astype(jnp.float32)
        if self.config.embed_scale != 1.0:
            h = h * self.config.embed_scale
        return h

    def forward(self, input_ids):
        with jax.named_scope("embed"):
            h = self._embedded(input_ids)
        for layer in self.layers:
            h = layer(h)
        return Tensor(self._logits(h))

    # -- incremental decoding --------------------------------------------------
    def _mixers(self):
        return [l.mixer for l in self.layers if l.mixer is not None]

    def cache_spec(self, max_len):
        """Per layer that has a mixer, what it keeps: ``kv`` ring planes
        or a ``latent`` plane as long as the session, or planes without
        columns (``conv_state``, ``ssm_state``)."""
        return [m.cache_spec(max_len) for m in self._mixers()]

    def latent_form(self, T):
        """The form the latent layers' cached attention is traced in for
        a block of ``T`` tokens (``LatentAttention.cached_form``); None
        for a model without such a layer."""
        forms = {m.cached_form(int(T)) for m in self._mixers()
                 if isinstance(m, LatentAttention)}
        if not forms:
            return None
        return forms.pop() if len(forms) == 1 else "mixed"

    def init_cache(self, batch, max_len, dtype=None):
        """One cache a layer that has a mixer, in ``dtype`` (the weights'
        by default) but for what a mixer keeps in a dtype of its own (a
        state-space layer's float32 state)."""
        if dtype is None:
            dtype = str(self.embed.weight.dtype)
        return [m.gen_cache(batch, max_len, dtype) for m in self._mixers()]

    def forward_cached(self, input_ids, cache, cache_position,
                       start_positions, write_rows=None):
        """Append ``input_ids [B, T]`` at column ``cache_position`` (the
        LEFT-padded prompt or a chunk of it, or one token a row) and
        return (logits ``[B, T, V]`` float32, the updated caches).
        ``write_rows [B]`` marks the rows a slot loop's step feeds."""
        ids = unwrap(input_ids)
        T = ids.shape[1]
        pos = unwrap(cache_position)
        pos = jnp.int32(pos) if isinstance(pos, int) \
            else jnp.asarray(pos, jnp.int32)
        start = jnp.asarray(unwrap(start_positions), jnp.int32)
        rows = None if write_rows is None else unwrap(write_rows)
        with jax.named_scope("embed"):
            h = self._embedded(Tensor(ids))
            live = (pos + jnp.arange(T, dtype=jnp.int32))[None, :] \
                >= start[:, None]
            if rows is not None:
                live = live & rows[:, None]
        zero = jnp.int32(0)
        counts, new, kept = (zero, zero, zero), [], iter(cache)
        for layer in self.layers:
            h, c = layer.forward_cached(
                h, None if layer.mixer is None else next(kept), pos, start,
                rows, live)
            if c is not None:
                new.append(c)
            if isinstance(layer.ffn, DroplessMoE):
                a, b, m = layer.ffn.last_counts
                counts = (counts[0] + a, counts[1] + b,
                          jnp.maximum(counts[2], m))
        self._counts = counts
        return Tensor(self._logits(h)), new

    def decode_counts(self):
        """int32 ``[3]`` of the last ``forward_cached`` (same trace):
        ``DECODE_COUNT_NAMES``, over the live tokens of all MoE layers."""
        return jnp.stack(self._counts) if self._counts is not None else None

    def generate(self, input_ids, lengths=None, max_new_tokens=32, **kw):
        from ..generation import generate as _generate
        return _generate(self, input_ids, lengths=lengths,
                         max_new_tokens=max_new_tokens, **kw)
