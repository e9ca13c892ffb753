"""Lightning linear attention: per head a float32 MATRIX state fed by outer
products, with a fixed decay a head.

``q, k, v = u W_q, u W_k, u W_v`` in ``heads`` heads of ``head_dim``;
RMSNorm over each head's features of q and of k (one learned vector each);
rotary positions on q and k (the token's position in its request, the
whole head); per head, in float32::

    S_t = exp(-s_h) S_{t-1} + k_t^T v_t         [head_dim, head_dim]
    o_t = q_t S_t / sqrt(head_dim)

``S = 0`` before the request's first token; ``out = (RMSNorm_head(o) *
sigmoid(u W_gate)) W_o`` (the norm over each head's features, one learned
``heads x head_dim`` vector; the gate before ``W_o``).  The decays ``s_h``
are constants of the layer (``slopes``), not parameters.

It is the recurrence of :mod:`~paddle_tpu.nn.layer.mamba2` with a step size
that is 1 for a live token and 0 for any other, ``B := k``, ``C := q``, ``x
:= v``, as many groups as heads (a full-rank key and query a head) and ``A
= -s_h``: the chunked scan of a block (scope ``scan``) and the one-token
update of a step (scope ``update``) are that module's ``state_mix``, not
copies of it.  What differs is around the recurrence: no convolution, no
learned step, norms and positions before the state, a norm and a sigmoid
gate after it.

What the layer keeps of a row (kind ``ssm_state``, ``columns: 0``): the
state ``[B, heads, head_dim, head_dim]`` (value features first, as
``mamba2``'s ``[.., P, N]``) in float32 whatever dtype the loop asks for.
A summed state: it counts iff ``pos > start``, a token before ``start``
passes it through, a row outside ``write_rows`` keeps it (``mamba2``'s
liveness rule, which the slot loop's ``ssm_rows_updated`` /
``chunk_ssm_tokens`` count by).

Inference only: nothing here is taped.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
import numpy as np

from ...framework.tensor import Tensor, unwrap
from .. import initializer as I
from ..functional.attention import rotary
from .latent_attention import RMSNorm
from .layers import Layer
from .mamba2 import _product, state_mix

__all__ = ["LightningAttention", "LinearStateCache", "decay_slopes"]

LinearStateCache = collections.namedtuple("LinearStateCache", ["state"])
LinearStateCache.kind = "ssm_state"
LinearStateCache.wraps = False

_F32 = jnp.float32


def decay_slopes(heads: int, depth: float):
    """The family's ``build_slope_tensor`` scaled by the layer: ``2 ** (-8
    (h + 1) / heads) x (1 - depth + 1e-5)``, float32 ``[heads]`` (``heads``
    a power of two), ``depth`` the layer's ``l / (L - 1)`` in its model."""
    h = np.arange(1, heads + 1, dtype=np.float64)
    return (2.0 ** (-8.0 * h / heads)
            * (1.0 - depth + 1e-5)).astype(np.float32)


class LightningAttention(Layer):
    def __init__(self, hidden, heads, head_dim, slopes, rope_base=10000.0,
                 chunk=128, epsilon=1e-6, weight_attr=None, dtype=None):
        super().__init__()
        self.H, self.d = int(heads), int(head_dim)
        self.inner = self.H * self.d
        self.base = None if rope_base is None else float(rope_base)
        self.chunk, self.eps = int(chunk), float(epsilon)
        # log of the decay rates, as ``state_mix`` takes them
        self.log_slopes = np.log(np.asarray(slopes, np.float32))
        if self.log_slopes.shape != (self.H,):
            raise ValueError(f"{self.H} heads, slopes {self.log_slopes.shape}")

        def mat(*shape):
            return self.create_parameter(
                list(shape), attr=weight_attr, dtype=dtype,
                default_initializer=I.Normal(0.0, 0.02))
        self.q_proj, self.k_proj, self.v_proj, self.gate_proj = (
            mat(hidden, self.inner) for _ in range(4))
        self.o_proj = mat(self.inner, hidden)
        self.q_norm = RMSNorm(self.d, epsilon, dtype=dtype)
        self.k_norm = RMSNorm(self.d, epsilon, dtype=dtype)
        self.norm = self.create_parameter(
            [self.inner], attr=weight_attr, dtype=dtype,
            default_initializer=I.Constant(1.0))

    def cache_spec(self, max_len):
        return {"kind": LinearStateCache.kind, "heads_per_lane_row": 1,
                "columns": 0, "wraps": False, "window": None,
                "select_top": None}

    def gen_cache(self, batch, max_len, dtype="float32"):
        """The state is float32 whatever ``dtype`` says."""
        from ...ops import zeros
        return LinearStateCache(
            zeros([batch, self.H, self.d, self.d], dtype="float32"))

    def _mix(self, u, h0, live, pos_ids):
        """The whole mixer over the block ``u [B, T, hidden]`` (normed):
        (output ``[B, T, hidden]``, the state after the block)."""
        Bt, T, _ = u.shape

        def heads(w, norm=None):
            y = _product(u, w).reshape(Bt, T, self.H, self.d)
            if norm is None:
                return y
            y = unwrap(norm(y))
            return y if self.base is None else rotary(y, pos_ids, self.base)
        q, k, v = (heads(self.q_proj, self.q_norm),
                   heads(self.k_proj, self.k_norm), heads(self.v_proj))
        dt = jnp.broadcast_to(live[..., None].astype(_F32), (Bt, T, self.H))
        with jax.named_scope("linear_attention"):
            y, h = state_mix(v, dt, k, q, jnp.asarray(self.log_slopes), h0,
                             self.chunk)
        y = y * self.d ** -0.5
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + self.eps)
        gate = jax.nn.sigmoid(_product(u, self.gate_proj).astype(_F32))
        y = (y.reshape(Bt, T, self.inner) * unwrap(self.norm).astype(_F32)
             * gate).astype(u.dtype)
        return _product(y, self.o_proj), h

    def forward_cached(self, x, cache, pos, start, write_rows=None):
        """Feed the block ``x [B, T, hidden]`` (normed) whose first column
        is ``pos``; ``start [B]`` is each row's first valid column (module
        docstring: the summed state's liveness rule)."""
        T = x.shape[1]
        state = unwrap(cache.state)
        cols = pos + jnp.arange(T, dtype=jnp.int32)
        live = cols[None, :] >= start[:, None]                   # [B, T]
        h0 = jnp.where((pos > start)[:, None, None, None], state, 0.0)
        y, h = self._mix(x, h0, live,
                         jnp.maximum(cols[None, :] - start[:, None], 0))
        if write_rows is not None:
            h = jnp.where(write_rows[:, None, None, None], h, state)
        return y, LinearStateCache(Tensor(h))

    def forward(self, x):
        """Cache-less over a whole sequence from position 0 (the chunked
        scan from a zero state)."""
        raw = unwrap(x)
        B, T, _ = raw.shape
        y, _ = self._mix(
            raw, jnp.zeros((B, self.H, self.d, self.d), _F32),
            jnp.ones((B, T), bool),
            jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T)))
        return y
