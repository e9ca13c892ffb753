"""The gated delta rule: per value head a float32 MATRIX state that every
token first decays, then READS through its own key, and corrects by what it
found there.

``[q | k | v] = u W_qkv`` (``key_heads`` heads of ``key_dim`` for q and for
k, ``value_heads`` of ``value_dim`` for v), ``z = u W_z``, ``b = u W_b``,
``a = u W_a`` (one number a value head each); a depthwise causal
convolution of ``taps`` taps over the channels of ``[q | k | v]`` (zeros
before the request's first token), then SiLU; ``q_h <- q_h / |q_h| x
key_dim^-1/2``, ``k_h <- k_h / |k_h|`` (``|x| = sqrt(sum x^2 + 1e-6)``);
value head ``j`` reads key head ``j // (value_heads / key_heads)``;
``beta = sigmoid(b)``, ``alpha = exp(-exp(A_log) softplus(a + dt_bias))``;
per value head, in float32::

    S_t = alpha_t S_{t-1} + beta_t (v_t - alpha_t S_{t-1} k_t) k_t^T
    o_t = S_t q_t                                   [value_dim, key_dim]

``S = 0`` before the request's first token; ``out = (RMSNorm_head(o) * (1 +
w_o) * gate_scale sigmoid(z)) W_out`` (the norm over each head's features,
one learned ``value_dim`` vector shared by the heads, zero-centred).

Against :mod:`~paddle_tpu.nn.layer.mamba2`'s recurrence (and the lightning
layers', which are that one): the decay is the TOKEN's, and what a token
writes is not its value but its value less what the decayed state already
gives for its key, so the write depends on the state.  One recurrence in two
forms:

  * ``T == 1``, the **one-token update** (scope ``update``): the equations
    as they stand, the state read once for ``S k`` and ``S q`` together
    (``S_t q = alpha S_{t-1} q + (k . q) beta (v - alpha S_{t-1} k)``) and
    once more to be written;
  * a block, the **chunked scan** (scope ``scan``).  Inside a scan chunk of
    ``chunk`` tokens, with ``g_t`` the log decay summed up to token ``t``,
    the pseudo-values ``u_t = beta_t (v_t - alpha_t S_{t-1} k_t)`` obey
    ``(I + A) U = diag(beta) (V - diag(e^g) K S_0^T)``, ``A = tril(diag(beta)
    (K K^T * decay), -1)`` (``decay``: ``mamba2.decay_between``).  The
    triangular system is solved once a chunk for both right-hand sides
    (scope ``solve``: ``T = (I + A)^-1``, :func:`unit_lower_inverse`, then
    ``U_0 = T diag(beta) V`` and ``W = T diag(beta e^g) K``), and the
    chunks then pass the state on one after another (scope ``carry``):
    ``U = U_0 - W S_0^T``, ``o_t = e^{g_t} S_0 q_t + sum_{s <= t}
    decay_ts (k_s . q_t) u_s``, ``S_L = e^{g_L} S_0 + sum_s e^{g_L - g_s}
    u_s k_s^T`` (``mamba2.state_scan``'s own carry, with a sum that
    depends on the state carried in).  The solve and every product that
    reads the carried state are float32 at precision "highest"; the other
    products take operands in the weights' dtype and accumulate in
    float32.

What the layer keeps of a row (kind ``ssm_state``, ``columns: 0``, as a
state-space layer's): the convolution's last ``taps - 1`` inputs ``[B, 1,
taps-1, channels]`` in the weights' dtype, positional (an entry counts iff
its column is at or after the row's ``start``), and the state ``[B,
value_heads, value_dim, key_dim]`` in **float32 whatever dtype the loop asks
for**, summed: it counts iff ``pos > start``, a token before ``start``
passes it through (``alpha = 1``, ``beta = 0``), a row outside
``write_rows`` keeps both as they were.

Inference only: nothing here is taped.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...framework.tensor import Tensor, unwrap
from .. import initializer as I
from .layers import Layer
from .mamba2 import SsmStateCache, _product, conv_silu, decay_between

__all__ = ["GatedDeltaNet", "delta_update", "delta_scan", "delta_mix",
           "unit_lower_inverse"]

_F32 = jnp.float32
_EXACT = jax.lax.Precision.HIGHEST
L2_EPS = 1e-6


def _exact(spec, a, b):
    return jnp.einsum(spec, a, b, precision=_EXACT,
                      preferred_element_type=_F32)


def unit_lower_inverse(a):
    """``(I + A)^-1`` for ``a [..., L, L]`` strictly lower triangular
    (``L`` a power of two), float32 at precision "highest", by halves: with
    ``I + A = [[X, 0], [A21, Y]]`` the inverse is ``[[X^-1, 0], [-Y^-1 A21
    X^-1, Y^-1]]``, so the inverses of the diagonal blocks of 1, 2, 4, ...
    are joined two and two: ``T <- T - T A_pairs T`` with ``T`` the
    block-diagonal inverse so far and ``A_pairs`` the blocks of ``A`` that
    tie two neighbours.  ``log2 L`` rounds of two products of whole ``[L,
    L]`` matrices (the blocks are never cut out: an operand of 2 x 2
    blocks would lie a number a tile on the chip).  Exact, on the matrix
    unit, and like a substitution it stays bounded where the keys repeat
    (the product ``(I - A)(I + A^2)(I + A^4)...`` has the same count of
    products but its powers of ``A`` grow like binomials there, and float32
    loses the sum: PERF.md section 6, PR 48)."""
    L = a.shape[-1]
    r = jnp.arange(L)
    t = jnp.broadcast_to(jnp.eye(L, dtype=_F32), a.shape)
    b = 1
    while b < L:
        # rows of an odd block of ``b``, columns of the even one before it
        pairs = (r[:, None] // (2 * b) == r[None, :] // (2 * b)) \
            & (r[:, None] // b % 2 == 1) & (r[None, :] // b % 2 == 0)
        t = t - _exact("...ab,...bc->...ac",
                       _exact("...ab,...bc->...ac", t,
                              jnp.where(pairs, a.astype(_F32), 0.0)), t)
        b *= 2
    return t


def delta_update(q, k, v, a, beta, h0):
    """The one-token update: ``q``, ``k [B, G, N]`` (value head ``h`` reads
    key head ``h // (H / G)``), ``v [B, H, P]``, ``a [B, H]`` the log of
    the token's decay and ``beta [B, H]``, float32, ``h0 [B, H, P, N]``
    float32 -> (``o [B, H, P]`` float32, the new state)."""
    rep = v.shape[1] // k.shape[1]
    kh, qh = (jnp.repeat(t.astype(_F32), rep, axis=1)[:, :, None, :]
              for t in (k, q))
    alpha = jnp.exp(a)[..., None]                           # [B, H, 1]
    # the old state read once, through the key and through the query
    sk = jnp.sum(h0 * kh, -1) * alpha                       # alpha S k
    sq = jnp.sum(h0 * qh, -1) * alpha
    u = beta[..., None] * (v.astype(_F32) - sk)             # [B, H, P]
    h = h0 * alpha[..., None] + u[..., None] * kh
    return sq + u * jnp.sum(kh * qh, -1), h


def delta_scan(q, k, v, a, beta, h0, chunk, inverse=unit_lower_inverse):
    """The same recurrence over ``T = n x chunk`` tokens as a chunked scan
    (module docstring): ``q``, ``k [B, T, G, N]``, ``v [B, T, H, P]``, ``a``
    and ``beta [B, T, H]`` float32 (a token that is not live has ``a = 0``
    and ``beta = 0``: it passes the state through), ``h0 [B, H, P, N]``
    float32 -> (``o [B, T, H, P]`` float32, the state after the last
    token).  ``inverse`` solves the triangular system."""
    Bt, T, H, P = v.shape
    G, N, L = k.shape[2], k.shape[3], chunk
    n, r, dt_op = T // L, H // G, v.dtype
    q, k, v, a, beta = (t.reshape((Bt, n, L) + t.shape[2:])
                        for t in (q, k, v, a, beta))
    cs = jnp.cumsum(a, axis=2)                  # log decay up to t, incl.
    total = cs[:, :, -1]                                    # [B, n, H]
    decay = decay_between(jnp.moveaxis(cs, 3, 2))       # [B, n, H, t, s]
    heads = lambda t: jnp.repeat(t, r, axis=2)                  # noqa: E731
    kh = jnp.repeat(k, r, axis=3)                       # [B, n, L, H, N]
    with jax.named_scope("solve"):
        kk = jnp.einsum("bnlgk,bnsgk->bngls", k, k,
                        preferred_element_type=_F32)
        bt = jnp.moveaxis(beta, 3, 2)                       # [B, n, H, L]
        inv = inverse(jnp.tril(bt[..., None] * decay * heads(kk), -1))
        u0 = _exact("bnhts,bnshp->bnthp", inv,
                    beta[..., None] * v.astype(_F32))
        w = _exact("bnhts,bnshk->bnthk", inv,
                   (beta * jnp.exp(cs))[..., None] * kh.astype(_F32))
    with jax.named_scope("carry"):
        # o_t += sum_{s <= t} decay_ts (q_t . k_s) u_s
        qk = jnp.einsum("bnlgk,bnsgk->bngls", q, k,
                        preferred_element_type=_F32)
        m = (heads(qk) * decay).astype(dt_op)               # [B,n,H,t,s]
        # a token's key as the chunk's end sees it, its query as it sees
        # the state that entered the chunk
        k_end = (kh.astype(_F32)
                 * jnp.exp(total[:, :, None, :] - cs)[..., None]).astype(dt_op)
        q_in = jnp.repeat(q, r, axis=3).astype(_F32) * jnp.exp(cs)[..., None]
        keep = jnp.exp(total)                               # [B, n, H]
        h, out = h0, []
        for i in range(n):
            u = u0[:, i] - _exact("blhk,bhpk->blhp", w[:, i], h)
            uo = u.astype(dt_op)
            out.append(_exact("blhk,bhpk->blhp", q_in[:, i], h)
                       + jnp.einsum("bhts,bshp->bthp", m[:, i], uo,
                                    preferred_element_type=_F32))
            # (the chunks' states pass on as a state-space layer's do)
            h = h * keep[:, i, :, None, None] + jnp.einsum(
                "bshp,bshk->bhpk", uo, k_end[:, i],
                preferred_element_type=_F32)
    return jnp.stack(out, 1).reshape(Bt, T, H, P), h


def delta_mix(q, k, v, a, beta, h0, chunk, inverse=unit_lower_inverse,
              kept=None):
    """The recurrence over the block in the form its width asks for: the
    one-token update (scope ``update``) for ``T == 1``, else the chunked
    scan (scope ``scan``) over the block padded to whole scan chunks (a
    padded token passes the state through).  Operands as
    :func:`delta_scan`'s; ``kept = (rows [B], state)`` hands back
    ``state``'s row for every row outside ``rows`` (a step's dead rows),
    INSIDE the scope: the select is the root of the fusion that writes the
    new planes, and a trace reads a fusion by its root's scope.  Returns
    (``o [B, T, H, P]`` float32, the state after the block)."""
    def held(h):
        if kept is None:
            return h
        rows, state = kept
        return jnp.where(rows[:, None, None, None], h, state)
    T = v.shape[1]
    if T == 1:
        with jax.named_scope("update"):
            o, h = delta_update(q[:, 0], k[:, 0], v[:, 0], a[:, 0],
                                beta[:, 0], h0)
            return o[:, None], held(h)
    pad = -T % chunk
    with jax.named_scope("scan"):
        q, k, v, a, beta = (jnp.pad(
            t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, a, beta))
        o, h = delta_scan(q, k, v, a, beta, h0, chunk, inverse)
        return o[:, :T], held(h)


class GatedDeltaNet(Layer):
    def __init__(self, hidden, key_heads, value_heads, key_dim, value_dim,
                 taps=4, chunk=64, epsilon=1e-6, gate_scale=2.0,
                 weight_attr=None, dtype=None):
        super().__init__()
        if value_heads % key_heads:
            raise ValueError(f"{value_heads} value heads over {key_heads} "
                             f"key heads")
        self.G, self.H = int(key_heads), int(value_heads)
        self.N, self.P = int(key_dim), int(value_dim)
        self.taps, self.chunk = int(taps), int(chunk)
        self.eps, self.gate_scale = float(epsilon), float(gate_scale)
        self.keys, self.inner = self.G * self.N, self.H * self.P
        self.conv_dim = 2 * self.keys + self.inner

        def mat(*shape):
            return self.create_parameter(
                list(shape), attr=weight_attr, dtype=dtype,
                default_initializer=I.Normal(0.0, 0.02))

        def per_head():
            # the recurrence's own scalars stay float32 whatever the
            # matrices are (a decay is an exp of them)
            return self.create_parameter(
                [self.H], dtype="float32", is_bias=True,
                default_initializer=I.Constant(0.0))
        self.qkv_proj = mat(hidden, self.conv_dim)
        self.z_proj = mat(hidden, self.inner)
        self.b_proj, self.a_proj = mat(hidden, self.H), mat(hidden, self.H)
        # tap j weighs the input taps - 1 - j tokens back
        self.conv = mat(self.conv_dim, self.taps)
        self.dt_bias, self.A_log = per_head(), per_head()
        # zero-centred: the gain is 1 + norm
        self.norm = self.create_parameter(
            [self.P], attr=weight_attr, dtype=dtype,
            default_initializer=I.Constant(0.0))
        self.out_proj = mat(self.inner, hidden)

    # -- what the layer keeps ------------------------------------------------
    def cache_spec(self, max_len):
        return {"kind": SsmStateCache.kind, "heads_per_lane_row": 1,
                "columns": 0, "wraps": False, "window": None,
                "select_top": None}

    def gen_cache(self, batch, max_len, dtype="float32"):
        """``dtype`` is the convolution inputs'; the state is float32."""
        from ...ops import zeros
        return SsmStateCache(
            zeros([batch, 1, self.taps - 1, self.conv_dim], dtype=dtype),
            zeros([batch, self.H, self.P, self.N], dtype="float32"))

    # -- the mixer ------------------------------------------------------------
    def _heads(self, qkv):
        """``q``, ``k [B, T, G, N]`` (unit length, q times ``N^-1/2``) and
        ``v [B, T, H, P]`` of the convolved ``[q | k | v]``, in its
        dtype."""
        lead = qkv.shape[:-1]
        q, k, v = jnp.split(qkv, [self.keys, 2 * self.keys], axis=-1)

        def unit(x, scale):
            x = x.reshape(lead + (self.G, self.N)).astype(_F32)
            x = x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)
            return (x * scale).astype(qkv.dtype)
        return (unit(q, self.N ** -0.5), unit(k, 1.0),
                v.reshape(lead + (self.H, self.P)))

    def _mix(self, u, before, h0, live, kept=None):
        """The whole mixer over the block ``u [B, T, hidden]`` (normed):
        (output ``[B, T, hidden]``, the convolution's inputs over
        ``before`` and the block, the state after the block, ``kept``'s
        rows as they were: :func:`delta_mix`)."""
        Bt, T, _ = u.shape
        with jax.named_scope("conv"):
            qkv, full = conv_silu(_product(u, self.qkv_proj), before, live,
                                  unwrap(self.conv))
        q, k, v = self._heads(qkv)
        beta = jax.nn.sigmoid(_product(u, self.b_proj).astype(_F32))
        rate = jnp.exp(unwrap(self.A_log).astype(_F32)) * jax.nn.softplus(
            _product(u, self.a_proj).astype(_F32)
            + unwrap(self.dt_bias).astype(_F32))
        # a token before the row's first passes the state through
        a = jnp.where(live[..., None], -rate, 0.0)
        beta = jnp.where(live[..., None], beta, 0.0)
        with jax.named_scope("linear_attention"):
            o, h = delta_mix(q, k, v, a, beta, h0, self.chunk, kept=kept)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + self.eps)
        o = o * (1.0 + unwrap(self.norm).astype(_F32))
        gate = self.gate_scale * jax.nn.sigmoid(
            _product(u, self.z_proj).astype(_F32))
        y = (o.reshape(Bt, T, self.inner) * gate).astype(u.dtype)
        return _product(y, self.out_proj), full, h

    def forward_cached(self, x, cache, pos, start, write_rows=None):
        """Feed the block ``x [B, T, hidden]`` (normed) whose first column
        is ``pos``; ``start [B]`` is each row's first valid column, and a
        row outside ``write_rows [B]`` keeps what it has (module
        docstring: the two liveness rules)."""
        T, n = x.shape[1], self.taps - 1
        conv, state = unwrap(cache.conv)[:, 0], unwrap(cache.state)
        cols = pos + jnp.arange(-n, T, dtype=jnp.int32)
        live = cols[None, :] >= start[:, None]                 # [B, n + T]
        before = jnp.where(live[:, :n, None], conv,
                           jnp.zeros((), conv.dtype))
        h0 = jnp.where((pos > start)[:, None, None, None], state, 0.0)
        y, full, h = self._mix(
            x, before, h0, live[:, n:],
            None if write_rows is None else (write_rows, state))
        new = full[:, T:].astype(conv.dtype)
        if write_rows is not None:
            new = jnp.where(write_rows[:, None, None], new, conv)
        return y, SsmStateCache(Tensor(new[:, None]), Tensor(h))

    def forward(self, x):
        """Cache-less over a whole sequence from position 0 (the chunked
        scan from a zero state)."""
        raw = unwrap(x)
        B, T, _ = raw.shape
        y, _, _ = self._mix(
            raw, jnp.zeros((B, self.taps - 1, self.conv_dim), raw.dtype),
            jnp.zeros((B, self.H, self.P, self.N), _F32),
            jnp.ones((B, T), bool))
        return y
