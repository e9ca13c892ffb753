"""The Mamba-2 mixer: a selective state-space layer whose state SUMS every
earlier token of its request.

``[z | xBC | dt] = u W_in``; ``xBC <- silu(conv(xBC) + b)`` (causal,
depthwise, ``taps`` wide, zeros before the request's first token); ``[x |
B | C] = xBC`` with ``x`` as ``heads`` heads of ``head_dim``, ``B`` and
``C`` as ``groups`` groups of ``state`` (head ``h`` reads group ``h //
(heads / groups)``); per head ``D = softplus(dt + dt_bias)``, ``A =
-exp(A_log)``::

    h_t = exp(D_t A) h_{t-1} + D_t x_t (x) B_t        [head_dim, state]
    y_t = h_t C_t + D_skip x_t

``y <- GroupRMSNorm(y * silu(z)) * w`` over groups of ``heads * head_dim /
groups`` features (the gate BEFORE the norm), ``out = y W_out``.

One recurrence in two forms.  A block of ``T > 1`` tokens runs the
**chunked scan**: inside each chunk of ``chunk`` tokens the recurrence is
written as products (the scores ``C_t . B_s`` weighted by the decay from
``s`` to ``t``, the chunk's own contribution to the state, the carried
state read through ``C``), and only the ``T / chunk`` chunk states are
passed on one after another.  ``T == 1`` is the **one-token update** of
the equations as they stand.  Decay sums and ``exp`` are float32; the
products take operands in the weights' dtype and accumulate in float32,
but the product that reads the carried state takes it as the float32 it is
kept in.

What the layer keeps of a row (kind ``ssm_state``, ``cache_spec``
``columns: 0``): the convolution's last ``taps - 1`` ``xBC`` inputs ``[B,
1, taps-1, conv_dim]`` in the weights' dtype, and the state ``[B, heads,
head_dim, state]`` in **float32 whatever dtype the loop asks for** (a
state rounded to bfloat16 after every token loses what a small ``D_t``
adds to it).

**Liveness.**  The convolution's inputs are positional, as a short
convolution's: an entry counts iff its column is at or after the row's
``start``.  The state cannot be: it stands for ALL the columns before the
block.  The state handed to a block whose first column is ``pos`` counts
iff ``pos > start`` (else the request begins inside this block, and it is
zeros); a token before ``start`` inside the block passes the state through
unchanged (``D_t := 0``); a row outside ``write_rows`` keeps both as they
were.  That covers a slot's previous occupant, left padding, a chunk that
is all padding and a ring restart, with no reset program.

Inference only: nothing here is taped.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp

from ...framework.tensor import Tensor, unwrap
from .. import initializer as I
from .layers import Layer

__all__ = ["Mamba2Mixer", "SsmStateCache", "decay_between", "conv_silu"]

SsmStateCache = collections.namedtuple("SsmStateCache", ["conv", "state"])
SsmStateCache.kind = "ssm_state"
SsmStateCache.wraps = False

_F32 = jnp.float32
_EXACT = jax.lax.Precision.HIGHEST


def _product(x, w):
    """``x [..., a] @ w [a, b]`` with float32 accumulation, in ``x``'s
    dtype."""
    return jnp.einsum("...a,ab->...b", x, unwrap(w),
                      preferred_element_type=_F32).astype(x.dtype)


def decay_between(cs):
    """``cs [..., L]``, the log decay summed up to each token (its own
    included) -> ``[..., t, s]``: the decay from token ``s`` to token
    ``t``, ``exp(cs_t - cs_s)``, for ``s <= t`` and 0 above the diagonal.
    (The mask goes inside the exp: above the diagonal the gap is positive
    and may overflow.)"""
    L = cs.shape[-1]
    gap = cs[..., :, None] - cs[..., None, :]
    causal = jnp.tril(jnp.ones((L, L), bool))
    return jnp.exp(jnp.where(causal, gap, -jnp.inf))


def conv_silu(x, before, live, w, bias=None):
    """``silu(conv(x) + bias)`` over the block ``x [B, T, C]``: causal,
    depthwise, ``w [C, taps]`` (tap j weighs the input ``taps - 1 - j``
    tokens back: the last tap is the token's own), ``before [B, taps-1,
    C]`` the inputs at the columns before the block, ``live [B, T]`` the
    block's columns that belong to the request (any other counts as
    zeros).  Returns (that, the inputs over ``before`` and the block).
    Inputs are kept as rounded to the dtype the cache keeps them in, so
    that a token fed in a chunk and one fed by a step see the same
    past."""
    T, taps = x.shape[1], w.shape[1]
    u = jnp.where(live[..., None], x, jnp.zeros((), x.dtype))
    full = jnp.concatenate([before.astype(x.dtype), u], axis=1)
    w = w.astype(_F32)
    acc = sum(w[:, j] * full[:, j:j + T].astype(_F32) for j in range(taps))
    if bias is not None:
        acc = acc + bias.astype(_F32)
    return jax.nn.silu(acc).astype(x.dtype), full


def state_update(x, dt, b, c, a_log, h0):
    """The one-token update of ``h_t = exp(dt_t a) h_{t-1} + dt_t x_t (x)
    b_t``, ``y_t = h_t c_t``, ``a = -exp(a_log)``: ``x [B, H, P]``, ``dt
    [B, H]`` float32, ``b``, ``c [B, G, N]`` (head ``h`` reads group ``h //
    (H / G)``), ``a_log [H]``, ``h0 [B, H, P, N]`` float32 -> (``y [B, H,
    P]`` float32, the new state)."""
    rep = x.shape[1] // b.shape[1]
    a = -jnp.exp(a_log.astype(_F32))
    bh, ch = (jnp.repeat(t.astype(_F32), rep, axis=1) for t in (b, c))
    decay = jnp.exp(dt * a)                                  # [B, H]
    h = h0 * decay[..., None, None] \
        + (dt[..., None] * x.astype(_F32))[..., None] * bh[:, :, None, :]
    return jnp.sum(h * ch[:, :, None, :], axis=-1), h


def state_scan(x, dt, b, c, a_log, h0, chunk):
    """The same recurrence over ``T = n x chunk`` tokens as a chunked
    scan: ``x [B, T, H, P]``, ``dt [B, T, H]`` float32 (0 where a token is
    not live: it passes the state through), ``b``, ``c [B, T, G, N]``,
    ``a_log [H]``, ``h0 [B, H, P, N]`` float32 -> (``y [B, T, H, P]``
    float32, the state after the last token)."""
    Bt, T, H, P = x.shape
    G, N, L = b.shape[2], b.shape[3], chunk
    n, r, dt_op = T // L, H // G, x.dtype
    x, dt, b, c = (t.reshape((Bt, n, L) + t.shape[2:])
                   for t in (x, dt, b, c))
    a = dt * -jnp.exp(a_log.astype(_F32))                  # [B, n, L, H]
    cs = jnp.cumsum(a, axis=2)                  # log decay up to t, incl.
    total = cs[:, :, -1]                                    # [B, n, H]
    # inside a chunk: y_t += sum_{s <= t} e^{cs_t - cs_s} D_s (C_t.B_s) x_s
    cb = jnp.einsum("bnlgk,bnsgk->bngls", c, b,
                    preferred_element_type=_F32)
    w = decay_between(jnp.moveaxis(cs, 3, 2)) \
        * jnp.moveaxis(dt, 3, 2)[..., None, :]              # [B,n,H,t,s]
    m = (jnp.repeat(cb, r, axis=2) * w).astype(dt_op)      # [B,n,H,t,s]
    y = jnp.einsum("bnhts,bnshp->bnthp", m, x,
                   preferred_element_type=_F32)
    # what a chunk adds to the state by its end
    to_end = jnp.exp(total[:, :, None, :] - cs) * dt        # [B, n, L, H]
    xw = (x.astype(_F32) * to_end[..., None]).astype(dt_op)
    add = jnp.einsum("bnsgrp,bnsgk->bngrpk",
                     xw.reshape(Bt, n, L, G, r, P), b,
                     preferred_element_type=_F32).reshape(Bt, n, H, P, N)
    # the states pass from chunk to chunk one after another
    keep = jnp.exp(total)                                   # [B, n, H]
    h, entering = h0, []
    for i in range(n):
        entering.append(h)
        h = h * keep[:, i, :, None, None] + add[:, i]
    carried = jnp.stack(entering, axis=1)               # [B, n, H, P, N]
    # the carried state read through C, as the float32 it is
    through = jnp.einsum("bnlgk,bngrpk->bnlgrp", c.astype(_F32),
                         carried.reshape(Bt, n, G, r, P, N),
                         precision=_EXACT,
                         preferred_element_type=_F32)
    y = y + through.reshape(Bt, n, L, H, P) * jnp.exp(cs)[..., None]
    return y.reshape(Bt, T, H, P), h


def state_mix(x, dt, b, c, a_log, h0, chunk):
    """The recurrence over the block ``x [B, T, H, P]`` in the form its
    width asks for: the one-token update (scope ``update``) for ``T ==
    1``, else the chunked scan (scope ``scan``) over the block padded to
    whole chunks (a padded token has ``dt = 0``: it passes the state
    through).  Operands as :func:`state_scan`'s; returns (``y [B, T, H,
    P]`` float32, the state after the block)."""
    T = x.shape[1]
    if T == 1:
        with jax.named_scope("update"):
            y, h = state_update(x[:, 0], dt[:, 0], b[:, 0], c[:, 0], a_log,
                                h0)
            return y[:, None], h
    pad = -T % chunk
    with jax.named_scope("scan"):
        xs, dts, bs, cs = (jnp.pad(
            t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, dt, b, c))
        y, h = state_scan(xs, dts, bs, cs, a_log, h0, chunk)
        return y[:, :T], h


class Mamba2Mixer(Layer):
    def __init__(self, hidden, heads, head_dim, state, groups, taps=4,
                 chunk=128, epsilon=1e-5, weight_attr=None, dtype=None):
        super().__init__()
        if heads % groups:
            raise ValueError(f"{heads} heads over {groups} groups of B/C")
        self.H, self.P, self.N, self.G = (int(heads), int(head_dim),
                                          int(state), int(groups))
        self.taps, self.chunk, self.eps = int(taps), int(chunk), float(epsilon)
        self.inner = self.H * self.P
        self.conv_dim = self.inner + 2 * self.G * self.N

        def mat(*shape):
            return self.create_parameter(
                list(shape), attr=weight_attr, dtype=dtype,
                default_initializer=I.Normal(0.0, 0.02))

        def per_head(value):
            # the recurrence's own scalars stay float32 whatever the
            # matrices are (a decay is an exp of them)
            return self.create_parameter(
                [self.H], dtype="float32", is_bias=True,
                default_initializer=I.Constant(value))
        self.in_proj = mat(hidden, self.inner + self.conv_dim + self.H)
        # tap j weighs xBC(t - (taps-1) + j): the last tap is the token's own
        self.conv = mat(self.conv_dim, self.taps)
        self.conv_bias = self.create_parameter(
            [self.conv_dim], dtype=dtype, is_bias=True)
        self.dt_bias, self.A_log, self.D = (per_head(0.0), per_head(0.0),
                                            per_head(1.0))
        self.norm = self.create_parameter(
            [self.inner], attr=weight_attr, dtype=dtype,
            default_initializer=I.Constant(1.0))
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

    # -- the pieces ------------------------------------------------------------
    def _conv(self, xbc, before, live):
        """``silu(conv(xBC) + b)`` over the block ``xbc [B, T, conv_dim]``
        (:func:`conv_silu`)."""
        return conv_silu(xbc, before, live, unwrap(self.conv),
                         unwrap(self.conv_bias))

    def _split(self, xbc):
        """``x [B, T, H, P]``, ``B`` and ``C [B, T, G, N]``."""
        lead = xbc.shape[:-1]
        x, b, c = jnp.split(xbc, [self.inner, self.inner + self.G * self.N],
                            axis=-1)
        return (x.reshape(lead + (self.H, self.P)),
                b.reshape(lead + (self.G, self.N)),
                c.reshape(lead + (self.G, self.N)))

    def _mix(self, u, before, h0, live):
        """The whole mixer over the block ``u [B, T, hidden]`` (normed):
        (output ``[B, T, hidden]``, the convolution's inputs over
        ``before`` and the block, the state after the block)."""
        Bt, T, _ = u.shape
        z, xbc, dt = jnp.split(
            _product(u, self.in_proj),
            [self.inner, self.inner + self.conv_dim], axis=-1)
        with jax.named_scope("conv"):
            xbc, full = self._conv(xbc, before, live)
        x, b, c = self._split(xbc)
        dt = jax.nn.softplus(dt.astype(_F32)
                             + unwrap(self.dt_bias).astype(_F32))
        dt = jnp.where(live[..., None], dt, 0.0)
        y, h = state_mix(x, dt, b, c, unwrap(self.A_log), h0, self.chunk)
        y = y + unwrap(self.D).astype(_F32)[:, None] * x.astype(_F32)
        y = y.reshape(Bt, T, self.inner) \
            * jax.nn.silu(z.astype(_F32))
        g = y.reshape(Bt, T, self.G, self.inner // self.G)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + self.eps)
        y = (g.reshape(Bt, T, self.inner)
             * unwrap(self.norm).astype(_F32)).astype(u.dtype)
        return _product(y, self.out_proj), full, h

    def forward_cached(self, x, cache, pos, start, write_rows=None):
        """Feed the block ``x [B, T, hidden]`` (normed) whose first column
        is ``pos``; ``start [B]`` is each row's first valid column, and a
        row outside ``write_rows [B]`` keeps what it has (module
        docstring: the two liveness rules)."""
        T, k = x.shape[1], self.taps - 1
        conv, state = unwrap(cache.conv)[:, 0], unwrap(cache.state)
        cols = pos + jnp.arange(-k, T, dtype=jnp.int32)
        live = cols[None, :] >= start[:, None]                 # [B, k + T]
        before = jnp.where(live[:, :k, None], conv,
                           jnp.zeros((), conv.dtype))
        h0 = jnp.where((pos > start)[:, None, None, None], state, 0.0)
        y, full, h = self._mix(x, before, h0, live[:, k:])
        new = full[:, T:].astype(conv.dtype)
        if write_rows is not None:
            new = jnp.where(write_rows[:, None, None], new, conv)
            h = jnp.where(write_rows[:, None, None, None], h, state)
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
