"""Shared pieces of the plain references: float32 arithmetic at precision
"highest", and the lower-precision stand-ins that the controls compute in.

Nothing here imports the program (``paddle_tpu``).  A reference is written
against ``Arith``: every matrix product of the model goes through
``Arith.einsum``, so the same forward can be computed in float32 (the
reference), in bfloat16 (what the configurations state) or in float8 (the
control: the nearest precision below bfloat16, the step that would tempt a
later PR).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _fake_float8(x):
    """Round ``x`` to float8 e4m3 with one scale per tensor, keeping the
    float32 container; straight-through gradient (the backward products
    then use the rounded operands and float32 cotangents)."""
    x = x.astype(jnp.float32)
    scale = jax.lax.stop_gradient(
        jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


@jax.custom_vjp
def _float8_cotangent(y):
    """Identity forward; backward, the cotangent is rounded to float8 e5m2
    with one scale per tensor — the usual float8 training recipe (e4m3
    operands forward, e5m2 gradients backward)."""
    return y


def _f8c_fwd(y):
    return y, None


def _f8c_bwd(_, g):
    g = g.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(g)), 1e-30) / 57344.0
    return ((g / scale).astype(jnp.float8_e5m2).astype(jnp.float32) * scale,)


_float8_cotangent.defvjp(_f8c_fwd, _f8c_bwd)


def _fake_bfloat16(x):
    x = x.astype(jnp.float32)
    q = x.astype(jnp.bfloat16).astype(jnp.float32)
    return x + jax.lax.stop_gradient(q - x)


_ROUNDERS = {
    "float32": lambda x: x.astype(jnp.float32),
    "bfloat16": _fake_bfloat16,
    "float8_e4m3": _fake_float8,
}

# the nearest precision below the one a configuration states
CONTROL_PRECISION = {"float32": "bfloat16", "bfloat16": "float8_e4m3"}


class Arith:
    """Matrix products with both operands rounded to ``precision`` and
    accumulated in float32 at precision "highest"; in float8 the backward
    pass's cotangents are rounded too (e5m2)."""

    def __init__(self, precision: str = "float32"):
        if precision not in _ROUNDERS:
            raise ValueError(f"unknown precision {precision!r}; "
                             f"have {sorted(_ROUNDERS)}")
        self.precision = precision
        self._round = _ROUNDERS[precision]

    def einsum(self, spec, a, b):
        y = jnp.einsum(spec, self._round(a), self._round(b),
                       precision=HIGHEST, preferred_element_type=jnp.float32)
        return _float8_cotangent(y) if self.precision == "float8_e4m3" else y


def layer_norm(x, g, b, eps):
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32) \
        + b.astype(jnp.float32)


def gelu(x):
    return jax.nn.gelu(x, approximate=False)


def attention(ar: Arith, x, lw, heads, causal):
    """Full multi-head self-attention of one layer over ``x`` [b, s, h];
    ``lw`` holds that layer's q/k/v/o weights [h, h] and biases [h]."""
    b, s, h = x.shape
    d = h // heads
    f32 = jnp.float32

    def proj(w, bias):
        y = ar.einsum("bsh,hk->bsk", x, lw[w]) + lw[bias].astype(f32)
        return y.reshape(b, s, heads, d)

    q, k, v = proj("q_w", "q_b"), proj("k_w", "k_b"), proj("v_w", "v_b")
    scores = ar.einsum("bqnd,bknd->bnqk", q, k) * (d ** -0.5)
    if causal:
        keep = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(keep[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = ar.einsum("bnqk,bknd->bqnd", probs, v).reshape(b, s, h)
    return ar.einsum("bsh,hk->bsk", ctx, lw["o_w"]) + lw["o_b"].astype(f32)


def ffn(ar: Arith, x, lw):
    f32 = jnp.float32
    y = gelu(ar.einsum("bsh,hf->bsf", x, lw["f1_w"]) + lw["f1_b"].astype(f32))
    return ar.einsum("bsf,fh->bsh", y, lw["f2_w"]) + lw["f2_b"].astype(f32)


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    key = jax.random.key(seed % (2 ** 31 - 1))
    return jax.random.fold_in(key, (seed // (2 ** 31 - 1)) % (2 ** 31 - 1))


def leaf_norms(tree):
    """{leaf id: float32 scalar or [L] vector}: the 2-norm of every leaf,
    per layer for the stacked ``layers`` group."""
    out = {}
    for k, v in tree.items():
        if k == "layers":
            for n, a in v.items():
                a = a.astype(jnp.float32)
                out[f"layers/{n}"] = jnp.sqrt(jnp.sum(
                    jnp.square(a), axis=tuple(range(1, a.ndim))))
        else:
            out[k] = jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
    return out


def flatten_norms(norms) -> dict:
    """Host floats keyed "word", "layers/q_w/3", ..."""
    import numpy as np
    out = {}
    for k, v in norms.items():
        a = np.asarray(v, np.float64)
        if a.ndim == 0:
            out[k] = float(a)
        else:
            for i, x in enumerate(a):
                out[f"{k}/{i}"] = float(x)
    return out


SKETCHES = 16


def _sign(n, offset, salt):
    """[n] values of +-1: one bit of an integer hash (lowbias32) of each
    element's flat index + ``offset``, mixed with ``salt`` (a uint32
    scalar, traced: a program must not change with the seed, or every new
    seed compiles it anew).  No random-number generator: the hash fuses
    into the reduction that uses it, costs no memory, and is the same
    wherever it is computed."""
    u = jnp.uint32
    x = (jax.lax.iota(u, n) + u(offset % (2 ** 32))) ^ salt
    x = (x ^ (x >> 16)) * u(0x7FEB352D)
    x = (x ^ (x >> 15)) * u(0x846CA68B)
    x = x ^ (x >> 16)
    return (x & 1).astype(jnp.float32) * 2.0 - 1.0


def _count_sketch(rows, offset, salt, k):
    """``rows`` [r, n] -> [r, k]: every element, times its sign, is added
    into bucket (index mod k) of its row.  The sum over the buckets of
    (sketch of a - sketch of b)^2 is an unbiased estimate of |a - b|^2."""
    r, n = rows.shape
    v = rows.astype(jnp.float32) * _sign(r * n, offset, salt).reshape(r, n)
    v = jnp.pad(v, ((0, 0), (0, (-n) % k)))
    return v.reshape(r, -1, k).sum(axis=1)


def sketch_salt(seed: int):
    """The run's seed as the uint32 that ``sketches`` takes as an argument."""
    import numpy as np
    return np.uint32(int(seed) % (2 ** 32))


def sketches(tree, salt, k: int = SKETCHES):
    """Count-sketches of every leaf, seeded by ``salt`` (``sketch_salt``): {leaf: [k]} for the top leaves,
    {"layers/<leaf>": [L, k]} for the per-layer ones.  A per-layer leaf may
    be one stacked array [L, ...] or a list of L arrays (the program's own
    leaves, not restacked); both give the same numbers.  The difference of
    two trees' sketches estimates the norm of their DIFFERENCE, which
    rounding noise moves long before it moves the difference of their
    norms."""
    u = jnp.uint32
    base = jnp.asarray(salt, u) * u(2654435761)
    out = {}
    names = sorted(n for n in tree if n != "layers") + \
        [f"layers/{n}" for n in sorted(tree["layers"])]
    for i, name in enumerate(names):
        salt = base + u((i + 1) * 40503)
        if not name.startswith("layers/"):
            out[name] = _count_sketch(tree[name].reshape(1, -1), 0, salt, k)[0]
            continue
        a = tree["layers"][name.split("/")[1]]
        if isinstance(a, (list, tuple)):
            out[name] = jnp.concatenate([
                _count_sketch(x.reshape(1, -1), j * x.size, salt, k)
                for j, x in enumerate(a)])
        else:
            out[name] = _count_sketch(a.reshape(a.shape[0], -1), 0, salt, k)
    return out


def flatten_sketches(sk) -> dict:
    """Host arrays keyed "word", "layers/q_w/3", each [k]."""
    import numpy as np
    out = {}
    for name, v in sk.items():
        a = np.asarray(v, np.float64)
        if a.ndim == 1:
            out[name] = a
        else:
            for j, row in enumerate(a):
                out[f"{name}/{j}"] = row
    return out
