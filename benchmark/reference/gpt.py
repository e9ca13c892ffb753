"""Plain reference of a GPT-2 style decoder: the full forward pass over a
prompt with its served tokens, float32 at precision "highest".

Follows Radford et al. 2019 (learned positions, pre-LayerNorm blocks, full
multi-head attention, tied output head).  Departure, to follow what the
program computes: the exact (erf) GELU where GPT-2 uses the tanh form.

Weights live in one canonical tree — the blocks stacked on a leading axis,
or lists of per-layer arrays — in the dtype they are served in; each layer
is upcast to float32 inside the layer loop, so no float32 copy of the model
is ever held and the reference fits beside a serving program's state.
``benchmark/models/gpt.py`` maps the tree onto the program's parameter
names.  Imports nothing of the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .common import Arith, attention, ffn, layer_norm, seed_key


def init_weights(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """The canonical weight tree from ``seed``, made on the device in one
    jitted call in the served dtype: N(0, initializer_range) for matrices
    and embeddings, scaled by 1/sqrt(2 n_layer) on the two residual
    projections (GPT-2's published initialisation), biases 0, gains 1."""
    h, f, L = cfg["n_embd"], cfg["n_inner"], cfg["n_layer"]
    std = cfg["initializer_range"]
    resid = std / (2 * L) ** 0.5
    shapes = {"wte": ((cfg["vocab_size"], h), std),
              "wpe": ((cfg["n_positions"], h), std)}
    lshapes = {"q_w": ((L, h, h), std), "k_w": ((L, h, h), std),
               "v_w": ((L, h, h), std), "o_w": ((L, h, h), resid),
               "f1_w": ((L, h, f), std), "f2_w": ((L, f, h), resid)}
    lzeros = {"q_b": (L, h), "k_b": (L, h), "v_b": (L, h), "o_b": (L, h),
              "ln1_b": (L, h), "f1_b": (L, f), "f2_b": (L, h), "ln2_b": (L, h)}
    lones = {"ln1_g": (L, h), "ln2_g": (L, h)}

    @jax.jit
    def make(key):
        def normals(shapes, key):
            keys = jax.random.split(key, len(shapes))
            return {n: (jax.random.normal(k, s, jnp.float32) * sd)
                    .astype(dtype)
                    for k, (n, (s, sd)) in zip(keys, sorted(shapes.items()))}
        k1, k2 = jax.random.split(key)
        w = normals(shapes, k1)
        w["lnf_g"] = jnp.ones((h,), dtype)
        w["lnf_b"] = jnp.zeros((h,), dtype)
        lw = normals(lshapes, k2)
        lw.update({n: jnp.zeros(s, dtype) for n, s in lzeros.items()})
        lw.update({n: jnp.ones(s, dtype) for n, s in lones.items()})
        w["layers"] = lw
        return w

    return make(seed_key(seed))


@functools.partial(jax.jit, static_argnames=("precision",))
def _embed(wte, wpe, ids, *, precision):
    T = ids.shape[1]
    return wte[ids].astype(jnp.float32) + wpe[:T][None].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("heads", "eps", "precision"))
def _block(x, lw, *, heads, eps, precision):
    ar = Arith(precision)
    a = layer_norm(x, lw["ln1_g"], lw["ln1_b"], eps)
    x = x + attention(ar, a, lw, heads, causal=True)
    a = layer_norm(x, lw["ln2_g"], lw["ln2_b"], eps)
    return x + ffn(ar, a, lw)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, g, b, wte, at, *, eps, precision):
    x = layer_norm(x, g, b, eps)[0][at]
    return Arith(precision).einsum("th,vh->tv", x, wte)


def _logits_at(w, ids, at, *, heads, eps, precision):
    """Logits [len(at), vocab] of the full causal forward over ``ids``
    [1, T] at the positions ``at``.  One layer at a time, each upcast from
    the served dtype inside its own small program; ``w["layers"]`` holds
    stacked arrays [L, ...] or lists of L arrays."""
    x = _embed(w["wte"], w["wpe"], ids, precision=precision)
    layers = w["layers"]
    for j in range(len(layers["q_w"])):
        x = _block(x, {n: a[j] for n, a in layers.items()}, heads=heads,
                   eps=eps, precision=precision)
    return _head(x, w["lnf_g"], w["lnf_b"], w["wte"], at, eps=eps,
                 precision=precision)


PAD = 256


def served_logits(cfg: dict, w, prompt, served, precision="float32"):
    """Logits [len(served), vocab] at the positions that produced each
    served token: one forward over prompt + served tokens, right-padded to
    a multiple of ``PAD`` positions (causal, so padding changes nothing)
    to keep the shapes, and so the compiled programs, few."""
    import numpy as np
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    served = np.asarray(served, np.int32).reshape(-1)
    ids = np.concatenate([prompt, served[:-1]])
    if ids.size > cfg["n_positions"]:
        raise ValueError(f"{ids.size} tokens do not fit the model's "
                         f"{cfg['n_positions']} positions")
    T = min(-(-ids.size // PAD) * PAD, cfg["n_positions"])
    padded = np.zeros((1, T), np.int32)
    padded[0, :ids.size] = ids
    # one gathered shape per configuration: its token budget
    n_at = max(served.size, int(cfg.get("serve", {}).get("max_new_tokens", 0)))
    at = np.minimum(prompt.size - 1 + np.arange(n_at), ids.size - 1)
    out = _logits_at(w, jnp.asarray(padded), jnp.asarray(at, jnp.int32),
                     heads=cfg["n_head"], eps=cfg["layer_norm_epsilon"],
                     precision=precision)
    return out[:served.size]


def served_gaps(cfg: dict, w, prompt, served, precision="float32"):
    """For each served token: how far its reference logit lies below the
    reference's best at that position, relative to max|logit| there.
    0 where the served token is the reference's greedy token."""
    logits = served_logits(cfg, w, prompt, served, precision)
    served = jnp.asarray(served, jnp.int32).reshape(-1)
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
    return (best - got) / jnp.max(jnp.abs(logits), axis=-1)


def control_gaps(cfg: dict, w, prompt, served, control_precision):
    """The control: at each position of the same prompt and tokens, the gap
    (under the float32 reference) of the token the lower precision puts
    first."""
    ref = served_logits(cfg, w, prompt, served, "float32")
    low = served_logits(cfg, w, prompt, served, control_precision)
    pick = jnp.argmax(low, axis=-1)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
    return (best - got) / jnp.max(jnp.abs(ref), axis=-1)


def position_gaps(cfg: dict, w, ids, precision):
    """At EVERY position of the token sequence ``ids``: the gap (under the
    float32 reference) of the token that ``precision`` puts first.  The
    control over a whole context at once — random contexts hold far more
    near-ties than a greedy continuation, which soon repeats itself."""
    ids = jnp.asarray(ids, jnp.int32).reshape(1, -1)
    at = jnp.arange(ids.shape[1], dtype=jnp.int32)
    kw = dict(heads=cfg["n_head"], eps=cfg["layer_norm_epsilon"])
    ref = _logits_at(w, ids, at, precision="float32", **kw)
    low = _logits_at(w, ids, at, precision=precision, **kw)
    got = jnp.take_along_axis(ref, jnp.argmax(low, axis=-1)[:, None], axis=-1)
    return (jnp.max(ref, axis=-1) - got[:, 0]) / jnp.max(jnp.abs(ref), axis=-1)
