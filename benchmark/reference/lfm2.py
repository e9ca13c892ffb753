"""Plain reference of the ``lfm2_moe`` language model (LFM2-8B-A1B,
https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json): the
full forward over a prompt with its served tokens, float32 at precision
"highest", no cache, no batching, no kernels.

The equations (ISSUE 31, section 1), as computed here, for the first
``num_hidden_layers`` entries of ``layer_types``:

* block: ``h = x + Mix(RMSNorm_op(x))``, ``y = h + FFN(RMSNorm_ffn(h))``;
  after the last kept layer RMSNorm, logits ``= E h`` with the embedding
  ``E`` (tied).
* ``conv``: ``[B | C | X] = W_in x`` (3 x hidden), ``u = B * X``, ``c(t) =
  w_0 * u(t-2) + w_1 * u(t-1) + w_2 * u(t)`` written out as that sum of
  three shifted copies (``conv_L_cache`` 3, depthwise, ``u = 0`` before
  the first token), ``Mix = W_out (C * c)``.
* ``full_attention``: ``q_h = RoPE(RMSNorm_q(W_q,h x))`` for each of the
  query heads, ``k_g = RoPE(RMSNorm_k(W_k,g x))``, ``v_g = W_v,g x`` for
  the KV heads; the norms are over a head's features with one learned
  vector each; rotary over all of them, halves paired, positions from 0;
  ``a_h(t, s) = q_h(t) . k_{h // rep}(s) / sqrt(d)``, causal softmax, one
  query head at a time; ``Mix = W_o concat_h o_h``.
* dense FFN (layers below ``num_dense_layers``): ``W_2(silu(W_1 u) * W_3
  u)``.
* MoE FFN: ``s = sigmoid(W_r u)`` over all experts, chosen = top
  ``num_experts_per_tok`` of ``s + b`` (``use_expert_bias``: ``b``
  chooses only), ``w_i = s_i / (sum_chosen s + 1e-6)`` (``norm_topk_prob``)
  times ``routed_scaling_factor``; every expert is held; one expert at a
  time, every token through it, weighted by 0 where it was not chosen.
* compared (``served_gaps``): the mean, over blocks of 64 consecutive
  served tokens, of how far a served token's logit lies below the best.

What the config does not spell out is listed in the configuration file
under ``assumed``.  Weights live in one flat canonical tree
(``l<i>.<leaf>``) in the dtype they are served in; each matrix is upcast
inside its own product, and the tied head runs over the vocabulary in
blocks that keep only what the comparison needs (the best logit, the
picked one, the largest magnitude, the argmax), so the reference fits
beside a serving program that fills the chip.  Imports nothing of the
program.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .common import Arith, seed_key
from .dots3 import rms_norm, rope, swiglu

CONV = "conv"
HEAD_BLOCK = 8192       # vocabulary rows of the tied head held at once
PAD = 256


def layer_kinds(cfg: dict) -> list:
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // \
        cfg["num_attention_heads"]


def leaf_shapes(cfg: dict) -> dict:
    """{leaf: (shape, init std or None for a gain)} of the canonical tree.
    Each matrix is drawn at 1 / sqrt(fan_in) against an input of unit
    scale, so every product's output has unit scale and the attention
    scores, the router's logits and the output logits a spread of about
    one (the tied table at ``hidden ** -0.5`` for that reason: what it
    adds to the stream is renormed by the first layer)."""
    h, V = cfg["hidden_size"], cfg["vocab_size"]
    H, KV, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        head_dim(cfg)
    E, Fe, F = cfg["num_experts"], cfg["moe_intermediate_size"], \
        cfg["intermediate_size"]
    taps = cfg["conv_L_cache"]
    out = {"embed": ((V, h), h ** -0.5), "norm_f": ((h,), None)}
    for i, kind in enumerate(layer_kinds(cfg)):
        L = {"op_norm": ((h,), None), "ffn_norm": ((h,), None)}
        if kind == CONV:
            L.update({"in_proj": ((h, 3 * h), h ** -0.5),
                      "conv": ((h, taps), taps ** -0.5),
                      "out_proj": ((h, h), h ** -0.5)})
        else:
            L.update({"q": ((h, H * d), h ** -0.5),
                      "k": ((h, KV * d), h ** -0.5),
                      "v": ((h, KV * d), h ** -0.5),
                      "q_norm": ((d,), None), "k_norm": ((d,), None),
                      "o": ((H * d, h), (H * d) ** -0.5)})
        if i < cfg["num_dense_layers"]:
            L.update({"ffn_g": ((h, F), h ** -0.5), "ffn_u": ((h, F), h ** -0.5),
                      "ffn_d": ((F, h), F ** -0.5)})
        else:
            L.update({"router": ((h, E), h ** -0.5), "router_b": ((E,), 0.01),
                      "exp_g": ((E, h, Fe), h ** -0.5),
                      "exp_u": ((E, h, Fe), h ** -0.5),
                      "exp_d": ((E, Fe, h), Fe ** -0.5)})
        out.update({f"l{i}.{k}": v for k, v in L.items()})
    return out


def init_weights(cfg: dict, seed: int, dtype=None):
    """The canonical tree from ``seed``, made on the device in the served
    dtype; ``router_b`` (the expert bias, used to choose only) stays
    float32."""
    dtype = jnp.dtype(dtype or cfg.get("dtype", "bfloat16"))
    leaves = sorted(leaf_shapes(cfg).items())

    def draw(key, std, shape, dt):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dt)

    def leaf(key, i, name, shape, std, draw=draw):
        if std is None:
            return jnp.ones(shape, dtype)
        dt = jnp.float32 if name.endswith("router_b") else dtype
        return draw(jax.random.fold_in(key, i), jnp.float32(std), shape, dt)

    key = seed_key(seed)
    if sum(math.prod(s) for _, (s, _) in leaves) < 2 ** 26:
        # a test's size: one program for the whole tree (the same numbers)
        return jax.jit(lambda k: {n: leaf(k, i, n, s, sd) for i, (n, (s, sd))
                                  in enumerate(leaves)})(key)
    # the real size: one leaf at a time, so that the float32 draws of a
    # model that fills the chip are never held together; one program a
    # shape (the layers repeat them), not one a leaf
    one = jax.jit(draw, static_argnums=(2, 3))
    return {n: leaf(key, i, n, s, sd, one) for i, (n, (s, sd))
            in enumerate(leaves)}


# -- the pieces ----------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _conv_mixer(x, lw, *, eps, precision):
    ar = Arith(precision)
    xn = rms_norm(x, lw["op_norm"], eps)
    b, c, xx = jnp.split(ar.einsum("th,hk->tk", xn, lw["in_proj"]), 3, -1)
    u = b * xx
    w = lw["conv"].astype(jnp.float32)
    taps = w.shape[1]
    conv = jnp.zeros_like(u)
    for j in range(taps):               # tap j weighs u(t - (taps-1) + j)
        back = taps - 1 - j
        shifted = jnp.pad(u, ((back, 0), (0, 0)))[:u.shape[0]]
        conv = conv + w[:, j] * shifted
    return x + ar.einsum("th,hk->tk", c * conv, lw["out_proj"])


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _attention(x, lw, *, cfg_key, precision):
    cfg = dict(cfg_key)
    ar = Arith(precision)
    H, KV, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        head_dim(cfg)
    eps, base, T = cfg["norm_eps"], float(cfg["rope_theta"]), x.shape[0]
    xn = rms_norm(x, lw["op_norm"], eps)
    q = rope(rms_norm(ar.einsum("th,hk->tk", xn, lw["q"]).reshape(T, H, d),
                      lw["q_norm"], eps), base)
    k = rope(rms_norm(ar.einsum("th,hk->tk", xn, lw["k"]).reshape(T, KV, d),
                      lw["k_norm"], eps), base)
    v = ar.einsum("th,hk->tk", xn, lw["v"]).reshape(T, KV, d)
    t = jnp.arange(T)
    causal = t[None, :] <= t[:, None]

    def head(i):                        # one query head at a time
        g = i // (H // KV)
        s = ar.einsum("td,sd->ts", q[:, i], k[:, g]) * d ** -0.5
        p = jax.nn.softmax(jnp.where(causal, s, -1e30), -1)
        return ar.einsum("ts,sd->td", p, v[:, g])

    o = jax.lax.map(head, jnp.arange(H))                      # [H, T, d]
    o = jnp.moveaxis(o, 0, 1).reshape(T, H * d)
    return x + ar.einsum("tk,kh->th", o, lw["o"])


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _dense_ffn(x, lw, *, eps, precision):
    u = rms_norm(x, lw["ffn_norm"], eps)
    return x + swiglu(Arith(precision), u, lw["ffn_g"], lw["ffn_u"],
                      lw["ffn_d"])


def route(ar, u, lw, cfg):
    """(chosen ids ``[T, k]``, weights ``[T, k]``)."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(ar.einsum("th,he->te", u, lw["router"]))
    b = lw["router_b"].astype(jnp.float32) if cfg["use_expert_bias"] else 0.0
    _, ids = jax.lax.top_k(s + b, k)
    w = jnp.take_along_axis(s, ids, 1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-6)
    return ids, w * cfg["routed_scaling_factor"]


def moe(ar, u, lw, cfg, experts=None):
    """``sum_chosen w_i E_i(u)`` over the experts ``[lo, hi)`` (all of
    them when None): one expert at a time, each token weighted by its
    routing weight for that expert, 0 where it was not chosen."""
    ids, w = route(ar, u, lw, cfg)
    lo, hi = experts or (0, lw["exp_g"].shape[0])

    def one(acc, ew):
        e, wg, wu, wd = ew
        we = jnp.sum(jnp.where(ids == e, w, 0.0), -1)
        return acc + we[:, None] * swiglu(ar, u, wg, wu, wd), None

    out, _ = jax.lax.scan(
        one, jnp.zeros(u.shape, jnp.float32),
        (jnp.arange(lo, hi), lw["exp_g"][lo:hi], lw["exp_u"][lo:hi],
         lw["exp_d"][lo:hi]))
    return out


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _moe_ffn(x, lw, *, cfg_key, precision):
    cfg = dict(cfg_key)
    u = rms_norm(x, lw["ffn_norm"], cfg["norm_eps"])
    return x + moe(Arith(precision), u, lw, cfg)


@functools.partial(jax.jit, static_argnames=("eps", "precision", "block"))
def _head(x, g, table, at, pick, *, eps, precision, block=HEAD_BLOCK):
    """Of the tied head's logits at the positions ``at``, over the
    vocabulary in blocks of ``block`` rows: (the best, the one of ``pick``,
    the largest magnitude, the argmax), each ``[len(at)]``."""
    ar = Arith(precision)
    xn = rms_norm(x, g, eps)[at]
    V = table.shape[0]
    n = -(-V // block)
    rows = jnp.arange(block)

    def one(carry, i):
        best, got, big, arg = carry
        v0 = jnp.minimum(i * block, V - block)     # the last block overlaps
        part = ar.einsum("th,vh->tv", xn, jax.lax.dynamic_slice_in_dim(
            table, v0, block, 0))
        ids = v0 + rows
        top = part.max(-1)
        arg = jnp.where(top > best, ids[part.argmax(-1)], arg)
        got = got + jnp.sum(jnp.where(ids[None] == pick[:, None], part, 0.0)
                            * (ids >= i * block)[None], -1)
        return (jnp.maximum(best, top), got,
                jnp.maximum(big, jnp.abs(part).max(-1)), arg), None

    m = xn.shape[0]
    init = (jnp.full((m,), -jnp.inf), jnp.zeros((m,)), jnp.zeros((m,)),
            jnp.zeros((m,), jnp.int32))
    out, _ = jax.lax.scan(one, init, jnp.arange(n))
    return out


def _cfg_key(cfg: dict):
    """The numbers of the config the jitted pieces need, hashable."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, str))))


def _layer_weights(w, i):
    p = f"l{i}."
    return {k[len(p):]: v for k, v in w.items()
            if isinstance(k, str) and k.startswith(p)}


def _hidden(cfg, w, ids, precision):
    """The stream ``[T, hidden]`` after the last kept layer of the full
    causal forward over ``ids [T]``: one layer, one piece at a time."""
    key, eps = _cfg_key(cfg), cfg["norm_eps"]
    x = w["embed"][ids].astype(jnp.float32)
    for i, kind in enumerate(layer_kinds(cfg)):
        lw = _layer_weights(w, i)
        if kind == CONV:
            x = _conv_mixer(x, lw, eps=eps, precision=precision)
        else:
            x = _attention(x, lw, cfg_key=key, precision=precision)
        if i < cfg["num_dense_layers"]:
            x = _dense_ffn(x, lw, eps=eps, precision=precision)
        else:
            x = _moe_ffn(x, lw, cfg_key=key, precision=precision)
    return x


def _padded(cfg, prompt, served):
    """prompt + served tokens right-padded to a multiple of ``PAD``
    (causal, so padding changes nothing), and the positions that produced
    each served token (as many as ``max_new_tokens``, so that one program
    serves every request)."""
    import numpy as np
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    served = np.asarray(served, np.int32).reshape(-1)
    ids = np.concatenate([prompt, served[:-1]])
    pad = min(PAD, int(cfg.get("reference_pad", PAD)))
    padded = np.zeros((-(-ids.size // pad) * pad,), np.int32)
    padded[:ids.size] = ids
    n_at = max(served.size, int(cfg.get("serve", {}).get("max_new_tokens", 0)))
    at = np.minimum(prompt.size - 1 + np.arange(n_at), ids.size - 1)
    return padded, at, served.size


def _served(cfg, w, prompt, served, precision, pick=None):
    """(best, picked, largest magnitude, argmax) of the logits at the
    positions that produced each served token, each ``[len(served)]``.
    ``pick`` defaults to the served tokens."""
    import numpy as np
    padded, at, n = _padded(cfg, prompt, served)
    x = _hidden(cfg, w, jnp.asarray(padded), precision)
    picks = np.zeros((at.shape[0],), np.int32)
    picks[:n] = np.asarray(served if pick is None else pick,
                           np.int32).reshape(-1)
    block = min(HEAD_BLOCK, w["embed"].shape[0])
    stats = _head(x, w["norm_f"], w["embed"], jnp.asarray(at, jnp.int32),
                  jnp.asarray(picks), eps=cfg["norm_eps"],
                  precision=precision, block=block)
    return tuple(s[:n] for s in stats)


def served_logits(cfg: dict, w, prompt, served, precision="float32"):
    """Logits ``[len(served), vocab]`` at the positions that produced each
    served token, whole: for the tests' small sizes."""
    padded, at, n = _padded(cfg, prompt, served)
    xn = rms_norm(_hidden(cfg, w, jnp.asarray(padded), precision),
                  w["norm_f"], cfg["norm_eps"])[at[:n]]
    return Arith(precision).einsum("th,vh->tv", xn, w["embed"])


# Ten expert layers each choose 4 of 32 by scores that lie close together
# (with random weights 29% of the (layer, token) pairs have their 4th and
# 5th expert within 1e-2, 6% within 2e-3), and the choice is not
# continuous: in bfloat16 nearly every token takes another expert than the
# float32 reference somewhere, and everything after that layer follows.
# The reference's own equations with bfloat16 operands then put another
# token first at one position in ten, up to 0.16 of max|logit| under the
# reference's best; resolving the reference's near-ties both ways
# (``dots3.tolerant_gaps``, ISSUE 31's prescription) does not help, because
# the flips come from the stream's noise upstream and are no nearer where
# the margins are small (PERF.md section 2 has the readings).  So single
# tokens read the routing's discontinuity, not the arithmetic.  What reads
# the arithmetic is the MEAN gap over a run of tokens: bfloat16 0.008-0.012
# against float8's 0.15.  The comparison is therefore over blocks of
# ``GAP_BLOCK`` consecutive served tokens.
GAP_BLOCK = 64


def _block_means(gaps):
    """The mean of ``gaps [n]`` over consecutive blocks of at least
    ``GAP_BLOCK`` tokens (one block when there are fewer)."""
    import numpy as np
    gaps = np.asarray(gaps, np.float32)
    return np.asarray([b.mean() for b in np.array_split(
        gaps, max(gaps.size // GAP_BLOCK, 1))], np.float32)


def _gaps(stats):
    best, got, big, _ = stats
    return (best - got) / big


def served_gaps(cfg: dict, w, prompt, served, precision="float32"):
    """How far the served tokens' reference logits lie below the
    reference's best at their positions, relative to max|logit| there:
    the mean over each block of ``GAP_BLOCK`` consecutive served tokens
    (why blocks: above)."""
    return _block_means(_gaps(_served(cfg, w, prompt, served, precision)))


def control_gaps(cfg: dict, w, prompt, served, control_precision):
    """The control: the same block means for the tokens that the lower
    precision puts first at each position of the same prompt and tokens,
    under the float32 reference."""
    pick = _served(cfg, w, prompt, served, control_precision)[3]
    return _block_means(_gaps(_served(cfg, w, prompt, served, "float32",
                                      pick)))
