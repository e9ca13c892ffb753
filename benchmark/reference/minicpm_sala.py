"""Plain reference of the ``minicpm_sala`` language model (MiniCPM-SALA,
https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json): the
full forward over a prompt with its served tokens, float32 at precision
"highest", no cache, no chunks, no kernels, no batching: the linear layers'
state token by token, the sparse layers' pooling and block choice written
out per token.

The equations (ISSUE 44), as computed here, for the ``num_hidden_layers``
entries of ``mixer_types`` kept (published layers ``first_published_layer``
onward):

* model: ``x_0 = scale_emb E[ids]``; layer ``h = x + r Mix(RMSNorm(x))``,
  ``y = h + r MLP(RMSNorm(h))``, ``r = scale_depth / sqrt(published
  depth)`` (1.4 / sqrt(32): the published depth stays under the root
  whatever is kept), ``MLP(u) = W_d (silu(W_g u) * W_u u)``; ``logits =
  W_head (RMSNorm(x_L) / (hidden_size / dim_model_base))``; ``rms_norm_eps``
  1e-6; untied head.
* ``lightning-attn``: ``q, k, v = W_{q,k,v} u`` in ``lightning_nh`` heads of
  ``lightning_head_dim``; RMSNorm over each head's features of q and of k
  (one learned vector each); rotary (``rope_theta``, the whole head, halves
  paired) on q and k by the token's position; per head, float32, TOKEN BY
  TOKEN (``lax.scan``)::

      S_t = exp(-s_h) S_{t-1} + k_t^T v_t,   S = 0 before the first token
      o_t = q_t S_t / sqrt(head_dim)

  ``Mix = W_o (RMSNorm_head(o) * sigmoid(W_gate u))``: the output norm over
  each head's features with one learned ``heads x head_dim`` vector, the
  gate ``hidden -> heads x head_dim`` before ``W_o``.  ``s_h = 2 ** (-8 (h
  + 1) / heads) x (1 - l / (published depth - 1) + 1e-5)``, ``l`` the
  layer's PUBLISHED index (the family's ``build_slope_tensor``).
* ``minicpm4``: q in ``num_attention_heads`` heads, k and v in
  ``num_key_value_heads`` heads of ``head_dim``, per-head RMSNorm on q and
  k, NO rotary, scale ``head_dim ** -0.5``, query head ``i`` reads cached
  head ``i // rep``; ``Mix = W_o (Attn * sigmoid(W_gate u))``.  With
  ``sparse_config`` (kernel_size 32, kernel_stride 16, block_size 64, topk
  64, init_blocks 1, window_size 2048, dense_len 8192), for the query at
  position ``t`` (context ``n = t + 1``): if ``n <= dense_len``, causal
  softmax over all ``n`` columns.  Else (1) pooled keys ``c_j = mean(k[16 j
  : 16 j + 32])`` for every ``j`` with ``16 j + 32 <= n``, a cached head;
  (2) ``a_j = sum_{h in group} softmax_j(q_h . c_j / sqrt(d))``; (3) block
  ``b`` = tokens ``[64 b, 64 b + 64)`` scores ``max a_j`` over the pooled
  entries that overlap it; (4) block 0 and the blocks that hold the last
  2,048 tokens are always chosen, the rest of the 64 by score, ties to the
  lower index (``lax.top_k``'s order); (5) causal softmax over the tokens
  of the chosen blocks.  The rule is PER TOKEN, so this one full forward
  and a prefill in chunks are the same function (the public code decides
  once a forward call, by the call's key length).
* compared (``served_gaps``): how far a served token's logit lies below
  the reference's best at its position, relative to the largest magnitude
  there, as the mean over blocks of 256 consecutive served tokens
  (``nemotron_h``'s comparison, ``block_means``; the reason here: four layers each choose
  ~30 of ~190 blocks by scores over mean-pooled keys that lie close
  together, so in bfloat16 some blocks are chosen otherwise than here and
  single tokens read the choice's discontinuity, not the arithmetic).

Departures and assumptions, each also under ``assumed`` in the
configuration file: the residual stream is float32; the decay slopes, the
output norm's span and the gate's shape are the family's (the config gives
none); ``sparse_config`` is MiniCPM4's published one (the catalog's row
confirms the top-64); the dense/sparse rule is per token.  The layer is
written from the issue's equations: no network here to read the public
modelling code against them.

Two controls: ``control_gaps`` computes the forward with every product's
operands rounded to a lower precision (float8, as in every cell), and with
``control_precision="bfloat16_state"`` keeps the products in float32 but
rounds the matrix state to bfloat16 after every token.

Weights live in one flat canonical tree (``l<i>.<leaf>``) in the dtype they
are served in; each matrix is upcast inside its own product; the MLP and
the sparse attention run over the tokens in blocks and the head over the
vocabulary in blocks (``lfm2._head``), so that a 12k-token request fits
beside a serving program that fills the chip.  Imports nothing of the
program.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .common import Arith, seed_key
from .dots3 import rms_norm, swiglu
from .lfm2 import HEAD_BLOCK, _gaps, _head, _layer_weights
from .nemotron_h import block_means

LINEAR, SPARSE = "lightning-attn", "minicpm4"
# sequences are padded to a multiple of this: two lengths cover every
# request of the cell (11,777-12,288 + 64-768 tokens)
PAD = 1024
MLP_BLOCK = 1024        # tokens of the MLP held at once
QUERY_BLOCK = 128       # queries of a sparse layer held at once
STATE_CONTROL = "bfloat16_state"


def layer_kinds(cfg: dict) -> list:
    return list(cfg["mixer_types"][:cfg["num_hidden_layers"]])


def published_index(cfg: dict, i: int) -> int:
    return int(cfg.get("first_published_layer", 0)) + i


def published_depth(cfg: dict) -> int:
    return int(cfg.get("num_hidden_layers_published",
                       cfg["num_hidden_layers"]))


def residual_scale(cfg: dict) -> float:
    return cfg["scale_depth"] / math.sqrt(published_depth(cfg))


def logit_divisor(cfg: dict) -> float:
    return cfg["hidden_size"] / cfg["dim_model_base"]


def decay_slopes(cfg: dict, i: int):
    """``s_h`` of kept layer ``i``, float32 ``[lightning_nh]``."""
    H = cfg["lightning_nh"]
    h = jnp.arange(1, H + 1, dtype=jnp.float32)
    return 2.0 ** (-8.0 * h / H) * jnp.float32(
        1.0 - published_index(cfg, i) / (published_depth(cfg) - 1) + 1e-5)


def leaf_shapes(cfg: dict) -> dict:
    """{leaf: (shape, init std or None for a gain of ones)} of the
    canonical tree; each matrix at 1 / sqrt(fan_in) against an input of
    unit scale, as the other references'."""
    h, V, F = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    H, KV, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    LH, Ld = cfg["lightning_nh"], cfg["lightning_head_dim"]
    out = {"embed": ((V, h), h ** -0.5), "head": ((V, h), h ** -0.5),
           "norm_f": ((h,), None)}
    for i, kind in enumerate(layer_kinds(cfg)):
        L = {"op_norm": ((h,), None), "ffn_norm": ((h,), None),
             "ffn_g": ((h, F), h ** -0.5), "ffn_u": ((h, F), h ** -0.5),
             "ffn_d": ((F, h), F ** -0.5)}
        if kind == LINEAR:
            inner = LH * Ld
            L.update({"q": ((h, inner), h ** -0.5),
                      "k": ((h, inner), h ** -0.5),
                      "v": ((h, inner), h ** -0.5),
                      "gate": ((h, inner), h ** -0.5),
                      "o": ((inner, h), inner ** -0.5),
                      "q_norm": ((Ld,), None), "k_norm": ((Ld,), None),
                      "o_norm": ((inner,), None)})
        elif kind == SPARSE:
            L.update({"q": ((h, H * d), h ** -0.5),
                      "k": ((h, KV * d), h ** -0.5),
                      "v": ((h, KV * d), h ** -0.5),
                      "gate": ((h, H * d), h ** -0.5),
                      "o": ((H * d, h), (H * d) ** -0.5),
                      "q_norm": ((d,), None), "k_norm": ((d,), None)})
        else:
            raise ValueError(f"mixer_types[{i}] = {kind!r}")
        out.update({f"l{i}.{k}": v for k, v in L.items()})
    return out


def init_weights(cfg: dict, seed: int, dtype=None):
    """The canonical tree from ``seed``, made on the device in the served
    dtype."""
    dtype = jnp.dtype(dtype or cfg.get("dtype", "bfloat16"))
    leaves = sorted(leaf_shapes(cfg).items())

    def draw(key, std, shape, dt):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dt)

    def leaf(key, i, shape, std, draw=draw):
        if std is None:
            return jnp.ones(shape, dtype)
        return draw(jax.random.fold_in(key, i), jnp.float32(std), shape,
                    dtype)

    key = seed_key(seed)
    if sum(math.prod(s) for _, (s, _) in leaves) < 2 ** 26:
        # a test's size: one program for the whole tree (the same numbers)
        return jax.jit(lambda k: {n: leaf(k, i, s, sd) for i, (n, (s, sd))
                                  in enumerate(leaves)})(key)
    # the real size: one leaf at a time, one program a shape
    one = jax.jit(draw, static_argnums=(2, 3))
    return {n: leaf(key, i, s, sd, one) for i, (n, (s, sd))
            in enumerate(leaves)}


# -- the pieces ----------------------------------------------------------------

def rope_at(x, base):
    """``x [T, H, d]`` rotated by its own positions 0..T-1 over the whole
    head, halves paired."""
    d = x.shape[-1]
    inv = jnp.float32(base) ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv)[:, None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def recurrence(q, k, v, slopes, state_dtype=None, final_state=False):
    """``o_t = q_t S_t`` of ``S_t = exp(-s) S_{t-1} + k_t^T v_t`` from ``S
    = 0``, token by token: ``q``, ``k``, ``v [T, H, d]``, ``slopes [H]``,
    all float32 (the scale is the caller's).  ``state_dtype`` rounds the
    state after every token (the state control); ``final_state`` hands
    back ``(o, S_T [H, d, d])``."""
    decay = jnp.exp(-slopes)[:, None, None]

    def token(S, inp):
        qt, kt, vt = inp
        S = decay * S + kt[:, :, None] * vt[:, None, :]
        if state_dtype is not None:
            S = S.astype(state_dtype).astype(jnp.float32)
        return S, jnp.einsum("hk,hkv->hv", qt, S,
                             precision=jax.lax.Precision.HIGHEST)

    H, d = q.shape[1], q.shape[2]
    S, o = jax.lax.scan(token, jnp.zeros((H, d, d), jnp.float32), (q, k, v))
    return (o, S) if final_state else o


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision",
                                             "state_dtype"))
def _lightning(x, lw, slopes, *, cfg_key, precision, state_dtype=None):
    cfg = dict(cfg_key)
    ar, eps = Arith(precision), cfg["rms_norm_eps"]
    T, H, d = x.shape[0], cfg["lightning_nh"], cfg["lightning_head_dim"]
    u = rms_norm(x, lw["op_norm"], eps)

    def heads(name, norm=None):
        y = ar.einsum("th,hk->tk", u, lw[name]).reshape(T, H, d)
        if norm is None:
            return y
        return rope_at(rms_norm(y, lw[norm], eps), cfg["rope_theta"])
    o = recurrence(heads("q", "q_norm"), heads("k", "k_norm"), heads("v"),
                   slopes, state_dtype) * d ** -0.5
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
    o = o.reshape(T, H * d) * lw["o_norm"].astype(jnp.float32) \
        * jax.nn.sigmoid(ar.einsum("th,hk->tk", u, lw["gate"]))
    return x + cfg["r"] * ar.einsum("tk,kh->th", o, lw["o"])


def pooled_keys(k, kernel, stride):
    """``c_j = mean(k[stride j : stride j + kernel])`` for every window
    that lies inside ``k [T, d]``: ``[J, d]``."""
    J = max((k.shape[0] - kernel) // stride + 1, 0)
    at = jnp.arange(J)[:, None] * stride + jnp.arange(kernel)[None, :]
    return k[at].mean(1)


def chosen_blocks(ar, q, c, t, sp):
    """The blocks the queries ``q [rep, Q, d]`` of one cached head, at
    positions ``t [Q]``, read of their contexts past ``dense_len``:
    membership ``[Q, nb]`` over blocks of ``block_size`` tokens (steps 2 to
    4 of the module docstring); ``c [J, d]`` the head's pooled keys."""
    ks, st, bs = sp["kernel_size"], sp["kernel_stride"], sp["block_size"]
    d, J = q.shape[-1], c.shape[0]
    n = t + 1                                                     # [Q]
    nb = -(-(int(sp["positions"])) // bs)
    j = jnp.arange(J)
    inside = (st * j + ks)[None, :] <= n[:, None]                 # [Q, J]
    s = ar.einsum("rqd,jd->rqj", q, c) * d ** -0.5
    p = jax.nn.softmax(jnp.where(inside[None], s, -1e30), -1)
    a = jnp.where(inside, p.sum(0), 0.0)                          # [Q, J]
    b = jnp.arange(nb)
    overlap = (st * j[None, :] < bs * (b[:, None] + 1)) \
        & (st * j[None, :] + ks > bs * b[:, None])                # [nb, J]
    score = jnp.max(jnp.where(overlap[None] & inside[:, None, :],
                              a[:, None, :], -jnp.inf), -1)       # [Q, nb]
    valid = (b[None, :] * bs) < n[:, None]
    forced = (b[None, :] < sp["init_blocks"]) \
        | (b[None, :] >= (jnp.maximum(n - sp["window_size"], 0)
                          // bs)[:, None])
    score = jnp.where(valid, jnp.where(forced, jnp.inf, score), -jnp.inf)
    _, top = jax.lax.top_k(score, min(sp["topk"], nb))
    member = jnp.zeros(score.shape, bool).at[
        jnp.arange(score.shape[0])[:, None], top].set(True)
    return member & valid


@functools.partial(jax.jit, static_argnames=("cfg_key", "sparse_key",
                                             "precision"))
def _sparse(x, lw, *, cfg_key, sparse_key, precision):
    cfg, sp = dict(cfg_key), dict(sparse_key)
    ar, eps = Arith(precision), cfg["rms_norm_eps"]
    T = x.shape[0]
    H, KV, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    rep, bs = H // KV, sp["block_size"]
    sp["positions"] = T
    u = rms_norm(x, lw["op_norm"], eps)
    q = rms_norm(ar.einsum("th,hk->tk", u, lw["q"]).reshape(T, H, d),
                 lw["q_norm"], eps)
    k = rms_norm(ar.einsum("th,hk->tk", u, lw["k"]).reshape(T, KV, d),
                 lw["k_norm"], eps)
    v = ar.einsum("th,hk->tk", u, lw["v"]).reshape(T, KV, d)
    q = jnp.moveaxis(q.reshape(T, KV, rep, d), 0, 2)      # [KV, rep, T, d]
    c = [pooled_keys(k[:, g], sp["kernel_size"], sp["kernel_stride"])
         for g in range(KV)]
    QB = math.gcd(QUERY_BLOCK, T)
    cols = jnp.arange(T)

    def block(i):
        t = i * QB + jnp.arange(QB)
        dense = (t + 1 <= sp["dense_len"])[:, None]
        causal = cols[None, :] <= t[:, None]                      # [QB, T]
        out = []
        for g in range(KV):
            qg = jax.lax.dynamic_slice_in_dim(q[g], i * QB, QB, 1)
            member = chosen_blocks(ar, qg, c[g], t, sp)           # [QB, nb]
            keep = causal & (dense | member[:, cols // bs])
            s = ar.einsum("rqd,sd->rqs", qg, k[:, g]) * d ** -0.5
            p = jax.nn.softmax(jnp.where(keep[None], s, -1e30), -1)
            out.append(ar.einsum("rqs,sd->rqd", p, v[:, g]))
        return jnp.stack(out)                             # [KV, rep, QB, d]

    o = jax.lax.map(block, jnp.arange(T // QB))       # [n, KV, rep, QB, d]
    o = jnp.moveaxis(o, 3, 1).reshape(T, H * d)
    o = o * jax.nn.sigmoid(ar.einsum("th,hk->tk", u, lw["gate"]))
    return x + cfg["r"] * ar.einsum("tk,kh->th", o, lw["o"])


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _mlp(x, lw, *, cfg_key, precision):
    cfg = dict(cfg_key)
    ar = Arith(precision)
    T = x.shape[0]
    B = math.gcd(MLP_BLOCK, T)

    def block(xb):
        u = rms_norm(xb, lw["ffn_norm"], cfg["rms_norm_eps"])
        return xb + cfg["r"] * swiglu(ar, u, lw["ffn_g"], lw["ffn_u"],
                                      lw["ffn_d"])
    return jax.lax.map(block, x.reshape(T // B, B, -1)).reshape(x.shape)


def _cfg_key(cfg: dict):
    """The numbers of the config the jitted pieces need, hashable, with
    the residual scale ``r`` among them."""
    return tuple(sorted(
        [(k, v) for k, v in cfg.items()
         if isinstance(v, (int, float, bool, str))]
        + [("r", residual_scale(cfg))]))


def _sparse_key(cfg: dict):
    return tuple(sorted(cfg["sparse_config"].items()))


def _products_in(precision):
    return "float32" if precision == STATE_CONTROL else precision


def _hidden(cfg, w, ids, precision):
    """The stream ``[T, hidden]`` after the last kept layer of the full
    causal forward over ``ids [T]`` (a multiple of the blocks): one layer,
    one piece at a time."""
    key, skey = _cfg_key(cfg), _sparse_key(cfg)
    state_dtype = jnp.bfloat16 if precision == STATE_CONTROL else None
    precision = _products_in(precision)
    x = w["embed"][ids].astype(jnp.float32) * cfg["scale_emb"]
    for i, kind in enumerate(layer_kinds(cfg)):
        lw = _layer_weights(w, i)
        if kind == LINEAR:
            x = _lightning(x, lw, decay_slopes(cfg, i), cfg_key=key,
                           precision=precision, state_dtype=state_dtype)
        else:
            x = _sparse(x, lw, cfg_key=key, sparse_key=skey,
                        precision=precision)
        x = _mlp(x, lw, cfg_key=key, precision=precision)
    return x


def _padded(cfg, prompt, served):
    """prompt + served tokens right-padded to a multiple of ``PAD``
    (causal, and every rule above is per token, so padding changes nothing:
    a padded token only follows), and the positions that produced each
    served token (as many as ``max_new_tokens``, so that one program serves
    every request)."""
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
    ``pick`` defaults to the served tokens.  (The logits' divisor scales
    every logit alike, so the head's blocked statistics take the normed
    state's gain divided by it.)"""
    import numpy as np
    padded, at, n = _padded(cfg, prompt, served)
    x = _hidden(cfg, w, jnp.asarray(padded), precision)
    picks = np.zeros((at.shape[0],), np.int32)
    picks[:n] = np.asarray(served if pick is None else pick,
                           np.int32).reshape(-1)
    stats = _head(x, w["norm_f"].astype(jnp.float32) / logit_divisor(cfg),
                  w["head"], jnp.asarray(at, jnp.int32), jnp.asarray(picks),
                  eps=cfg["rms_norm_eps"], precision=_products_in(precision),
                  block=min(HEAD_BLOCK, w["head"].shape[0]))
    return tuple(s[:n] for s in stats)


def served_logits(cfg: dict, w, prompt, served, precision="float32"):
    """Logits ``[len(served), vocab]`` at the positions that produced each
    served token, whole: for the tests' small sizes."""
    padded, at, n = _padded(cfg, prompt, served)
    xn = rms_norm(_hidden(cfg, w, jnp.asarray(padded), precision),
                  w["norm_f"], cfg["rms_norm_eps"])[at[:n]]
    return Arith(_products_in(precision)).einsum(
        "th,vh->tv", xn / logit_divisor(cfg), w["head"])


def token_gaps(cfg: dict, w, prompt, served, precision="float32", pick=None):
    """Per served token: how far the reference logit of ``pick`` (the
    served token by default) lies below the reference's best at its
    position, relative to max|logit| there."""
    return _gaps(_served(cfg, w, prompt, served, precision, pick))


def served_gaps(cfg: dict, w, prompt, served, precision="float32"):
    """``token_gaps`` as the mean over each block of 256 consecutive
    served tokens (why blocks: the module docstring); one
    printed line gives the request's mean and the single tokens' widest
    beside it."""
    gaps = token_gaps(cfg, w, prompt, served, precision)
    out = block_means(gaps)
    print(f"reference gaps: {out.size} blocks of {gaps.shape[0]} tokens, "
          f"widest {float(out.max()):.5f}, mean {float(gaps.mean()):.5f}, "
          f"widest of 64-token blocks {float(block_means(gaps, 64).max()):.5f}"
          f", widest token {float(gaps.max()):.5f}", flush=True)
    return out


def control_gaps(cfg: dict, w, prompt, served, control_precision):
    """A control: the same block means for the tokens that the lower
    precision (``float8_e4m3``, ``bfloat16``, or ``bfloat16_state``: the
    matrix state alone rounded after every token) puts first at each
    position of the same prompt and tokens, under the float32 reference."""
    pick = _served(cfg, w, prompt, served, control_precision)[3]
    return block_means(token_gaps(cfg, w, prompt, served, pick=pick))
