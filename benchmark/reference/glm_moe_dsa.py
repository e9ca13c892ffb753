"""Plain reference of GLM-5's language model (``model_type``
``glm_moe_dsa``, https://huggingface.co/zai-org/GLM-5/blob/main/
config.json): the full forward over a prompt with its served tokens,
float32 at precision "highest", no cache, no batching, no kernels.

The equations (ISSUE 38, section 1: the DeepSeek-V3.2 form, which the
config's keys spell out), as computed here, ``n(.)`` an RMSNorm:

* block: ``h = x + Attn(n(x))``, ``y = h + FFN(n(h))``; final RMSNorm;
  untied head over the held slice of the vocabulary, in blocks of
  ``HEAD_BLOCK`` vocabulary rows.
* latent attention in the NON-absorbed, per-head form, every layer alike:
  ``c_q = n(W_qa x)``, ``[q_n | q_r] = W_qb c_q`` (192 | 64 a head), ``[c |
  k_r] = W_kva x``, ``c_kv = n(c)``; per head (one at a time, the queries
  in blocks of ``QUERY_BLOCK``) ``k_n = W_uk c_kv`` (192), ``v = W_uv
  c_kv`` (256: wider than the key's content part); score ``(q_n.k_n +
  rope(q_r).rope(k_r)) x (d_n + d_r)^-0.5`` over the SELECTED causal
  columns; ``W_o`` applied head by head and summed.  No gate, no rescale of
  the latents, no bias, plain rotary (``rope_parameters``: theta 1e6,
  ``rope_type`` default).
* the selector, on EVERY layer: ``q_I = W_qI c_q`` (32 x 128), ``k_I =
  LayerNorm(W_kI x)`` (128), rotary on the first ``qk_rope_head_dim`` of
  both, ``w = W_w x * 32^-0.5 * 128^-0.5``, ``I(t, s) = sum_j w_j(t)
  relu(q_I,j(t) . k_I(s))`` in float32; allowed = the ``index_topk`` causal
  columns of largest ``I`` (the lower column first among equals), all of
  them while fewer exist (``dots3.selected``, a query block at a time).
* FFN: the leading ``first_k_dense_replace`` layers a dense SwiGLU; then
  ``s = sigmoid(W_r u)`` over all 256, chosen = top 8 of ``s + b``
  (``noaux_tc``; ``n_group`` 1, ``topk_group`` 1: no group limit), weights
  ``s_i / sum_chosen s`` times ``routed_scaling_factor``; THE SHARE: only
  experts ``experts_held`` exist here, an assignment to an absent one adds
  nothing; plus the shared expert (:func:`moe_parts`; :func:`route` says
  which other resolution of a token's top 8 the comparison also admits).

Assumed (also listed in the configuration file): the rotary pairs the two
halves of the rotated features (``rope_interleave`` and
``indexer_rope_interleave`` pair adjacent ones: with random weights a
renaming of features); positions count from 0 at a request's first token;
the ``noaux_tc`` correction is drawn N(0, 0.01); the selector follows
DeepSeek-V3.2's published inference code without its Hadamard rotation
(orthogonal: it cancels in ``q_I . k_I``) and without fp8 keys.  Left out:
the multi-token-prediction module (the main model's logits do not depend
on it).

Weights live in one flat canonical tree (``l<i>.<leaf>``) in the dtype they
are served in; each matrix is upcast inside its own product, and the
queries and the output projection go head by head, so that no float32
``[T, 64 x 256]`` array is ever held: at 16k tokens the reference fits
beside a serving program's state.  Imports nothing of the program.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .common import Arith, layer_norm, seed_key
from .dots3 import rms_norm, selected, swiglu, tolerant_gaps
from .kimi_k2 import _dense_ffn, _gaps, _head, _layer_weights, dims, rope

QUERY_BLOCK = 256       # queries whose [block, T] scores are held at once
PAD = 256
TIE_RANKS = 4           # experts past the top k that a near-tie may reach
# under which margin (in ``s + b``) a choice counts as tied.  The served
# bfloat16 program was seen to take the other expert at margins of 0.0002
# .. 0.0017 (nine tokens) and twice at one of 0.0024 / 0.0043 and 0.0032 /
# 0.0041 (two choices of the token both that near), in 16 requests; under
# ``dots3``'s 2e-3 those two read 0.068 and 0.036.  The float8 control
# still reads 0.063 and 0.088 under this one (PERF.md section 2)
TIE = 5e-3


def rope_theta(cfg: dict) -> float:
    return float(cfg["rope_parameters"]["rope_theta"])


def leaf_shapes(cfg: dict) -> dict:
    """{leaf: (shape, init std; None for a gain, 0.0 for a bias)} of the
    canonical tree.  Each matrix is drawn at 1 / sqrt(fan_in), its input
    having unit RMS, so every product's output has unit scale (as
    ``kimi_k2.leaf_shapes``; the selector's five leaves as ``dots3``'s)."""
    h, V = cfg["hidden_size"], cfg["vocab_size"]
    E, Fe = cfg["n_routed_experts_published"], cfg["moe_intermediate_size"]
    lo, hi = cfg["experts_held"]
    d = dims(cfg)
    H, dn, dr, dv, rq, rkv = (d[k] for k in ("H", "dn", "dr", "dv", "rq",
                                             "rkv"))
    J, D = cfg["index_n_heads"], cfg["index_head_dim"]
    out = {"embed": ((V, h), 1.0), "head": ((h, V), h ** -0.5),
           "norm_f": ((h,), None)}
    for i in range(cfg["num_hidden_layers"]):
        L = {"in_norm": ((h,), None), "post_norm": ((h,), None),
             "q_a": ((h, rq), h ** -0.5), "q_a_norm": ((rq,), None),
             "q_b": ((rq, H * (dn + dr)), rq ** -0.5),
             "kv_a": ((h, rkv + dr), h ** -0.5), "kv_a_norm": ((rkv,), None),
             "w_uk": ((H, rkv, dn), rkv ** -0.5),
             "w_uv": ((H, rkv, dv), rkv ** -0.5),
             "o": ((H * dv, h), (H * dv) ** -0.5),
             "idx_q": ((rq, J * D), rq ** -0.5),
             "idx_k": ((h, D), h ** -0.5),
             "idx_k_g": ((D,), None), "idx_k_b": ((D,), 0.0),
             "idx_w": ((h, J), h ** -0.5)}
        if i < cfg["first_k_dense_replace"]:
            F = cfg["intermediate_size"]
            L.update({"ffn_g": ((h, F), h ** -0.5), "ffn_u": ((h, F), h ** -0.5),
                      "ffn_d": ((F, h), F ** -0.5)})
        else:
            n, Fs = hi - lo, Fe * cfg["n_shared_experts"]
            L.update({"router": ((h, E), h ** -0.5),
                      "router_b": ((E,), 0.01),
                      "exp_g": ((n, h, Fe), h ** -0.5),
                      "exp_u": ((n, h, Fe), h ** -0.5),
                      "exp_d": ((n, Fe, h), Fe ** -0.5),
                      "sh_g": ((h, Fs), h ** -0.5), "sh_u": ((h, Fs), h ** -0.5),
                      "sh_d": ((Fs, h), Fs ** -0.5)})
        out.update({f"l{i}.{k}": v for k, v in L.items()})
    return out


def init_weights(cfg: dict, seed: int, dtype=None):
    """The canonical tree from ``seed``, made on the device in the served
    dtype, one leaf at a time at the real size (one program a shape);
    ``router_b`` (the ``noaux_tc`` correction, used to choose only) stays
    float32."""
    dtype = jnp.dtype(dtype or cfg.get("dtype", "bfloat16"))
    leaves = sorted(leaf_shapes(cfg).items())

    def draw(key, std, shape, dt):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dt)

    def leaf(key, i, name, shape, std, draw=draw):
        if not std:
            return (jnp.ones if std is None else jnp.zeros)(shape, dtype)
        dt = jnp.float32 if name.endswith("router_b") else dtype
        return draw(jax.random.fold_in(key, i), jnp.float32(std), shape, dt)

    key = seed_key(seed)
    if sum(math.prod(s) for _, (s, _) in leaves) < 2 ** 26:
        return jax.jit(lambda k: {n: leaf(k, i, n, s, sd) for i, (n, (s, sd))
                                  in enumerate(leaves)})(key)
    one = jax.jit(draw, static_argnums=(2, 3))
    return {n: leaf(key, i, n, s, sd, one) for i, (n, (s, sd))
            in enumerate(leaves)}


# -- the pieces ----------------------------------------------------------------

def rope_first(x, inv):
    """``x [T, d]`` or ``[T, J, d]``: rotary on its first ``2 len(inv)``
    features, the rest as they are (the selector's queries and key)."""
    d = 2 * inv.shape[0]
    return jnp.concatenate([rope(x[..., :d], inv), x[..., d:]], -1)


def selector_parts(ar, xn, c_q, lw, cfg, inv):
    """The selector's per-token pieces: queries ``[T, J, D]``, head weights
    ``[T, J]`` and keys ``[T, D]``, rotary on the first features."""
    T = xn.shape[0]
    J, D = cfg["index_n_heads"], cfg["index_head_dim"]
    qi = rope_first(ar.einsum("tr,rk->tk", c_q, lw["idx_q"])
                    .reshape(T, J, D), inv)
    ki = rope_first(layer_norm(ar.einsum("th,hd->td", xn, lw["idx_k"]),
                               lw["idx_k_g"], lw["idx_k_b"],
                               cfg["rms_norm_eps"]), inv)
    wi = ar.einsum("th,hj->tj", xn, lw["idx_w"]) * (J ** -0.5) * (D ** -0.5)
    return qi, wi, ki


def selector_scores(ar, qi, wi, ki):
    """``I [t, T] = sum_j w_j relu(q_j . k)`` of the queries ``qi [t, J,
    D]``, ``wi [t, J]`` against every key ``ki [T, D]``: one selector head
    at a time, float32."""
    def head(acc, jw):
        qj, wj = jw
        return acc + wj[:, None] * jax.nn.relu(
            ar.einsum("td,sd->ts", qj, ki)), None

    acc, _ = jax.lax.scan(
        head, jnp.zeros((qi.shape[0], ki.shape[0]), jnp.float32),
        (jnp.swapaxes(qi, 0, 1), wi.T))
    return acc


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def selection(xn, c_q, lw, inv, *, cfg_key, precision="float32"):
    """Membership ``[T, T]`` (bool) of the selected causal columns of every
    query, a block of ``QUERY_BLOCK`` queries at a time."""
    cfg, ar, T = dict(cfg_key), Arith(precision), xn.shape[0]
    B = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    qi, wi, ki = selector_parts(ar, xn, c_q, lw, cfg, inv)
    s_all = jnp.arange(T)

    def block(qb):
        q, w, t = qb
        return selected(selector_scores(ar, q, w, ki),
                        s_all[None, :] <= t[:, None], cfg["index_topk"])

    return jax.lax.map(block, (
        qi.reshape((T // B, B) + qi.shape[1:]), wi.reshape(T // B, B, -1),
        s_all.reshape(T // B, B))).reshape(T, T)


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _attention(x, lw, inv, *, cfg_key, precision):
    cfg = dict(cfg_key)
    ar, d = Arith(precision), dims(cfg)
    H, dn, dr, dv, rq, rkv = (d[k] for k in ("H", "dn", "dr", "dv", "rq",
                                             "rkv"))
    eps, T, scale = cfg["rms_norm_eps"], x.shape[0], (dn + dr) ** -0.5
    xn = rms_norm(x, lw["in_norm"], eps)
    c_q = rms_norm(ar.einsum("th,hr->tr", xn, lw["q_a"]), lw["q_a_norm"], eps)
    kv = ar.einsum("th,hk->tk", xn, lw["kv_a"])
    c_kv = rms_norm(kv[:, :rkv], lw["kv_a_norm"], eps)
    k_r = rope(kv[:, rkv:], inv)
    allowed = selection(xn, c_q, lw, inv, cfg_key=cfg_key,
                        precision=precision)
    B = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T

    def head(acc, args):
        wq, wuk, wuv, wo = args                     # ONE head
        q = ar.einsum("tr,rk->tk", c_q, wq)
        q_n, q_r = q[:, :dn], rope(q[:, dn:], inv)
        k_n = ar.einsum("sr,rd->sd", c_kv, wuk)
        v = ar.einsum("sr,rv->sv", c_kv, wuv)

        def block(qb):
            qn_b, qr_b, keep = qb
            s = (ar.einsum("td,sd->ts", qn_b, k_n)
                 + ar.einsum("td,sd->ts", qr_b, k_r)) * scale
            p = jax.nn.softmax(jnp.where(keep, s, -1e30), -1)
            return ar.einsum("ts,sv->tv", p, v)

        o = jax.lax.map(block, (q_n.reshape(T // B, B, dn),
                                q_r.reshape(T // B, B, dr),
                                allowed.reshape(T // B, B, T)))
        return acc + ar.einsum("tv,vh->th", o.reshape(T, dv), wo), None

    per_head = lambda w, n: jnp.moveaxis(                        # noqa: E731
        w.reshape(w.shape[0], H, n), 1, 0)
    out, _ = jax.lax.scan(
        head, jnp.zeros(x.shape, jnp.float32),
        (per_head(lw["q_b"], dn + dr), lw["w_uk"], lw["w_uv"],
         lw["o"].reshape(H, dv, x.shape[1])))
    return x + out


# -- the routing, with its near-ties -------------------------------------------
#
# ``dots3.route`` gives a token ONE other resolution of its top-k: the last
# expert chosen against the first one left out, and only where one of the
# two is held here.  With 16 of 256 experts held that leaves a near-tie of
# THREE unseen: the two experts next to the boundary both absent and a held
# one just beyond them (chosen just above, or left out just below).  A
# bfloat16 program that orders those three otherwise adds or drops a whole
# held expert, and no margin said so: served tokens 0.036-0.068 under the
# reference's best whose four margins were 0.004 and more or infinite
# (PERF.md section 6, PR 38: one run in five read over the limit).  Here
# the other resolution is the CLOSEST pair across the boundary, one chosen
# and one of the ``TIE_RANKS`` next, in which an expert held here takes
# part; where the adjacent pair has one it is that pair, ``dots3.route``'s.

def route(ar, u, lw, cfg, held, flip=None):
    """(chosen ids ``[T, k]``, weights ``[T, k]``, margin ``[T]``) over the
    router's whole published width.  ``margin``: by how much the chosen
    expert of the closest such pair lies above the unchosen one, in the
    units they are chosen by (``s + b``); infinite where no pair has an
    expert among ``held = (lo, hi)``: swapping moves nothing that is
    computed here.  ``flip [T]`` marks the tokens that take that pair's
    unchosen expert in place of its chosen one."""
    k, X = cfg["num_experts_per_tok"], TIE_RANKS
    s = jax.nn.sigmoid(ar.einsum("th,he->te", u, lw["router"]))
    top, ids = jax.lax.top_k(s + lw["router_b"].astype(jnp.float32), k + X)
    here = (ids >= held[0]) & (ids < held[1])
    apart = jnp.where(here[:, :k, None] | here[:, None, k:],
                      top[:, :k, None] - top[:, None, k:], jnp.inf)
    flat = apart.reshape(apart.shape[0], k * X)
    pair = jnp.argmin(flat, -1)
    margin = jnp.take_along_axis(flat, pair[:, None], 1)[:, 0]
    chosen = ids[:, :k]
    if flip is not None:
        other = jnp.take_along_axis(ids[:, k:], (pair % X)[:, None], 1)
        swap = flip[:, None] & (jnp.arange(k)[None, :] == (pair // X)[:, None])
        chosen = jnp.where(swap, other, chosen)
    w = jnp.take_along_axis(s, chosen, 1)
    if cfg["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    return chosen, w * cfg["routed_scaling_factor"], margin


def moe_parts(ar, u, lw, cfg, held, flip=None):
    """(the routed part of the experts ``held = (lo, hi)`` whose weights
    ``lw`` holds, the shared expert's part, the routing's ``margin``) for
    the normed tokens ``u``: one expert at a time, each token weighted by
    its routing weight for that expert, 0 where it was not chosen
    (``dots3.moe_parts`` over this file's :func:`route`)."""
    lo = held[0]                      # (may be traced: hi - lo is the
    n = lw["exp_g"].shape[0]          # number of experts ``lw`` holds)
    ids, w, margin = route(ar, u, lw, cfg, (lo, lo + n), flip)

    def one(acc, ew):
        e, wg, wu, wd = ew
        we = jnp.sum(jnp.where(ids == e, w, 0.0), -1)
        return acc + we[:, None] * swiglu(ar, u, wg, wu, wd), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros(u.shape, jnp.float32),
        (lo + jnp.arange(n), lw["exp_g"], lw["exp_u"], lw["exp_d"]))
    return routed, swiglu(ar, u, lw["sh_g"], lw["sh_u"], lw["sh_d"]), margin


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _moe_ffn(x, lw, flip, *, cfg_key, precision):
    cfg = dict(cfg_key)
    u = rms_norm(x, lw["post_norm"], cfg["rms_norm_eps"])
    routed, shared, margin = moe_parts(
        Arith(precision), u, lw, cfg, tuple(cfg["experts_held"]), flip)
    return x + routed + shared, margin


def _cfg_key(cfg: dict):
    """The numbers of the config the jitted pieces need, hashable."""
    keep = {k: v for k, v in cfg.items()
            if isinstance(v, (int, float, bool, str))}
    keep["experts_held"] = tuple(cfg["experts_held"])
    return tuple(sorted(keep.items()))


def rotary_frequencies(cfg: dict):
    d = cfg["qk_rope_head_dim"]
    return jnp.asarray(rope_theta(cfg) ** (
        -np.arange(0, d, 2, dtype=np.float64) / d), jnp.float32)


def _logits_at(cfg, w, ids, at, precision, flips=None):
    """(logits ``[len(at), vocab]``, the MoE layers' routing margins
    ``[layers, len(at)]``) of the full causal forward over ``ids [T]`` at
    the positions ``at``: one layer, one piece at a time.  ``flips
    [layers, T]`` marks, per MoE layer, the tokens that resolve their
    nearest top-k choice the other way (:func:`route`)."""
    key, inv = _cfg_key(cfg), rotary_frequencies(cfg)
    x = w["embed"][ids].astype(jnp.float32)
    none, margins = jnp.zeros(ids.shape, bool), []
    for i in range(cfg["num_hidden_layers"]):
        lw = _layer_weights(w, i)
        x = _attention(x, lw, inv, cfg_key=key, precision=precision)
        if i < cfg["first_k_dense_replace"]:
            x = _dense_ffn(x, lw, cfg_key=key, precision=precision)
            continue
        flip = none if flips is None else flips[len(margins)]
        x, margin = _moe_ffn(x, lw, flip, cfg_key=key, precision=precision)
        margins.append(margin[at])
    logits = _head(x, w["norm_f"], w["head"], at, eps=cfg["rms_norm_eps"],
                   precision=precision)
    return logits, (jnp.stack(margins) if margins
                    else jnp.zeros((0, at.shape[0]), jnp.float32))


def _served(cfg, w, prompt, served, precision, flips=None):
    """:func:`_logits_at` over prompt + served tokens, right-padded to a
    multiple of ``PAD`` (causal, so padding changes nothing), at the
    positions that produced each served token.  ``flips [layers,
    len(served)]`` is given by served token."""
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    served = np.asarray(served, np.int32).reshape(-1)
    ids = np.concatenate([prompt, served[:-1]])
    pad = min(PAD, int(cfg.get("reference_pad", PAD)))
    padded = np.zeros((-(-ids.size // pad) * pad,), np.int32)
    padded[:ids.size] = ids
    n_at = max(served.size, int(cfg.get("serve", {}).get("max_new_tokens", 0)))
    at = np.minimum(prompt.size - 1 + np.arange(n_at), ids.size - 1)
    if flips is not None:
        by_token = np.asarray(flips, bool)
        flips = np.zeros((by_token.shape[0], padded.size), bool)
        flips[:, at[:served.size]] = by_token
        flips = jnp.asarray(flips)
    logits, margins = _logits_at(cfg, w, jnp.asarray(padded),
                                 jnp.asarray(at, jnp.int32), precision, flips)
    return logits[:served.size], margins[:, :served.size]


def served_logits(cfg: dict, w, prompt, served, precision="float32"):
    """Logits ``[len(served), vocab]`` at the positions that produced each
    served token: one forward over prompt + served tokens."""
    return _served(cfg, w, prompt, served, precision)[0]


def _resolved(cfg, w, prompt, served, pick, precision, tie, log):
    """``pick``'s gaps under the reference at ``precision``, lowered to the
    least over the resolutions of the reference's own routing ties
    (``dots3.tolerant_gaps``; PERF.md section 2)."""
    def forward(flips=None):
        logits, margins = _served(cfg, w, prompt, served, precision, flips)
        return _gaps(logits, pick), margins
    return tolerant_gaps(*forward(), forward, tie, log=log)


def served_gaps(cfg: dict, w, prompt, served, precision="float32", tie=TIE,
                log=None):
    """For each served token: how far its reference logit lies below the
    reference's best at that position, relative to max|logit| there,
    under the resolution of that position's routing ties that puts it
    nearest."""
    pick = jnp.asarray(served, jnp.int32).reshape(-1)
    return _resolved(cfg, w, prompt, served, pick, precision, tie, log)


def control_gaps(cfg: dict, w, prompt, served, control_precision, tie=TIE,
                 log=None):
    """The control: at each position of the same prompt and tokens, the gap
    (under the float32 reference, its ties resolved as for a served token)
    of the token the lower precision puts first."""
    pick = jnp.argmax(served_logits(cfg, w, prompt, served,
                                    control_precision), -1)
    return _resolved(cfg, w, prompt, served, pick, "float32", tie, log)
