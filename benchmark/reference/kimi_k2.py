"""Plain reference of Kimi-K2.5's language model (``model_type``
``kimi_k2``, https://huggingface.co/moonshotai/Kimi-K2.5/blob/main/
config.json): the full forward over a prompt with its served tokens,
float32 at precision "highest", no cache, no batching, no kernels.

The equations (the DeepSeek-V3 form, which the config's keys spell out), as
computed here:

* block: ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; final
  RMSNorm; untied head over the held slice of the vocabulary, computed in
  blocks of ``HEAD_BLOCK`` vocabulary rows.
* latent attention in the NON-absorbed, per-head form, every layer alike:
  ``c_q = RMSNorm(W_qa x)``, ``[q_n | q_r] = W_qb c_q``, ``[c | k_r] =
  W_kva x``, ``c_kv = RMSNorm(c)``; per head (one at a time, the queries in
  blocks of ``QUERY_BLOCK``) ``k_n = W_uk c_kv``, ``v = W_uv c_kv``; score
  ``(q_n.k_n + rope(q_r).rope(k_r)) x scale`` over EVERY causal column (no
  selector, no window, no gate, no rescale of the latents); ``W_o``.
* YaRN, from the config's ``rope_scaling`` (``yarn_frequencies``): with
  ``d = qk_rope_head_dim``, ``f_i = rope_theta^(-2i/d)``, the dimension
  at which ``r`` turns fit ``original_max_position_embeddings`` positions
  ``dim(r) = d ln(original / (2 pi r)) / (2 ln rope_theta)``, ``low =
  floor(dim(beta_fast))``, ``high = ceil(dim(beta_slow))``, ``ramp_i =
  clip((i - low) / (high - low), 0, 1)``: ``f'_i = f_i (1 - ramp_i) + (f_i
  / factor) ramp_i``.  The cos/sin multiplier is ``mscale(factor, mscale) /
  mscale(factor, mscale_all_dim)`` = 1 here, and ``scale = (d_n + d_r)^-0.5
  x m^2`` with ``m = 0.1 mscale_all_dim ln(factor) + 1``.
* MoE (layers past ``first_k_dense_replace``): ``s = sigmoid(W_r u)`` over
  all 384, chosen = top 8 of ``s + b`` (``noaux_tc``; ``n_group`` 1 and
  ``topk_group`` 1: one group, no limit), weights ``s_i / sum_chosen s``
  times ``routed_scaling_factor``; THE SHARE: only experts ``experts_held``
  exist here, an assignment to an absent one adds nothing; plus the shared
  expert.  One expert at a time (``dots3.moe_parts``: the same routing).

Assumed (also listed in the configuration file): the rotary pairs the two
halves of the rotated features; positions count from 0 at a request's
first token; the ``noaux_tc`` correction is drawn N(0, 0.01).  Left out:
the vision tower (the catalog's config is the language model's).

Weights live in one flat canonical tree (``l<i>.<leaf>``) in the dtype they
are served in; each matrix is upcast inside its own product.  Imports
nothing of the program.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .common import Arith, seed_key
from .dots3 import (TIE, moe_parts, rms_norm, swiglu, tolerant_gaps)

QUERY_BLOCK = 256       # queries whose [block, T] scores are held at once
HEAD_BLOCK = 4096       # vocabulary rows of the head computed at once
FFN_BLOCKS = 8          # blocks of the dense FFN's width, one at a time
PAD = 256


def dims(cfg: dict) -> dict:
    return {"H": cfg["num_attention_heads"], "dn": cfg["qk_nope_head_dim"],
            "dr": cfg["qk_rope_head_dim"], "dv": cfg["v_head_dim"],
            "rq": cfg["q_lora_rank"], "rkv": cfg["kv_lora_rank"]}


def leaf_shapes(cfg: dict) -> dict:
    """{leaf: (shape, init std or None for a gain)} of the canonical tree.
    Each matrix is drawn at 1 / sqrt(fan_in), its input having unit RMS, so
    every product's output has unit scale (as ``dots3.leaf_shapes``)."""
    h, V = cfg["hidden_size"], cfg["vocab_size"]
    E, Fe = cfg["n_routed_experts_published"], cfg["moe_intermediate_size"]
    lo, hi = cfg["experts_held"]
    d = dims(cfg)
    H, dn, dr, dv, rq, rkv = (d[k] for k in ("H", "dn", "dr", "dv", "rq",
                                             "rkv"))
    out = {"embed": ((V, h), 1.0), "head": ((h, V), h ** -0.5),
           "norm_f": ((h,), None)}
    for i in range(cfg["num_hidden_layers"]):
        L = {"in_norm": ((h,), None), "post_norm": ((h,), None),
             "q_a": ((h, rq), h ** -0.5), "q_a_norm": ((rq,), None),
             "q_b": ((rq, H * (dn + dr)), rq ** -0.5),
             "kv_a": ((h, rkv + dr), h ** -0.5), "kv_a_norm": ((rkv,), None),
             "w_uk": ((H, rkv, dn), rkv ** -0.5),
             "w_uv": ((H, rkv, dv), rkv ** -0.5),
             "o": ((H * dv, h), (H * dv) ** -0.5)}
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
        if std is None:
            return jnp.ones(shape, dtype)
        dt = jnp.float32 if name.endswith("router_b") else dtype
        return draw(jax.random.fold_in(key, i), jnp.float32(std), shape, dt)

    key = seed_key(seed)
    if sum(math.prod(s) for _, (s, _) in leaves) < 2 ** 26:
        return jax.jit(lambda k: {n: leaf(k, i, n, s, sd) for i, (n, (s, sd))
                                  in enumerate(leaves)})(key)
    one = jax.jit(draw, static_argnums=(2, 3))
    return {n: leaf(key, i, n, s, sd, one) for i, (n, (s, sd))
            in enumerate(leaves)}


# -- YaRN, written out from the config's keys ----------------------------------

def yarn_frequencies(cfg: dict) -> np.ndarray:
    """The ``qk_rope_head_dim / 2`` rotary frequencies, float32: plain
    ``rope_theta^(-2i/d)`` without ``rope_scaling``, else blended with
    their ``1 / factor`` between the correction dimensions."""
    d, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    plain = base ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    sc = cfg.get("rope_scaling")
    if not sc:
        return plain.astype(np.float32)
    if sc["type"] != "yarn":
        raise ValueError(f"rope_scaling of type {sc['type']!r}")

    def dim_of(turns):
        return d * math.log(sc["original_max_position_embeddings"]
                            / (turns * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(dim_of(sc["beta_fast"])), 0)
    high = min(math.ceil(dim_of(sc["beta_slow"])), d - 1)
    if high == low:
        high = low + 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return (plain * (1.0 - ramp) + plain / sc["factor"] * ramp
            ).astype(np.float32)


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def softmax_scale(cfg: dict) -> float:
    """``(d_n + d_r)^-0.5``, times ``mscale(factor, mscale_all_dim)^2``
    under YaRN."""
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    sc = cfg.get("rope_scaling")
    if sc and sc.get("mscale_all_dim"):
        scale *= _mscale(sc["factor"], sc["mscale_all_dim"]) ** 2
    return scale


def rotary_multiplier(cfg: dict) -> float:
    """What YaRN multiplies cos and sin by: ``mscale(factor, mscale) /
    mscale(factor, mscale_all_dim)``."""
    sc = cfg.get("rope_scaling")
    if not sc:
        return 1.0
    return _mscale(sc["factor"], sc.get("mscale", 1)) \
        / _mscale(sc["factor"], sc.get("mscale_all_dim", 0))


def rope(x, inv, mult=1.0):
    """``x [T, d]`` or ``[T, H, d]`` rotated by its own positions 0..T-1
    at the frequencies ``inv [d / 2]`` (halves paired)."""
    d = x.shape[-1]
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    if x.ndim == 3:
        ang = ang[:, None, :]
    cos, sin = jnp.cos(ang) * mult, jnp.sin(ang) * mult
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# -- the pieces ----------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _attention(x, lw, inv, *, cfg_key, precision):
    cfg = dict(cfg_key)
    ar, d = Arith(precision), dims(cfg)
    H, dn, dr, dv, rkv = (d[k] for k in ("H", "dn", "dr", "dv", "rkv"))
    eps, T = cfg["rms_norm_eps"], x.shape[0]
    mult, scale = cfg["_rotary_multiplier"], cfg["_softmax_scale"]
    xn = rms_norm(x, lw["in_norm"], eps)
    c_q = rms_norm(ar.einsum("th,hr->tr", xn, lw["q_a"]), lw["q_a_norm"], eps)
    q = ar.einsum("tr,rk->tk", c_q, lw["q_b"]).reshape(T, H, dn + dr)
    q_n, q_r = q[..., :dn], rope(q[..., dn:], inv, mult)
    kv = ar.einsum("th,hk->tk", xn, lw["kv_a"])
    c_kv = rms_norm(kv[:, :rkv], lw["kv_a_norm"], eps)
    k_r = rope(kv[:, rkv:], inv, mult)
    B = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    s_all = jnp.arange(T)

    def head(args):
        qn, qr, wuk, wuv = args                     # ONE head
        k_n = ar.einsum("sr,rd->sd", c_kv, wuk)
        v = ar.einsum("sr,rv->sv", c_kv, wuv)

        def block(qb):
            qn_b, qr_b, t = qb
            s = (ar.einsum("td,sd->ts", qn_b, k_n)
                 + ar.einsum("td,sd->ts", qr_b, k_r)) * scale
            p = jax.nn.softmax(
                jnp.where(s_all[None, :] <= t[:, None], s, -1e30), -1)
            return ar.einsum("ts,sv->tv", p, v)

        o = jax.lax.map(block, (qn.reshape(T // B, B, dn),
                                qr.reshape(T // B, B, dr),
                                s_all.reshape(T // B, B)))
        return o.reshape(T, dv)

    o = jax.lax.map(head, (jnp.swapaxes(q_n, 0, 1), jnp.swapaxes(q_r, 0, 1),
                           lw["w_uk"], lw["w_uv"]))           # [H, T, dv]
    o = jnp.swapaxes(o, 0, 1).reshape(T, H * dv)
    return x + ar.einsum("tk,kh->th", o, lw["o"])


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _dense_ffn(x, lw, *, cfg_key, precision):
    """The dense SwiGLU in ``FFN_BLOCKS`` blocks of its width, one at a
    time, so that no float32 copy of a whole matrix is held."""
    cfg = dict(cfg_key)
    ar, h = Arith(precision), x.shape[1]
    u = rms_norm(x, lw["post_norm"], cfg["rms_norm_eps"])
    F = lw["ffn_g"].shape[1]
    nb = FFN_BLOCKS if F % FFN_BLOCKS == 0 else 1
    cols = lambda w: jnp.swapaxes(w.reshape(h, nb, F // nb), 0, 1)  # noqa: E731

    def one(acc, w):
        return acc + swiglu(ar, u, *w), None

    y, _ = jax.lax.scan(one, jnp.zeros(x.shape, jnp.float32),
                        (cols(lw["ffn_g"]), cols(lw["ffn_u"]),
                         lw["ffn_d"].reshape(nb, F // nb, h)))
    return x + y


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _moe_ffn(x, lw, flip, *, cfg_key, precision):
    cfg = dict(cfg_key)
    u = rms_norm(x, lw["post_norm"], cfg["rms_norm_eps"])
    routed, shared, margin = moe_parts(
        Arith(precision), u, lw, cfg, tuple(cfg["experts_held"]), flip)
    return x + routed + shared, margin


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, g, head, at, *, eps, precision):
    """The logits at ``at`` in blocks of ``HEAD_BLOCK`` vocabulary rows."""
    ar, u = Arith(precision), rms_norm(x, g, eps)[at]
    V = head.shape[1]
    return jnp.concatenate([ar.einsum("th,hv->tv", u, head[:, v:v + HEAD_BLOCK])
                            for v in range(0, V, HEAD_BLOCK)], -1)


def _cfg_key(cfg: dict):
    """The numbers of the config the jitted pieces need, hashable."""
    keep = {k: v for k, v in cfg.items()
            if isinstance(v, (int, float, bool, str))}
    keep["experts_held"] = tuple(cfg["experts_held"])
    keep["_softmax_scale"] = softmax_scale(cfg)
    keep["_rotary_multiplier"] = rotary_multiplier(cfg)
    return tuple(sorted(keep.items()))


def _layer_weights(w, i):
    p = f"l{i}."
    return {k[len(p):]: v for k, v in w.items()
            if isinstance(k, str) and k.startswith(p)}


def _logits_at(cfg, w, ids, at, precision, flips=None):
    """(logits ``[len(at), vocab]``, the MoE layers' routing margins
    ``[layers, len(at)]``) of the full causal forward over ``ids [T]`` at
    the positions ``at``: one layer, one piece at a time.  ``flips
    [layers, T]`` marks, per MoE layer, the tokens that resolve their
    last top-k choice the other way (``dots3.route``)."""
    key, inv = _cfg_key(cfg), jnp.asarray(yarn_frequencies(cfg))
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


def _gaps(ref, pick):
    best = jnp.max(ref, -1)
    got = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
    return (best - got) / jnp.max(jnp.abs(ref), -1)


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
