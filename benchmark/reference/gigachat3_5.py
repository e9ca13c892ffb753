"""Plain reference of GigaChat3.5-432B-A28B's language model (``model_type``
``gigachat3_5``, https://huggingface.co/ai-sage/GigaChat3.5-432B-A28B/blob/
main/config.json): the full forward over a prompt with its served tokens,
float32 at precision "highest", no cache, no chunks, no kernels, no
batching: the delta rule token by token, the latent attention per head
without absorption.

The equations (ISSUE 48: written from the config's keys, ``described_as``
and the public GatedDeltaNet / DeepSeek-V3 forms, with no network to read
the modelling file; every item marked (A) is under ``assumed`` in the
configuration file), for the ``num_hidden_layers`` layers kept (published
layers ``first_published_layer`` onward; ``full_attention_layers`` says
which of the kept are full), hidden 7168, eps ``rms_norm_eps`` throughout,
``u`` a sublayer's normed input:

* norm: ``N(x; w) = x / sqrt(mean(x^2) + eps) * (g sigmoid(w))``, ``g =
  layernorm_gating_weight`` = 2, ``w`` a learned vector (0 gives scale 1)
  (A: ``norm_type`` ``ZeroCenteredGatedNorm``).
* block (``layernorm_type`` ``pre_post``): ``h = x + N(Mix(N(x)))``, ``y =
  h + N(FFN(N(h)))``, four norm vectors a layer, the post norm inside the
  residual branch (A); final ``N`` before the untied head over the held
  slice of the vocabulary; the stream ``x`` is float32 (a departure).
* full layers (the DeepSeek-V3 form): ``c_q = N(W_qa u)``, ``[q_n | q_r] =
  W_qb c_q`` a head; ``[c | k_r] = W_kva u``, ``c <- N(c)``; rotary on
  ``q_r``, ``k_r`` (``rope_theta``, YaRN from ``rope_scaling``:
  ``kimi_k2.yarn_frequencies``; (A) the halves of the rotated features
  paired: ``rope_interleave`` pairs adjacent ones, a renaming under random
  weights); ``k_n = W_uk c``, ``v = W_uv c`` a head, ONE head at a time;
  scores ``(q_n . k_n + q_r . k_r) (d_n + d_r)^-1/2 m^2``, ``m = 0.1
  mscale_all_dim ln(factor) + 1`` (``use_mla_scaling_factor``), causal
  softmax over every column; ``Mix = W_o (o * sigmoid(W_g u))``, ``W_g``
  ``hidden -> heads x v_head_dim`` (A: ``gated_attention`` says a gate, not
  its width: a gate a feature from the layer's input).
* linear layers (``GigaChat35GatedDeltaNet``): ``[q | k | v] = W_qkv u``
  (``linear_num_key_heads`` heads of ``linear_key_head_dim`` for q and k,
  ``linear_num_value_heads`` of ``linear_value_head_dim`` for v), ``z = W_z
  u``, ``b = W_b u``, ``a = W_a u`` (a number a value head); a depthwise
  causal convolution of ``linear_conv_kernel_dim`` taps over the channels
  of ``[q | k | v]``, written out as the sum of shifted copies (zeros before
  the first token), then SiLU; ``q_h <- q_h / |q_h| x key_dim^-1/2``, ``k_h
  <- k_h / |k_h|`` (A: ``|x| = sqrt(sum x^2 + 1e-6)``); value head ``j``
  reads key head ``j // (value heads / key heads)``; ``beta = sigmoid(b)``,
  ``alpha = exp(-exp(A_log_j) softplus(a + dt_bias_j))``; per value head,
  float32, TOKEN BY TOKEN (``lax.scan``)::

      S_t = alpha_t S_{t-1} + beta_t (v_t - alpha_t S_{t-1} k_t) k_t^T
      o_t = S_t q_t,            S = 0 before the first token

  out: RMS over each head's features (``linear_attn_o_norm_eps``) times ``(1
  + w_o)`` (one learned ``value_dim`` vector) times
  ``linear_sigmoid_gate_scale sigmoid(z)`` (A: the norm, then the gate),
  then ``W_out``.
* FFN: the first ``first_k_dense_replace`` kept layers a dense SwiGLU; the
  rest ``s = sigmoid(W_r u)`` over all PUBLISHED experts, chosen = the top
  ``num_experts_per_tok`` of ``s + b`` (``n_group`` 1: no group limit; the
  correction chooses only), ``w_i = s_i / sum_chosen s x
  routed_scaling_factor``; THE SHARE: only experts ``experts_held`` exist
  here, an assignment to an absent one adds nothing; one held expert at a
  time; plus one shared expert, ungated.  Every SwiGLU clamped: ``W_down
  (silu(min(g, L)) * clip(v, -L, L))``, ``L = swiglu_limit`` (A: the clamp
  form without the ``+ 1``, since ``hidden_act`` is ``silu``).
* left out: the ``num_nextn_predict_layers`` draft modules (they draft;
  the model's forward pass does not run them).
* compared (``served_gaps``): for each served token how far its reference
  logit lies below the reference's best at its position, relative to the
  largest magnitude there, under the resolution of that position's routing
  near-ties that puts it nearest (``glm_moe_dsa.route``'s rule over
  ``dots3.tolerant_gaps``), as the MEAN over blocks of 256 consecutive
  served tokens (the hybrid cells' comparison, ``nemotron_h.block_means``).
  Why blocks: on the chip the widest single token of a sound run reads
  0.007-0.053 over 16 requests of 8 seeds at 128 slots (float8's 0.17-
  0.19), a heavy tail that a limit of 0.03 failed twice in six runs, while
  the reference's OWN equations with bfloat16 operands read 0.005 and
  0.016 at the widest token of 768: single tokens read which near-tied
  logits the rounding put first, not the arithmetic.  The means read the
  arithmetic: sound runs 0.00013-0.00051 at the widest block, float8
  0.0104-0.0108 (PERF.md section 2, PR 48).

Two controls: ``control_gaps`` computes the forward with every product's
operands rounded to a lower precision (float8), and ``recurrence``'s
``state_dtype`` rounds the matrix state after every token (the tests'
state control).

Weights live in one flat canonical tree (``l<i>.<leaf>``) in the dtype they
are served in; each matrix is upcast inside its own product; the dense FFN
runs in blocks of its width, the experts one at a time, the head over the
vocabulary in blocks: a 2k-token request fits beside a serving program that
fills the chip.  Imports nothing of the program.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .common import Arith, seed_key
from .dots3 import tolerant_gaps
from .glm_moe_dsa import TIE, route
from .kimi_k2 import (_gaps, _layer_weights, dims, rope, rotary_multiplier,
                      softmax_scale, yarn_frequencies)
from .nemotron_h import _draw, block_means

LINEAR, FULL = "linear_attention", "full_attention"
QUERY_BLOCK = 256       # queries whose [block, T] scores are held at once
HEAD_BLOCK = 4096       # vocabulary rows of the head computed at once
FFN_BLOCKS = 8          # blocks of the dense FFN's width, one at a time
PAD = 256
L2_EPS = 1e-6
FLOAT32_LEAVES = ("router_b", "dt_bias", "A_log")
# a zero-centred gain is drawn about 0 so that its form counts
GAIN_STD = 0.1
# the family's draws: a step log-uniform in [DT_MIN, DT_MAX] (``dt_bias``
# its inverse softplus), a decay rate uniform in [1, 16] (``A_log`` its log)
DT_MIN, DT_MAX = 0.001, 0.1


def layer_kinds(cfg: dict) -> list:
    full = set(cfg["full_attention_layers"])
    return [FULL if i in full else LINEAR
            for i in range(cfg["num_hidden_layers"])]


def linear_dims(cfg: dict) -> dict:
    G, H = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    N, P = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return {"G": G, "H": H, "N": N, "P": P, "keys": G * N, "inner": H * P,
            "conv_dim": 2 * G * N + H * P}


def leaf_shapes(cfg: dict) -> dict:
    """{leaf: (shape, init)} of the canonical tree; ``init`` is a std (each
    matrix at 1 / sqrt(fan_in) against an input of unit scale, as the other
    references'; a zero-centred gain at ``GAIN_STD``) or the name of one of
    the family's draws (``dt_bias``, ``A_log``)."""
    h, V = cfg["hidden_size"], cfg["vocab_size"]
    E, Fe = cfg["n_routed_experts_published"], cfg["moe_intermediate_size"]
    lo, hi = cfg["experts_held"]
    d, ld = dims(cfg), linear_dims(cfg)
    H, dn, dr, dv, rq, rkv = (d[k] for k in ("H", "dn", "dr", "dv", "rq",
                                             "rkv"))
    taps = cfg["linear_conv_kernel_dim"]
    out = {"embed": ((V, h), 1.0), "head": ((V, h), h ** -0.5),
           "norm_f": ((h,), GAIN_STD)}
    for i, kind in enumerate(layer_kinds(cfg)):
        L = {k: ((h,), GAIN_STD)
             for k in ("in_norm", "in_post", "ffn_norm", "ffn_post")}
        if kind == FULL:
            L.update({"q_a": ((h, rq), h ** -0.5),
                      "q_a_norm": ((rq,), GAIN_STD),
                      "q_b": ((rq, H * (dn + dr)), rq ** -0.5),
                      "kv_a": ((h, rkv + dr), h ** -0.5),
                      "kv_a_norm": ((rkv,), GAIN_STD),
                      "w_uk": ((H, rkv, dn), rkv ** -0.5),
                      "w_uv": ((H, rkv, dv), rkv ** -0.5),
                      "gate": ((h, H * dv), h ** -0.5),
                      "o": ((H * dv, h), (H * dv) ** -0.5)})
        else:
            L.update({"qkv": ((h, ld["conv_dim"]), h ** -0.5),
                      "z": ((h, ld["inner"]), h ** -0.5),
                      "b": ((h, ld["H"]), h ** -0.5),
                      "a": ((h, ld["H"]), h ** -0.5),
                      "conv": ((ld["conv_dim"], taps), taps ** -0.5),
                      "dt_bias": ((ld["H"],), "dt_bias"),
                      "A_log": ((ld["H"],), "A_log"),
                      "o_norm": ((ld["P"],), GAIN_STD),
                      "out": ((ld["inner"], h), ld["inner"] ** -0.5)})
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
    ``nemotron_h``'s draws (a normal at a std, or the family's ``dt_bias``
    / ``A_log``); the router's correction and the recurrence's per-head
    scalars (``FLOAT32_LEAVES``) stay float32."""
    dtype = jnp.dtype(dtype or cfg.get("dtype", "bfloat16"))
    leaves = sorted(leaf_shapes(cfg).items())
    lo, hi = jnp.float32(DT_MIN), jnp.float32(DT_MAX)

    def leaf(key, i, name, shape, init, draw=_draw):
        dt = jnp.float32 if name.endswith(FLOAT32_LEAVES) else dtype
        tag = init if isinstance(init, str) else jnp.float32(init)
        return draw(jax.random.fold_in(key, i), tag, shape, dt, lo, hi)

    key = seed_key(seed)
    if sum(math.prod(s) for _, (s, _) in leaves) < 2 ** 26:
        return jax.jit(lambda k: {n: leaf(k, i, n, s, sd) for i, (n, (s, sd))
                                  in enumerate(leaves)})(key)
    one = jax.jit(_draw, static_argnums=(2, 3))

    def placed(key, init, shape, dt, lo, hi):
        return (_draw if isinstance(init, str) else one)(
            key, init, shape, dt, lo, hi)
    return {n: leaf(key, i, n, s, sd, placed) for i, (n, (s, sd))
            in enumerate(leaves)}


# -- the pieces ----------------------------------------------------------------

def gated_norm(x, w, eps, g):
    """``x / rms(x) * (g sigmoid(w))`` over the last axis."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (g * jax.nn.sigmoid(w.astype(jnp.float32)))


def swiglu(ar, u, wg, wu, wd, limit):
    g = jnp.minimum(ar.einsum("th,hf->tf", u, wg), limit)
    v = jnp.clip(ar.einsum("th,hf->tf", u, wu), -limit, limit)
    return ar.einsum("tf,fh->th", jax.nn.silu(g) * v, wd)


def recurrence(q, k, v, alpha, beta, state_dtype=None, final_state=False):
    """``o_t = S_t q_t`` of ``S_t = alpha_t S_{t-1} + beta_t (v_t - alpha_t
    S_{t-1} k_t) k_t^T`` from ``S = 0``, token by token: ``q``, ``k [T, H,
    N]`` (each value head's key head already picked), ``v [T, H, P]``,
    ``alpha``, ``beta [T, H]``, all float32.  ``state_dtype`` rounds the
    state after every token (the state control); ``final_state`` hands
    back ``(o, S_T [H, P, N])``."""
    exact = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)

    def token(S, inp):
        qt, kt, vt, at, bt = inp
        S = at[:, None, None] * S
        u = bt[:, None] * (vt - exact("hpn,hn->hp", S, kt))
        S = S + u[:, :, None] * kt[:, None, :]
        if state_dtype is not None:
            S = S.astype(state_dtype).astype(jnp.float32)
        return S, exact("hpn,hn->hp", S, qt)

    H, P, N = v.shape[1], v.shape[2], k.shape[2]
    S, o = jax.lax.scan(token, jnp.zeros((H, P, N), jnp.float32),
                        (q, k, v, alpha, beta))
    return (o, S) if final_state else o


def _norm(cfg, x, w):
    return gated_norm(x, w, cfg["rms_norm_eps"],
                      cfg["layernorm_gating_weight"])


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision",
                                             "state_dtype"))
def _linear(x, lw, *, cfg_key, precision, state_dtype=None):
    cfg = dict(cfg_key)
    ar, ld, f32 = Arith(precision), linear_dims(cfg), jnp.float32
    T, G, H, N, P = x.shape[0], ld["G"], ld["H"], ld["N"], ld["P"]
    u = _norm(cfg, x, lw["in_norm"])
    qkv = ar.einsum("th,hk->tk", u, lw["qkv"])
    w = lw["conv"].astype(f32)
    taps = w.shape[1]
    conv = jnp.zeros_like(qkv)
    for j in range(taps):               # tap j weighs qkv(t - (taps-1) + j)
        conv = conv + w[:, j] * jnp.pad(qkv, ((taps - 1 - j, 0), (0, 0)))[:T]
    q, k, v = jnp.split(jax.nn.silu(conv), [ld["keys"], 2 * ld["keys"]], -1)

    def unit(t, scale):
        t = t.reshape(T, G, N)
        t = t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + L2_EPS)
        return jnp.repeat(t * scale, H // G, axis=1)
    beta = jax.nn.sigmoid(ar.einsum("th,hk->tk", u, lw["b"]))
    alpha = jnp.exp(-jnp.exp(lw["A_log"].astype(f32)) * jax.nn.softplus(
        ar.einsum("th,hk->tk", u, lw["a"]) + lw["dt_bias"].astype(f32)))
    o = recurrence(unit(q, N ** -0.5), unit(k, 1.0), v.reshape(T, H, P),
                   alpha, beta, state_dtype)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + cfg["linear_attn_o_norm_eps"])
    o = o * (1.0 + lw["o_norm"].astype(f32))
    o = o.reshape(T, H * P) * (cfg["linear_sigmoid_gate_scale"]
                               * jax.nn.sigmoid(
                                   ar.einsum("th,hk->tk", u, lw["z"])))
    return x + _norm(cfg, ar.einsum("tk,kh->th", o, lw["out"]),
                     lw["in_post"])


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _attention(x, lw, inv, *, cfg_key, precision):
    cfg = dict(cfg_key)
    ar, d = Arith(precision), dims(cfg)
    H, dn, dr, dv, rkv = (d[k] for k in ("H", "dn", "dr", "dv", "rkv"))
    T = x.shape[0]
    mult, scale = cfg["_rotary_multiplier"], cfg["_softmax_scale"]
    u = _norm(cfg, x, lw["in_norm"])
    c_q = _norm(cfg, ar.einsum("th,hr->tr", u, lw["q_a"]), lw["q_a_norm"])
    q = ar.einsum("tr,rk->tk", c_q, lw["q_b"]).reshape(T, H, dn + dr)
    q_n, q_r = q[..., :dn], rope(q[..., dn:], inv, mult)
    kv = ar.einsum("th,hk->tk", u, lw["kv_a"])
    c_kv = _norm(cfg, kv[:, :rkv], lw["kv_a_norm"])
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
    o = o * jax.nn.sigmoid(ar.einsum("th,hk->tk", u, lw["gate"]))
    return x + _norm(cfg, ar.einsum("tk,kh->th", o, lw["o"]), lw["in_post"])


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _dense_ffn(x, lw, *, cfg_key, precision):
    """The dense SwiGLU in ``FFN_BLOCKS`` blocks of its width, one at a
    time, so that no float32 copy of a whole matrix is held."""
    cfg = dict(cfg_key)
    ar, h = Arith(precision), x.shape[1]
    u = _norm(cfg, x, lw["ffn_norm"])
    F = lw["ffn_g"].shape[1]
    nb = FFN_BLOCKS if F % FFN_BLOCKS == 0 else 1
    cols = lambda w: jnp.swapaxes(w.reshape(h, nb, F // nb), 0, 1)  # noqa: E731

    def one(acc, w):
        return acc + swiglu(ar, u, *w, cfg["swiglu_limit"]), None

    y, _ = jax.lax.scan(one, jnp.zeros(x.shape, jnp.float32),
                        (cols(lw["ffn_g"]), cols(lw["ffn_u"]),
                         lw["ffn_d"].reshape(nb, F // nb, h)))
    return x + _norm(cfg, y, lw["ffn_post"])


def moe_parts(ar, u, lw, cfg, held, flip=None):
    """(the routed part of the experts ``held = (lo, hi)`` whose weights
    ``lw`` holds, the shared expert's part, the routing's ``margin``) for
    the normed tokens ``u``: one expert at a time, each token weighted by
    its routing weight for that expert, 0 where it was not chosen
    (``glm_moe_dsa.route``'s routing and near-tie rule)."""
    lo, limit = held[0], cfg["swiglu_limit"]
    n = lw["exp_g"].shape[0]
    ids, w, margin = route(ar, u, lw, cfg, (lo, lo + n), flip)

    def one(acc, ew):
        e, wg, wu, wd = ew
        we = jnp.sum(jnp.where(ids == e, w, 0.0), -1)
        return acc + we[:, None] * swiglu(ar, u, wg, wu, wd, limit), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros(u.shape, jnp.float32),
        (lo + jnp.arange(n), lw["exp_g"], lw["exp_u"], lw["exp_d"]))
    return routed, swiglu(ar, u, lw["sh_g"], lw["sh_u"], lw["sh_d"],
                          limit), margin


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _moe_ffn(x, lw, flip, *, cfg_key, precision):
    cfg = dict(cfg_key)
    u = _norm(cfg, x, lw["ffn_norm"])
    routed, shared, margin = moe_parts(
        Arith(precision), u, lw, cfg, tuple(cfg["experts_held"]), flip)
    return x + _norm(cfg, routed + shared, lw["ffn_post"]), margin


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _head(x, w, head, at, *, cfg_key, precision):
    """The logits at ``at`` in blocks of ``HEAD_BLOCK`` vocabulary rows."""
    ar, u = Arith(precision), _norm(dict(cfg_key), x, w)[at]
    V = head.shape[0]
    return jnp.concatenate([ar.einsum("th,vh->tv", u, head[v:v + HEAD_BLOCK])
                            for v in range(0, V, HEAD_BLOCK)], -1)


def _cfg_key(cfg: dict):
    """The numbers of the config the jitted pieces need, hashable."""
    keep = {k: v for k, v in cfg.items()
            if isinstance(v, (int, float, bool, str))}
    keep["experts_held"] = tuple(cfg["experts_held"])
    keep["_softmax_scale"] = softmax_scale(cfg)
    keep["_rotary_multiplier"] = rotary_multiplier(cfg)
    return tuple(sorted(keep.items()))


def _logits_at(cfg, w, ids, at, precision, flips=None, state_dtype=None):
    """(logits ``[len(at), vocab]``, the MoE layers' routing margins
    ``[layers, len(at)]``) of the full causal forward over ``ids [T]`` at
    the positions ``at``: one layer, one piece at a time.  ``flips
    [layers, T]`` marks, per MoE layer, the tokens that resolve their
    nearest top-k choice the other way."""
    key, inv = _cfg_key(cfg), jnp.asarray(yarn_frequencies(cfg))
    x = w["embed"][ids].astype(jnp.float32)
    none, margins = jnp.zeros(ids.shape, bool), []
    for i, kind in enumerate(layer_kinds(cfg)):
        lw = _layer_weights(w, i)
        if kind == FULL:
            x = _attention(x, lw, inv, cfg_key=key, precision=precision)
        else:
            x = _linear(x, lw, cfg_key=key, precision=precision,
                        state_dtype=state_dtype)
        if i < cfg["first_k_dense_replace"]:
            x = _dense_ffn(x, lw, cfg_key=key, precision=precision)
            continue
        flip = none if flips is None else flips[len(margins)]
        x, margin = _moe_ffn(x, lw, flip, cfg_key=key, precision=precision)
        margins.append(margin[at])
    logits = _head(x, w["norm_f"], w["head"], at, cfg_key=key,
                   precision=precision)
    return logits, (jnp.stack(margins) if margins
                    else jnp.zeros((0, at.shape[0]), jnp.float32))


def _served(cfg, w, prompt, served, precision, flips=None, state_dtype=None):
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
                                 jnp.asarray(at, jnp.int32), precision, flips,
                                 state_dtype)
    return logits[:served.size], margins[:, :served.size]


def served_logits(cfg: dict, w, prompt, served, precision="float32",
                  state_dtype=None):
    """Logits ``[len(served), vocab]`` at the positions that produced each
    served token: one forward over prompt + served tokens."""
    return _served(cfg, w, prompt, served, precision,
                   state_dtype=state_dtype)[0]


def _resolved(cfg, w, prompt, served, pick, precision, tie, log):
    """``pick``'s gaps under the reference at ``precision``, lowered to the
    least over the resolutions of the reference's own routing ties
    (``dots3.tolerant_gaps``; PERF.md section 2)."""
    def forward(flips=None):
        logits, margins = _served(cfg, w, prompt, served, precision, flips)
        return _gaps(logits, pick), margins
    return tolerant_gaps(*forward(), forward, tie, log=log)


def token_gaps(cfg: dict, w, prompt, served, precision="float32", tie=TIE,
               log=None):
    """For each served token: how far its reference logit lies below the
    reference's best at that position, relative to max|logit| there,
    under the resolution of that position's routing ties that puts it
    nearest."""
    pick = jnp.asarray(served, jnp.int32).reshape(-1)
    return _resolved(cfg, w, prompt, served, pick, precision, tie, log)


def _blocks(gaps):
    """The block means of ``gaps``, and one printed line with the single
    tokens' readings beside them."""
    gaps = np.asarray(gaps, np.float32)
    out = block_means(gaps)
    print(f"reference gaps: {out.size} blocks of {gaps.shape[0]} tokens, "
          f"widest {float(out.max()):.5f}, mean {float(gaps.mean()):.5f}, "
          f"widest of 64-token blocks {float(block_means(gaps, 64).max()):.5f}"
          f", widest token {float(gaps.max()):.5f}", flush=True)
    return out


def served_gaps(cfg: dict, w, prompt, served, precision="float32", tie=TIE,
                log=None):
    """:func:`token_gaps` as the mean over each block of 256 consecutive
    served tokens (why blocks: the module docstring)."""
    return _blocks(token_gaps(cfg, w, prompt, served, precision, tie, log))


def control_gaps(cfg: dict, w, prompt, served, control_precision, tie=TIE,
                 log=None):
    """The control: the same block means for the tokens the lower precision
    puts first at each position of the same prompt and tokens (under the
    float32 reference, its ties resolved as for a served token)."""
    pick = jnp.argmax(served_logits(cfg, w, prompt, served,
                                    control_precision), -1)
    return _blocks(_resolved(cfg, w, prompt, served, pick, "float32", tie,
                             log))
