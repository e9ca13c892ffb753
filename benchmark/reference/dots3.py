"""Plain reference of the ``dots3_note`` language model (dots3-note-prev,
https://huggingface.co/dots-studio/dots3-note-prev/blob/main/config.json):
the full forward over a prompt with its served tokens, float32 at precision
"highest", no cache, no batching, no kernels.

The equations (ISSUE 27, section 1), as computed here:

* block: ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; final
  RMSNorm; untied head over the held slice of the vocabulary.
* latent attention in the NON-absorbed, per-head form: ``c_q = s_q
  RMSNorm(W_qa x)``, ``[q_n | q_r] = W_qb c_q``, ``[c | k_r] = W_kva x``,
  ``c_kv = s_kv RMSNorm(c)``; per head ``k_n = W_uk c_kv``, ``v = W_uv
  c_kv``; score ``(q_n.k_n + rope(q_r).rope(k_r)) / sqrt(d_n + d_r)`` over
  the allowed columns; headwise gate ``sigmoid(W_g x)`` before ``W_o``.
* full layers: the selector ``I(t, s) = sum_j w_j(t) relu(q^I_j(t) .
  k^I(s))``, ``q^I = W_qI c_q`` (64 x 128, rotary on the first 64),
  ``k^I = LayerNorm(W_kI x)`` (rotary on the same 64), ``w = W_w x / (8
  sqrt(128))``; allowed = the ``index_topk`` causal columns of largest
  ``I`` from this file's own float32 scores (the lower column first among
  equals), all of them while fewer exist.  Window layers: allowed = ``t -
  window < s <= t``.
* MoE: ``s = sigmoid(W_r u)`` over all 256, chosen = top 8 of ``s + b``,
  weights ``s_i / sum_chosen s`` times ``routed_scaling_factor``; THE SHARE:
  only experts ``experts_held`` exist here, an assignment to an absent one
  adds nothing; plus the shared expert.  One expert at a time, every token
  through it, weighted by 0 where it was not chosen.

Assumed (the config gives these as booleans or names only; also listed in
the configuration file): ``s_q = sqrt(hidden / q_lora_rank)``, ``s_kv =
sqrt(hidden / kv_lora_rank)`` (apply_mla_qkv_lora_rescale); the gate is a
sigmoid of a projection of the layer's normed input, one scalar a head
(attention_gate_type headwise); the selector's LayerNorm on its key, its
rotary split and the scaling of ``w`` follow DeepSeek-V3.2's inference
code; the window counts the query's own column; no group limit in the
routing.  Left out: the vision and audio towers, multi-token prediction.

Weights live in one flat canonical tree (``l<i>.<leaf>``; the layers are
unlike, so nothing is stacked) in the dtype they are served in; each
matrix is upcast inside its own product, so no float32 copy of the model is
held and the reference fits beside a serving program's state.  Imports
nothing of the program.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .common import Arith, layer_norm, seed_key

FULL = "full_attention"
HEAD_GROUP = 4          # heads whose [T, T] scores are held at once


def dims(cfg: dict, kind: str) -> dict:
    """The attention sizes of a layer of ``kind`` from the config's keys."""
    p = "" if kind == FULL else "swa_"
    return {"H": cfg[p + "num_attention_heads"],
            "dn": cfg[p + "qk_nope_head_dim"],
            "dr": cfg[p + "qk_rope_head_dim"], "dv": cfg[p + "v_head_dim"],
            "rq": cfg[p + "q_lora_rank"], "rkv": cfg[p + "kv_lora_rank"],
            "base": float(cfg["rope_theta" if kind == FULL
                              else "swa_rope_theta"])}


def layer_kinds(cfg: dict) -> list:
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def leaf_shapes(cfg: dict) -> dict:
    """{leaf: (shape, init std or None for a gain/bias)} of the canonical
    tree.  Each matrix is drawn at 1 / (sqrt(fan_in) x RMS of its input),
    so every product's output has unit scale and the attention scores, the
    router's logits and the output logits a spread of about one: random
    weights then route evenly and decode without collapsing.  (Scaling the
    projections into the residual stream by 1 / sqrt(2 x layers), as GPT-2
    publishes, was tried and withdrawn: it shrinks what a flipped top-8
    choice moves, but the float8 control's errors as much: PERF.md section
    6, PR 27.)"""
    h, V = cfg["hidden_size"], cfg["vocab_size"]
    E, Fe = cfg["n_routed_experts_published"], cfg["moe_intermediate_size"]
    lo, hi = cfg["experts_held"]
    J, D = cfg["index_n_heads"], cfg["index_head_dim"]
    out = {"embed": ((V, h), 1.0), "head": ((h, V), h ** -0.5),
           "norm_f": ((h,), None)}
    for i, kind in enumerate(layer_kinds(cfg)):
        d = dims(cfg, kind)
        H, dn, dr, dv, rq, rkv = (d[k] for k in
                                  ("H", "dn", "dr", "dv", "rq", "rkv"))
        s_q, s_kv = math.sqrt(h / rq), math.sqrt(h / rkv)
        L = {"in_norm": ((h,), None), "post_norm": ((h,), None),
             "q_a": ((h, rq), h ** -0.5), "q_a_norm": ((rq,), None),
             "q_b": ((rq, H * (dn + dr)), rq ** -0.5 / s_q),
             "kv_a": ((h, rkv + dr), h ** -0.5), "kv_a_norm": ((rkv,), None),
             "w_uk": ((H, rkv, dn), rkv ** -0.5 / s_kv),
             "w_uv": ((H, rkv, dv), rkv ** -0.5 / s_kv),
             "gate": ((h, H), h ** -0.5),
             "o": ((H * dv, h), (H * dv) ** -0.5)}
        if kind == FULL:
            L.update({"idx_q": ((rq, J * D), rq ** -0.5 / s_q),
                      "idx_k": ((h, D), h ** -0.5),
                      "idx_k_g": ((D,), None), "idx_k_b": ((D,), 0.0),
                      "idx_w": ((h, J), h ** -0.5)})
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
    dtype; ``router_b`` (the ``noaux_tc`` correction, used to choose only)
    stays float32."""
    dtype = jnp.dtype(dtype or cfg.get("dtype", "bfloat16"))
    leaves = sorted(leaf_shapes(cfg).items())

    def draw(key, std, shape, dt):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dt)

    def leaf(key, i, name, shape, std, draw=draw):
        if std is None:
            return jnp.ones(shape, dtype)
        if std == 0.0:
            return jnp.zeros(shape, dtype)
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

def rms_norm(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def rope(x, base, dims_=None):
    """``x [T, d]`` or ``[T, H, d]`` rotated by its own positions 0..T-1
    over its first ``dims_`` features (halves paired)."""
    d = x.shape[-1] if dims_ is None else dims_
    inv = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    if x.ndim == 3:
        ang = ang[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:d]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., d:]], -1)


def swiglu(ar, u, wg, wu, wd):
    a = jax.nn.silu(ar.einsum("th,hf->tf", u, wg)) \
        * ar.einsum("th,hf->tf", u, wu)
    return ar.einsum("tf,fh->th", a, wd)


def selected(scores, causal, k):
    """Membership ``[T, T]`` of the ``k`` causal columns of largest score
    per query (the lower column first among equals); all causal columns
    while fewer than ``k`` exist."""
    if scores.shape[-1] <= k:
        return causal
    masked = jnp.where(causal, scores, -jnp.inf)
    kth = jax.lax.top_k(masked, k)[0][:, -1:]
    above, tied = masked > kth, masked == kth
    room = k - above.sum(-1, keepdims=True)
    return causal & (above | (tied & (jnp.cumsum(tied, -1) <= room)))


def selector(ar, xn, c_q, lw, cfg, base):
    """``I [T, T]``: one selector head at a time."""
    T = xn.shape[0]
    J, D, dr = cfg["index_n_heads"], cfg["index_head_dim"], \
        cfg["qk_rope_head_dim"]
    qi = rope(ar.einsum("tr,rk->tk", c_q, lw["idx_q"]).reshape(T, J, D),
              base, dr)
    ki = rope(layer_norm(ar.einsum("th,hd->td", xn, lw["idx_k"]),
                         lw["idx_k_g"], lw["idx_k_b"], cfg["rms_norm_eps"]),
              base, dr)
    wi = ar.einsum("th,hj->tj", xn, lw["idx_w"]) * (J ** -0.5) * (D ** -0.5)

    def head(acc, jw):
        qj, wj = jw
        return acc + wj[:, None] * jax.nn.relu(
            ar.einsum("td,sd->ts", qj, ki)), None

    acc, _ = jax.lax.scan(head, jnp.zeros((T, T), jnp.float32),
                          (jnp.swapaxes(qi, 0, 1), wi.T))
    return acc


@functools.partial(jax.jit, static_argnames=("kind", "cfg_key", "precision"))
def _attention(x, lw, *, kind, cfg_key, precision):
    cfg = dict(cfg_key)
    ar, d = Arith(precision), dims(cfg, kind)
    H, dn, dr, dv, rq, rkv, base = (d[k] for k in (
        "H", "dn", "dr", "dv", "rq", "rkv", "base"))
    h, eps, T = cfg["hidden_size"], cfg["rms_norm_eps"], x.shape[0]
    xn = rms_norm(x, lw["in_norm"], eps)
    c_q = math.sqrt(h / rq) * rms_norm(
        ar.einsum("th,hr->tr", xn, lw["q_a"]), lw["q_a_norm"], eps)
    q = ar.einsum("tr,rk->tk", c_q, lw["q_b"]).reshape(T, H, dn + dr)
    q_n, q_r = q[..., :dn], rope(q[..., dn:], base)
    kv = ar.einsum("th,hk->tk", xn, lw["kv_a"])
    c_kv = math.sqrt(h / rkv) * rms_norm(kv[:, :rkv], lw["kv_a_norm"], eps)
    k_r = rope(kv[:, rkv:], base)
    t = jnp.arange(T)
    allowed = t[None, :] <= t[:, None]
    if kind == FULL:
        allowed = selected(selector(ar, xn, c_q, lw, cfg, base), allowed,
                           cfg["index_topk"])
    else:
        allowed = allowed & (t[None, :] > t[:, None]
                             - cfg["sliding_window_size"])
    scale = (dn + dr) ** -0.5

    def heads(args):
        qn, qr, wuk, wuv = args            # HEAD_GROUP heads
        k_n = ar.einsum("sr,grd->gsd", c_kv, wuk)
        v = ar.einsum("sr,grv->gsv", c_kv, wuv)
        s = (ar.einsum("tgd,gsd->gts", qn, k_n)
             + ar.einsum("tgd,sd->gts", qr, k_r)) * scale
        p = jax.nn.softmax(jnp.where(allowed[None], s, -1e30), -1)
        return ar.einsum("gts,gsv->tgv", p, v)

    G = HEAD_GROUP if H % HEAD_GROUP == 0 else 1
    grp = lambda a, ax: jnp.moveaxis(                            # noqa: E731
        a.reshape(a.shape[:ax] + (H // G, G) + a.shape[ax + 1:]), ax, 0)
    o = jax.lax.map(heads, (grp(q_n, 1), grp(q_r, 1), grp(lw["w_uk"], 0),
                            grp(lw["w_uv"], 0)))          # [H/G, T, G, dv]
    o = jnp.moveaxis(o, 0, 1).reshape(T, H, dv)
    g = jax.nn.sigmoid(ar.einsum("th,hn->tn", xn, lw["gate"]))
    o = (o * g[..., None]).reshape(T, H * dv)
    return x + ar.einsum("tk,kh->th", o, lw["o"])


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _dense_ffn(x, lw, *, cfg_key, precision):
    cfg = dict(cfg_key)
    u = rms_norm(x, lw["post_norm"], cfg["rms_norm_eps"])
    return x + swiglu(Arith(precision), u, lw["ffn_g"], lw["ffn_u"],
                      lw["ffn_d"])


def route(ar, u, lw, cfg, held, flip=None):
    """(chosen ids ``[T, k]``, weights ``[T, k]``, margin ``[T]``) over the
    router's whole published width.  ``margin``: by how much the last
    expert chosen lies above the first one left out, in the units they are
    chosen by (``s + b``), where one of the two is among ``held = (lo,
    hi)`` (infinite where neither is: swapping them moves nothing that is
    computed here).  ``flip [T]`` marks the tokens that take the first
    expert left out in place of the last one chosen: the other resolution
    of that choice."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(ar.einsum("th,he->te", u, lw["router"]))
    top, ids = jax.lax.top_k(s + lw["router_b"].astype(jnp.float32), k + 1)
    last, nxt = ids[:, k - 1], ids[:, k]
    here = lambda e: (e >= held[0]) & (e < held[1])               # noqa: E731
    margin = jnp.where(here(last) | here(nxt), top[:, k - 1] - top[:, k],
                       jnp.inf)
    ids = ids[:, :k]
    if flip is not None:
        ids = ids.at[:, k - 1].set(jnp.where(flip, nxt, last))
    w = jnp.take_along_axis(s, ids, 1)
    if cfg["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    return ids, w * cfg["routed_scaling_factor"], margin


def moe_parts(ar, u, lw, cfg, held, flip=None):
    """(the routed part of the experts ``held = (lo, hi)`` whose weights
    ``lw`` holds, the shared expert's part, the routing's ``margin``) for
    the normed tokens ``u``: one expert at a time, each token weighted by
    its routing weight for that expert, 0 where it was not chosen."""
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


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, g, head, at, *, eps, precision):
    return Arith(precision).einsum("th,hv->tv", rms_norm(x, g, eps)[at], head)


def _cfg_key(cfg: dict):
    """The numbers of the config the jitted pieces need, hashable."""
    keep = {}
    for k, v in cfg.items():
        if isinstance(v, (int, float, bool, str)):
            keep[k] = v
    keep["experts_held"] = tuple(cfg["experts_held"])
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
    last top-k choice the other way (:func:`route`)."""
    key = _cfg_key(cfg)
    x = w["embed"][ids].astype(jnp.float32)
    none, margins = jnp.zeros(ids.shape, bool), []
    for i, kind in enumerate(layer_kinds(cfg)):
        lw = _layer_weights(w, i)
        x = _attention(x, lw, kind=kind, cfg_key=key, precision=precision)
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


PAD = 256


def _served(cfg, w, prompt, served, precision, flips=None):
    """:func:`_logits_at` over prompt + served tokens, right-padded to a
    multiple of ``PAD`` (causal, so padding changes nothing), at the
    positions that produced each served token.  ``flips [layers,
    len(served)]`` is given by served token."""
    import numpy as np
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


# The router's top-k is not continuous.  Where the last expert chosen and
# the first one left out lie closer than bfloat16 operands resolve, either
# resolution of the choice is the model, and the two give logits a tenth of
# their spread apart and more: everything after the expert layer follows
# the choice, the later layers' choices included.  A token is therefore
# held against the reference under each resolution of the reference's OWN
# ties at that token's position, the ties that a resolution opens in the
# layers after it included, and its gap is the least of them.  A choice
# whose margin is ``TIE`` or more is never resolved the other way: a
# program that gets such a choice wrong, or drops it, reads as before.
# ``TIE`` is in the units the choice is made in (``s + b``); ``TIE_FLOOR``
# is the gap under which no other resolution is looked at (it cannot
# decide ``correct``); ``TIE_FORWARDS`` bounds the forwards one call may
# add.  The readings they were set from: PERF.md section 2.
TIE = 2e-3
TIE_FLOOR = 0.01
TIE_FORWARDS = 6


def tolerant_gaps(gaps, margins, again, tie=TIE, floor=TIE_FLOOR, log=None):
    """``gaps [n]`` of one forward, lowered to the least gap over the
    resolutions of each position's own routing ties.  ``margins [layers,
    n]`` are that forward's; ``again(flips [layers, n]) -> (gaps, margins)``
    runs the forward with the marked choices resolved the other way.  Per
    position the resolutions form a tree: a node is a set of flipped
    layers, its children flip one LATER layer whose margin, in the node's
    own forward, is under ``tie``; the tree is walked breadth first, one
    node of every position that is still over ``floor`` in each forward
    (what another position's flip does to a token through the attention is
    far under what its own does).  ``log`` (a list) receives one record
    per node tried."""
    import numpy as np
    best = np.array(gaps, np.float32)
    margins = np.asarray(margins)
    layers = margins.shape[0]

    def children(node, column):
        return [node + (l,) for l in range(node[-1] + 1 if node else 0, layers)
                if column[l] < tie]

    waiting = {int(i): children((), margins[:, i])
               for i in np.flatnonzero(best > floor)}
    for _ in range(TIE_FORWARDS):
        tried = {i: nodes.pop(0) for i, nodes in waiting.items()
                 if nodes and best[i] > floor}
        if not tried:
            break
        flips = np.zeros(margins.shape, bool)
        for i, node in tried.items():
            flips[list(node), i] = True
        other, theirs = again(flips)
        other, theirs = np.asarray(other, np.float32), np.asarray(theirs)
        for i, node in tried.items():
            if log is not None:
                log.append({"token": i, "gap": float(gaps[i]),
                            "layers": list(node),
                            "margin": float(margins[node[-1], i]
                                            if len(node) == 1 else
                                            theirs[node[-1], i]),
                            "gap_resolved": float(other[i])})
            best[i] = min(best[i], other[i])
            waiting[i] += children(node, theirs[:, i])
    return best


def _again(forward, pick):
    logits, margins = forward
    return _gaps(logits, pick), margins


def served_gaps(cfg: dict, w, prompt, served, precision="float32", tie=TIE,
                log=None):
    """For each served token: how far its reference logit lies below the
    reference's best at that position, relative to max|logit| there,
    under the resolution of that position's routing ties that puts it
    nearest (:func:`tolerant_gaps`)."""
    pick = jnp.asarray(served, jnp.int32).reshape(-1)
    logits, margins = _served(cfg, w, prompt, served, precision)
    return tolerant_gaps(
        _gaps(logits, pick), margins,
        lambda flips: _again(_served(cfg, w, prompt, served, precision,
                                     flips), pick), tie, log=log)


def control_gaps(cfg: dict, w, prompt, served, control_precision, tie=TIE,
                 log=None):
    """The control: at each position of the same prompt and tokens, the gap
    (under the float32 reference, its ties resolved as for a served token)
    of the token the lower precision puts first."""
    ref, margins = _served(cfg, w, prompt, served, "float32")
    pick = jnp.argmax(served_logits(cfg, w, prompt, served,
                                    control_precision), -1)
    return tolerant_gaps(
        _gaps(ref, pick), margins,
        lambda flips: _again(_served(cfg, w, prompt, served, "float32",
                                     flips), pick), tie, log=log)
