"""Plain reference of the ``nemotron_h`` language model
(NVIDIA-Nemotron-3-Nano-30B-A3B, https://huggingface.co/nvidia/
NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json): the full
forward over a prompt with its served tokens, float32 at precision
"highest", no cache, no batching, no kernels, no chunked scan.

The equations (ISSUE 40), as computed here, for the first
``num_hidden_layers`` letters of ``hybrid_override_pattern``.  Every layer
is ONE part: ``x <- x + f(RMSNorm(x))``; after the last layer RMSNorm,
``logits = h W_head`` (untied).

* ``M`` (Mamba-2): ``[z | xBC | dt] = u W_in``; ``xBC <- silu(conv(xBC) +
  b)``, the convolution written out as the sum of ``conv_kernel`` shifted
  copies (causal, depthwise, zeros before the first token); ``[x | B | C] =
  xBC`` with ``x`` as ``mamba_num_heads`` heads of ``mamba_head_dim``, ``B``
  and ``C`` as ``n_groups`` groups of ``ssm_state_size`` (head ``h`` reads
  group ``h // (heads / groups)``); ``D = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)`` a head; the recurrence **token by token** (``lax.scan``)::

      h_t = exp(D_t A) h_{t-1} + D_t x_t (x) B_t      [head_dim, state]
      y_t = h_t C_t + D_skip x_t

  ``y <- GroupRMSNorm(y * silu(z)) * w`` over groups of ``heads x head_dim /
  n_groups`` features, ``f = y W_out``.
* ``*`` (attention): ``q = u W_q``, ``k, v = u W_k, u W_v``; causal softmax
  at scale ``head_dim ** -0.5``, query head ``i`` reads cached head ``i //
  rep``, one query head at a time; ``f = o W_o``.  No bias, no per-head
  norm, no positions.
* ``E`` (experts): ``s = sigmoid(u W_r)`` over all PUBLISHED experts; chosen
  = the ``num_experts_per_tok`` largest of ``s + b_corr`` (``n_group`` 1: no
  group limit; ``b_corr`` chooses only); ``w_i = routed_scaling_factor x
  s_i / (sum_chosen s + 1e-20)``; ``f = sum_{chosen, held} w_i W_down,i
  relu(W_up,i u)^2 + W_down,sh relu(W_up,sh u)^2``: one held expert at a
  time, every token through it, weighted by 0 where it was not chosen; what
  the experts held elsewhere would add is left out, as in the program.
* compared (``served_gaps``): the mean, over blocks of ``GAP_BLOCK`` = 256
  consecutive served tokens, of how far a served token's logit lies below
  the best (lfm2's comparison and lfm2's reason: 23 expert layers each
  choose 6 of 128 by scores that lie close together, so in bfloat16 nearly
  every token takes another expert somewhere and single tokens read the
  routing's discontinuity, not the arithmetic; the blocks are four times
  lfm2's because means over 64 tokens still spread by 3x from block to
  block here: PERF.md section 2 has both readings).  No tie is resolved
  either way, so no ``TIE``: ``dots3.tolerant_gaps`` walks a tree of at
  most six forwards, which 23 choosing layers outgrow.

Departures from the public modelling code, each also under ``assumed`` in
the configuration file: the residual stream is float32 (``residual_in_fp32``
false there); the convolution's state is its last ``conv_kernel - 1``
inputs (the public code keeps ``conv_kernel`` and shifts); ``D`` is not
clamped (``time_step_limit`` (0, inf)); attention carries no rotary
embedding (the public attention reads neither ``rope_theta`` nor
``partial_rotary_factor``).

Two controls: ``control_gaps`` computes the forward with every product's
operands rounded to a lower precision (float8, as in every cell), and with
``control_precision="bfloat16_state"`` keeps the products in float32 but
rounds the recurrence's state to bfloat16 after every token: the precision
the configuration says it does NOT use for the state.

Weights live in one flat canonical tree (``l<i>.<leaf>``) in the dtype they
are served in; each matrix is upcast inside its own product, and the head
runs over the vocabulary in blocks (``lfm2._head``), so the reference fits
beside a serving program that fills the chip.  Imports nothing of the
program.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .common import Arith, seed_key
from .dots3 import rms_norm
from .lfm2 import HEAD_BLOCK, _cfg_key, _gaps, _head, _layer_weights

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
# sequences are padded to a multiple of this: three lengths cover every
# request of the cell (2,048 + 1,024 tokens), so the pieces compile thrice
PAD = 1024
STATE_CONTROL = "bfloat16_state"
FLOAT32_LEAVES = ("router_b", "dt_bias", "A_log", "D")


def layer_kinds(cfg: dict) -> str:
    return cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]


def dims(cfg: dict) -> dict:
    """The widths the pieces are written in."""
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N = cfg["n_groups"], cfg["ssm_state_size"]
    return {"H": H, "P": P, "G": G, "N": N, "inner": H * P,
            "conv_dim": H * P + 2 * G * N,
            "in": 2 * H * P + 2 * G * N + H}


def held_experts(cfg: dict):
    """(lo, hi) of the published experts this chip holds."""
    return tuple(cfg.get("experts_held") or (0, cfg["n_routed_experts"]))


def leaf_shapes(cfg: dict) -> dict:
    """{leaf: (shape, init)} of the canonical tree; ``init`` is a std (each
    matrix at 1 / sqrt(fan_in) against an input of unit scale, as the other
    references'), None for a gain of ones, or the name of a draw of the
    family's own initialisation (``dt_bias``, ``A_log``)."""
    h, V = cfg["hidden_size"], cfg["vocab_size"]
    d = dims(cfg)
    AH, KV, ad = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    E = cfg.get("n_routed_experts_published", cfg["n_routed_experts"])
    lo, hi = held_experts(cfg)
    Fe = cfg["moe_intermediate_size"]
    Fs = cfg["moe_shared_expert_intermediate_size"]
    taps = cfg["conv_kernel"]
    out = {"embed": ((V, h), h ** -0.5), "head": ((V, h), h ** -0.5),
           "norm_f": ((h,), None)}
    for i, kind in enumerate(layer_kinds(cfg)):
        L = {"norm": ((h,), None)}
        if kind == MAMBA:
            L.update({"in_proj": ((h, d["in"]), h ** -0.5),
                      "conv": ((d["conv_dim"], taps), taps ** -0.5),
                      "conv_b": ((d["conv_dim"],), (3 * taps) ** -0.5),
                      "dt_bias": ((d["H"],), "dt_bias"),
                      "A_log": ((d["H"],), "A_log"),
                      "D": ((d["H"],), None),
                      "norm_g": ((d["inner"],), None),
                      "out_proj": ((d["inner"], h), d["inner"] ** -0.5)})
        elif kind == ATTENTION:
            L.update({"q": ((h, AH * ad), h ** -0.5),
                      "k": ((h, KV * ad), h ** -0.5),
                      "v": ((h, KV * ad), h ** -0.5),
                      "o": ((AH * ad, h), (AH * ad) ** -0.5)})
        elif kind == EXPERTS:
            L.update({"router": ((h, E), h ** -0.5), "router_b": ((E,), 0.01),
                      "exp_u": ((hi - lo, h, Fe), h ** -0.5),
                      "exp_d": ((hi - lo, Fe, h), Fe ** -0.5),
                      "sh_u": ((h, Fs), h ** -0.5),
                      "sh_d": ((Fs, h), Fs ** -0.5)})
        else:
            raise ValueError(f"hybrid_override_pattern[{i}] = {kind!r}")
        out.update({f"l{i}.{k}": v for k, v in L.items()})
    return out


def _draw(key, init, shape, dt, lo, hi):
    """One leaf: a normal at std ``init``, or one of the family's draws
    between ``lo`` and ``hi`` (``dt_bias`` = softplus^-1 of a step
    log-uniform in [time_step_min, time_step_max]; ``A_log`` = log of a
    decay rate uniform in [1, 16])."""
    if isinstance(init, str) and init == "dt_bias":
        step = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                          jnp.log(lo), jnp.log(hi)))
        return (step + jnp.log(-jnp.expm1(-step))).astype(dt)
    if isinstance(init, str):           # "A_log"
        return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                          1.0, 16.0)).astype(dt)
    return (jax.random.normal(key, shape, jnp.float32) * init).astype(dt)


def init_weights(cfg: dict, seed: int, dtype=None):
    """The canonical tree from ``seed``, made on the device in the served
    dtype; the router's correction and the recurrence's per-head scalars
    (``FLOAT32_LEAVES``) stay float32."""
    dtype = jnp.dtype(dtype or cfg.get("dtype", "bfloat16"))
    leaves = sorted(leaf_shapes(cfg).items())
    lo = jnp.float32(max(cfg["time_step_min"], cfg["time_step_floor"]))
    hi = jnp.float32(cfg["time_step_max"])

    def leaf(key, i, name, shape, init, draw=_draw):
        dt = jnp.float32 if name.endswith(FLOAT32_LEAVES) else dtype
        if init is None:
            return jnp.ones(shape, dt)
        tag = init if isinstance(init, str) else jnp.float32(init)
        return draw(jax.random.fold_in(key, i), tag, shape, dt, lo, hi)

    key = seed_key(seed)
    if sum(math.prod(s) for _, (s, _) in leaves) < 2 ** 26:
        # a test's size: one program for the whole tree (the same numbers)
        return jax.jit(lambda k: {n: leaf(k, i, n, s, sd) for i, (n, (s, sd))
                                  in enumerate(leaves)})(key)
    # the real size: one leaf at a time, so that the float32 draws of a
    # model that fills the chip are never held together; one program a
    # shape (the layers repeat them), not one a leaf
    one = jax.jit(_draw, static_argnums=(2, 3))

    def placed(key, init, shape, dt, lo, hi):
        if isinstance(init, str):
            return _draw(key, init, shape, dt, lo, hi)
        return one(key, init, shape, dt, lo, hi)
    return {n: leaf(key, i, n, s, sd, placed) for i, (n, (s, sd))
            in enumerate(leaves)}


# -- the pieces ----------------------------------------------------------------

def recurrence(x, dt, b, c, a, state_dtype=None, final_state=False):
    """``y_t = h_t C_t`` of ``h_t = exp(dt_t a) h_{t-1} + dt_t x_t (x) B_t``
    from ``h = 0``, token by token: ``x [T, H, P]``, ``dt [T, H]``, ``b``,
    ``c [T, H, N]`` (each head's group already picked), ``a [H]``; all
    float32.  ``state_dtype`` rounds the state after every token (the
    state control); ``final_state`` hands back ``(y, h_T [H, P, N])``, for
    the test that reads the served state's own error."""
    def token(h, inp):
        xt, dtt, bt, ct = inp
        h = jnp.exp(dtt * a)[:, None, None] * h \
            + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
        if state_dtype is not None:
            h = h.astype(state_dtype).astype(jnp.float32)
        return h, jnp.sum(h * ct[:, None, :], -1)

    H, P, N = x.shape[1], x.shape[2], b.shape[-1]
    h, y = jax.lax.scan(token, jnp.zeros((H, P, N), jnp.float32),
                        (x, dt, b, c))
    return (y, h) if final_state else y


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision",
                                             "state_dtype"))
def _mamba(x, lw, *, cfg_key, precision, state_dtype=None):
    cfg = dict(cfg_key)
    ar, d, f32 = Arith(precision), dims(cfg), jnp.float32
    T, H, P, G, N = x.shape[0], d["H"], d["P"], d["G"], d["N"]
    u = rms_norm(x, lw["norm"], cfg["norm_eps"])
    z, xbc, dt = jnp.split(ar.einsum("th,hk->tk", u, lw["in_proj"]),
                           [d["inner"], d["inner"] + d["conv_dim"]], -1)
    w = lw["conv"].astype(f32)
    taps = w.shape[1]
    conv = jnp.zeros_like(xbc) + lw["conv_b"].astype(f32)
    for j in range(taps):               # tap j weighs xBC(t - (taps-1) + j)
        back = taps - 1 - j
        conv = conv + w[:, j] * jnp.pad(xbc, ((back, 0), (0, 0)))[:T]
    xbc = jax.nn.silu(conv)
    xs, b, c = jnp.split(xbc, [d["inner"], d["inner"] + G * N], -1)
    xs = xs.reshape(T, H, P)
    b, c = (jnp.repeat(t.reshape(T, G, N), H // G, axis=1) for t in (b, c))
    dt = jax.nn.softplus(dt + lw["dt_bias"].astype(f32))
    y = recurrence(xs, dt, b, c, -jnp.exp(lw["A_log"].astype(f32)),
                   state_dtype)
    y = y + lw["D"].astype(f32)[:, None] * xs
    y = y.reshape(T, d["inner"]) * jax.nn.silu(z)
    g = y.reshape(T, G, d["inner"] // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                          + cfg["norm_eps"])
    y = g.reshape(T, d["inner"]) * lw["norm_g"].astype(f32)
    return x + ar.einsum("tk,kh->th", y, lw["out_proj"])


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _attention(x, lw, *, cfg_key, precision):
    cfg = dict(cfg_key)
    ar = Arith(precision)
    H, KV, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    T = x.shape[0]
    u = rms_norm(x, lw["norm"], cfg["norm_eps"])
    q = ar.einsum("th,hk->tk", u, lw["q"]).reshape(T, H, d)
    k = ar.einsum("th,hk->tk", u, lw["k"]).reshape(T, KV, d)
    v = ar.einsum("th,hk->tk", u, lw["v"]).reshape(T, KV, d)
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


def relu2(ar, u, w_up, w_down):
    return ar.einsum("tf,fh->th", jnp.square(jax.nn.relu(
        ar.einsum("th,hf->tf", u, w_up))), w_down)


def route(ar, u, lw, cfg):
    """(chosen ids ``[T, k]`` among the published experts, weights)."""
    s = jax.nn.sigmoid(ar.einsum("th,he->te", u, lw["router"]))
    _, ids = jax.lax.top_k(s + lw["router_b"].astype(jnp.float32),
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, ids, 1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return ids, w * cfg["routed_scaling_factor"]


def experts(ar, u, lw, cfg, held, shared=True):
    """``sum_{chosen, held} w_i E_i(u)`` (+ the shared expert): the leaves
    hold experts ``held = (lo, hi)`` of the published ones; one at a time,
    each token weighted by its routing weight for that expert, 0 where it
    was not chosen."""
    ids, w = route(ar, u, lw, cfg)
    lo, hi = held

    def one(acc, ew):
        e, wu, wd = ew
        we = jnp.sum(jnp.where(ids == e, w, 0.0), -1)
        return acc + we[:, None] * relu2(ar, u, wu, wd), None

    out, _ = jax.lax.scan(one, jnp.zeros(u.shape, jnp.float32),
                          (jnp.arange(lo, hi), lw["exp_u"], lw["exp_d"]))
    return out + relu2(ar, u, lw["sh_u"], lw["sh_d"]) if shared else out


@functools.partial(jax.jit, static_argnames=("cfg_key", "held", "precision"))
def _experts(x, lw, *, cfg_key, held, precision):
    cfg = dict(cfg_key)
    u = rms_norm(x, lw["norm"], cfg["norm_eps"])
    return x + experts(Arith(precision), u, lw, cfg, held)


def _products_in(precision):
    """The precision of the products under ``precision``: the state
    control rounds the state alone."""
    return "float32" if precision == STATE_CONTROL else precision


def _hidden(cfg, w, ids, precision):
    """The stream ``[T, hidden]`` after the last layer of the full causal
    forward over ``ids [T]``: one layer at a time."""
    key, held = _cfg_key(cfg), held_experts(cfg)
    state_dtype = jnp.bfloat16 if precision == STATE_CONTROL else None
    precision = _products_in(precision)
    x = w["embed"][ids].astype(jnp.float32)
    for i, kind in enumerate(layer_kinds(cfg)):
        lw = _layer_weights(w, i)
        if kind == MAMBA:
            x = _mamba(x, lw, cfg_key=key, precision=precision,
                       state_dtype=state_dtype)
        elif kind == ATTENTION:
            x = _attention(x, lw, cfg_key=key, precision=precision)
        else:
            x = _experts(x, lw, cfg_key=key, held=held, precision=precision)
    return x


def _padded(cfg, prompt, served):
    """prompt + served tokens right-padded to a multiple of ``PAD``
    (causal, so padding changes nothing: a padded token only follows), and
    the positions that produced each served token (as many as
    ``max_new_tokens``, so that one program serves every request)."""
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
    stats = _head(x, w["norm_f"], w["head"], jnp.asarray(at, jnp.int32),
                  jnp.asarray(picks), eps=cfg["norm_eps"],
                  precision=_products_in(precision),
                  block=min(HEAD_BLOCK, w["head"].shape[0]))
    return tuple(s[:n] for s in stats)


def served_logits(cfg: dict, w, prompt, served, precision="float32"):
    """Logits ``[len(served), vocab]`` at the positions that produced each
    served token, whole: for the tests' small sizes."""
    padded, at, n = _padded(cfg, prompt, served)
    xn = rms_norm(_hidden(cfg, w, jnp.asarray(padded), precision),
                  w["norm_f"], cfg["norm_eps"])[at[:n]]
    return Arith(_products_in(precision)).einsum("th,vh->tv", xn, w["head"])


GAP_BLOCK = 256


def block_means(gaps, block=GAP_BLOCK):
    """The mean of ``gaps [n]`` over consecutive blocks of at least
    ``block`` tokens (one block when there are fewer)."""
    import numpy as np
    gaps = np.asarray(gaps, np.float32)
    return np.asarray([b.mean() for b in np.array_split(
        gaps, max(gaps.size // block, 1))], np.float32)


def token_gaps(cfg: dict, w, prompt, served, precision="float32", pick=None):
    """Per served token: how far the reference logit of ``pick`` (the
    served token by default) lies below the reference's best at its
    position, relative to max|logit| there."""
    return _gaps(_served(cfg, w, prompt, served, precision, pick))


def served_gaps(cfg: dict, w, prompt, served, precision="float32"):
    """``token_gaps`` as the mean over each block of ``GAP_BLOCK``
    consecutive served tokens (why blocks: the module docstring); one
    printed line gives the request's mean and lfm2's 64-token reading
    beside it."""
    gaps = token_gaps(cfg, w, prompt, served, precision)
    out = block_means(gaps)
    print(f"reference gaps: {out.size} blocks of {gaps.shape[0]} tokens, "
          f"widest {float(out.max()):.5f}, mean {float(gaps.mean()):.5f}, "
          f"widest of 64-token blocks {float(block_means(gaps, 64).max()):.5f}",
          flush=True)
    return out


def control_gaps(cfg: dict, w, prompt, served, control_precision):
    """A control: the same block means for the tokens that the lower
    precision (``float8_e4m3``, ``bfloat16``, or ``bfloat16_state``: the
    recurrence's state alone rounded after every token) puts first at each
    position of the same prompt and tokens, under the float32 reference."""
    pick = _served(cfg, w, prompt, served, control_precision)[3]
    return block_means(token_gaps(cfg, w, prompt, served, pick=pick))
