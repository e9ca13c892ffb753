"""Plain reference of BERT masked-LM pretraining: forward, loss, gradients
and AdamW in straightforward ``jax.numpy``, float32 at precision "highest".

Follows Devlin et al. 2018 (post-LayerNorm encoder, exact GELU, tied MLM
decoder).  Departures, all to follow what the program computes: LayerNorm
epsilon is 1e-5 inside the encoder layers and ``layer_norm_eps`` in the
embeddings and the head; no next-sentence loss (the pooler and the NSP head
only see weight decay); no attention mask (every position is a token).

Weights live in one canonical tree with the encoder layers stacked on a
leading axis; ``benchmark/models/bert.py`` maps it onto the program's
parameter names.  Imports nothing of the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .common import (Arith, attention, ffn, gelu, layer_norm, leaf_norms,
                     seed_key, sketch_salt, sketches)

ENC_LN_EPS = 1e-5


def init_weights(cfg: dict, seed: int, dtype=jnp.float32):
    """The canonical weight tree from ``seed``, made on the device in one
    jitted call: matrices and embeddings N(0, initializer_range), biases 0,
    LayerNorm gains 1 — the published initialisation."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    L, V = cfg["num_hidden_layers"], cfg["vocab_size"]
    std = cfg["initializer_range"]
    shapes = {
        "word": (V, h), "pos": (cfg["max_position_embeddings"], h),
        "type": (cfg["type_vocab_size"], h),
        "pool_w": (h, h), "head_w": (h, h), "nsp_w": (h, 2),
    }
    lshapes = {"q_w": (L, h, h), "k_w": (L, h, h), "v_w": (L, h, h),
               "o_w": (L, h, h), "f1_w": (L, h, f), "f2_w": (L, f, h)}
    zeros = {"emb_ln_b": (h,), "pool_b": (h,), "head_b": (h,),
             "head_ln_b": (h,), "dec_b": (V,), "nsp_b": (2,)}
    lzeros = {"q_b": (L, h), "k_b": (L, h), "v_b": (L, h), "o_b": (L, h),
              "ln1_b": (L, h), "f1_b": (L, f), "f2_b": (L, h),
              "ln2_b": (L, h)}
    ones = {"emb_ln_g": (h,), "head_ln_g": (h,)}
    lones = {"ln1_g": (L, h), "ln2_g": (L, h)}

    @jax.jit
    def make(key):
        def normals(shapes, key):
            keys = jax.random.split(key, len(shapes))
            return {n: (jax.random.normal(k, s, jnp.float32) * std)
                    .astype(dtype)
                    for k, (n, s) in zip(keys, sorted(shapes.items()))}
        k1, k2 = jax.random.split(key)
        w = normals(shapes, k1)
        w.update({n: jnp.zeros(s, dtype) for n, s in zeros.items()})
        w.update({n: jnp.ones(s, dtype) for n, s in ones.items()})
        lw = normals(lshapes, k2)
        lw.update({n: jnp.zeros(s, dtype) for n, s in lzeros.items()})
        lw.update({n: jnp.ones(s, dtype) for n, s in lones.items()})
        w["layers"] = lw
        return w

    return make(seed_key(seed))


def encode(ar: Arith, cfg: dict, w, ids):
    """Sequence output [b, s, h] of the encoder for token ids [b, s]."""
    s = ids.shape[1]
    f32 = jnp.float32
    x = (w["word"][ids].astype(f32) + w["pos"][:s][None].astype(f32)
         + w["type"][0][None, None].astype(f32))
    x = layer_norm(x, w["emb_ln_g"], w["emb_ln_b"], cfg["layer_norm_eps"])
    heads = cfg["num_attention_heads"]

    @jax.checkpoint
    def layer(x, lw):
        x = layer_norm(x + attention(ar, x, lw, heads, causal=False),
                       lw["ln1_g"], lw["ln1_b"], ENC_LN_EPS)
        x = layer_norm(x + ffn(ar, x, lw), lw["ln2_g"], lw["ln2_b"],
                       ENC_LN_EPS)
        return x, None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    return x


def mlm_loss_sum(ar: Arith, cfg: dict, w, ids, positions, labels):
    """Sum over the masked positions of the cross entropy (not the mean:
    blocks of rows add up)."""
    f32 = jnp.float32
    x = encode(ar, cfg, w, ids)
    x = jnp.take_along_axis(x, positions[:, :, None], axis=1)   # [b, P, h]
    t = gelu(ar.einsum("bph,hk->bpk", x, w["head_w"]) + w["head_b"].astype(f32))
    t = layer_norm(t, w["head_ln_g"], w["head_ln_b"], cfg["layer_norm_eps"])
    logits = ar.einsum("bph,vh->bpv", t, w["word"]) + w["dec_b"].astype(f32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, :, None], axis=-1)[..., 0]
    return jnp.sum(logz - picked)


def mlm_loss(cfg: dict, w, ids, positions, labels, precision="float32"):
    """Mean masked-LM loss of one batch (forward only)."""
    ar = Arith(precision)
    n = positions.shape[0] * positions.shape[1]
    return mlm_loss_sum(ar, cfg, w, ids, positions, labels) / n


def _adamw(hp, w, g, m, v, t):
    b1, b2 = hp["beta1"], hp["beta2"]
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t

    def upd(p, g, m, v):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        step = hp["learning_rate"] * (
            (m / bc1) / (jnp.sqrt(v / bc2) + hp["epsilon"])
            + hp["weight_decay"] * p)
        return p - step, m, v

    out = jax.tree_util.tree_map(upd, w, g, m, v)
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda x: x[i], out, is_leaf=lambda x: isinstance(x, tuple))
    return pick(0), pick(1), pick(2)


def train_steps(cfg: dict, w0, batches, precision="float32", rows_per_block=1,
                sketch_seed=0):
    """Follow the first ``len(batches)`` AdamW steps from ``w0``.

    ``w0`` is the canonical weight tree, or the seed it is made from.
    ``batches`` is a list of (ids [B, s], positions [B, P], labels [B, P]).
    The batch is walked in blocks of ``rows_per_block`` rows whose gradients
    add up, so that float32 activations fit beside the state.  Returns
    (losses, (first-gradient norms per leaf, first-gradient sketches),
    parameter-change norms per leaf after the last step), norms as
    ``leaf_norms`` and sketches as ``sketches`` (seeded by ``sketch_seed``)
    give them.
    """
    hp = cfg["train"]
    ar = Arith(precision)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(w, m, v, t, salt, ids, positions, labels):
        B = ids.shape[0]
        nb = B // rows_per_block
        blocks = jax.tree_util.tree_map(
            lambda a: a.reshape((nb, rows_per_block) + a.shape[1:]),
            (ids, positions, labels))
        vg = jax.value_and_grad(
            lambda w, i, p, l: mlm_loss_sum(ar, cfg, w, i, p, l))

        def micro(acc, blk):
            l, g = vg(w, *blk)
            return (acc[0] + l, jax.tree_util.tree_map(jnp.add, acc[1], g)), None

        zero = jax.tree_util.tree_map(jnp.zeros_like, w)
        (lsum, gsum), _ = jax.lax.scan(micro, (jnp.float32(0.0), zero), blocks)
        n = positions.shape[0] * positions.shape[1]
        g = jax.tree_util.tree_map(lambda a: a / n, gsum)
        w, m, v = _adamw(hp, w, g, m, v, t)
        return w, m, v, lsum / n, (leaf_norms(g), sketches(g, salt))

    @jax.jit
    def delta_norms(w, w0):
        return leaf_norms(jax.tree_util.tree_map(lambda a, b: a - b, w, w0))

    seed_of_w0 = None
    if not isinstance(w0, dict):
        # (cfg-seed) instead of a tree: the start is made here and made
        # again for the final difference, so that at most the state's own
        # three copies are ever held
        seed_of_w0, w0 = int(w0), init_weights(cfg, int(w0))
    w = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32) + 0, w0)
    if seed_of_w0 is not None:
        del w0
    m = jax.tree_util.tree_map(jnp.zeros_like, w)
    v = jax.tree_util.tree_map(jnp.zeros_like, w)
    losses, gnorm = [], None
    for t, (ids, positions, labels) in enumerate(batches, start=1):
        w, m, v, loss, gn = step(w, m, v, jnp.float32(t),
                                 sketch_salt(sketch_seed), jnp.asarray(ids),
                                 jnp.asarray(positions), jnp.asarray(labels))
        losses.append(loss)
        if t == 1:
            gnorm = gn
    del m, v
    if seed_of_w0 is not None:
        w0 = init_weights(cfg, seed_of_w0)
    dnorm = delta_norms(w, jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), w0))
    return [float(x) for x in losses], gnorm, dnorm
