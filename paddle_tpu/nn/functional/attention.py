"""Attention functionals.

Reference parity: the reference era predates fused attention ops (it has only
softmax/matmul composition inside nn/layer/transformer.py); we expose a
first-class ``scaled_dot_product_attention`` because it is THE hot op on TPU.
Default path is a single fused XLA expression (bf16 matmuls on the MXU with
f32 softmax accumulation); when FLAGS_use_pallas_kernels is set and we're on
TPU, the Pallas flash-attention kernel (paddle_tpu/ops/pallas/) takes over.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...framework.flags import flag
from ...framework.primitive import Primitive
from ...framework.tensor import Tensor, unwrap


def _sdpa_fn(q, k, v, scale=None, causal=False):
    # q,k,v: (B, N, S, H) -- batch, heads, seq, head_dim
    hd = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(hd)
    logits = jnp.einsum("bnsh,bnth->bnst", q, k,
                        preferred_element_type=jnp.float32) * s
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(mask, logits, jnp.asarray(-1e30, logits.dtype))
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bnst,bnth->bnsh", probs, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _sdpa_mask_fn(q, k, v, mask, scale=None, causal=False):
    hd = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(hd)
    logits = jnp.einsum("bnsh,bnth->bnst", q, k,
                        preferred_element_type=jnp.float32) * s
    logits = logits + mask.astype(logits.dtype)
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(cm, logits, jnp.asarray(-1e30, logits.dtype))
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bnst,bnth->bnsh", probs, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _sdpa_packed_fn(q, k, v, mask=None):
    """Masked attention of ``(B, N, Tq, H)`` queries over PACKED ring
    planes ``(B, G, C, g*H)`` (``g`` adjacent heads side by side on the
    minor dim, ``G = ceil(N/g)``; nn/layer/transformer.py
    ``gen_ring_cache``).  The planes are never reshaped — splitting
    their lanes would bring back a full-plane copy — only the small
    operands are rearranged: each query is spread over its group's
    ``g*H`` lanes with zeros outside its own head's, so contracting
    over all lanes is the head's own dot product plus exact zeros; the
    probabilities contract with V over the columns and each head keeps
    its own ``H`` lanes of the result (the inverse of ``pack_heads``).
    Same scale, mask, f32 softmax and accumulation as
    :func:`_sdpa_mask_fn`."""
    b, n, t, hd = q.shape
    groups, lanes = k.shape[1], k.shape[3]
    g = lanes // hd
    qg = jnp.pad(q, ((0, 0), (0, groups * g - n), (0, 0), (0, 0))) \
        .reshape(b, groups, g, t, hd)
    own = jnp.arange(lanes)[None, :] // hd == jnp.arange(g)[:, None]
    qs = jnp.where(own[None, None, :, None, :],
                   jnp.tile(qg, (1, 1, 1, 1, g)), jnp.zeros((), q.dtype))
    logits = jnp.einsum("bgjtl,bgcl->bgjtc", qs, k,
                        preferred_element_type=jnp.float32) \
        * (1.0 / math.sqrt(hd))
    if mask is not None:
        logits = logits + mask.astype(logits.dtype)[:, :, None]
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bgjtc,bgcl->bgjtl", probs, v,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    # each head keeps its own lanes: select and sum over j (one term is
    # non-zero, so the sum is exact), NOT a stack of out[..., j, :,
    # j*H:(j+1)*H] slices — the TPU compiler of jax 0.9.0 miscompiles a
    # concatenate of slices taken at a lane offset (wrong values on the
    # v5e, right on the CPU; PERF.md section 6, PR 25)
    out = jnp.where(own[None, None, :, None, :], out,
                    jnp.zeros((), q.dtype)).sum(axis=2)
    return out.reshape(b, groups, t, g, hd).transpose(0, 1, 3, 2, 4) \
        .reshape(b, groups * g, t, hd)[:, :n]


_sdpa = Primitive("scaled_dot_product_attention", _sdpa_fn)
_sdpa_mask = Primitive("scaled_dot_product_attention_mask", _sdpa_mask_fn)
_sdpa_packed = Primitive("scaled_dot_product_attention_packed",
                         _sdpa_packed_fn)


def _use_pallas(q, k, mask=None, causal=False):
    if not flag("use_pallas_kernels") or jax.default_backend() != "tpu":
        return False
    # the flash kernel's bias input is non-differentiable; a trainable mask
    # (learned relative-position bias) must take the XLA path
    if isinstance(mask, Tensor) and not mask.stop_gradient:
        return False
    from ...ops.pallas import supports
    from ...ops.pallas.flash_attention import MIN_SEQ_FOR_FLASH
    kshape = unwrap(k).shape
    # short sequences are dispatch/bandwidth-bound: the one-expression XLA
    # path wins there (measured crossover at Sk=1024 on v5e)
    if len(kshape) != 4 or kshape[-2] < MIN_SEQ_FOR_FLASH:
        return False
    mk = unwrap(mask).shape if mask is not None else None
    return supports(unwrap(q).shape, kshape, mk, causal=causal)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None):
    """Inputs (B, S, N, H) per paddle-incubate convention; internally uses
    (B, N, S, H)."""
    from ...ops import transpose
    q = transpose(query, [0, 2, 1, 3])
    k = transpose(key, [0, 2, 1, 3])
    v = transpose(value, [0, 2, 1, 3])
    if _use_pallas(q, k, attn_mask, causal=bool(is_causal)):
        from ...ops.pallas import flash_attention
        out = flash_attention(q, k, v, bias=attn_mask, causal=is_causal)
    elif attn_mask is not None:
        out = _sdpa_mask(q, k, v, attn_mask, causal=bool(is_causal))
    else:
        out = _sdpa(q, k, v, causal=bool(is_causal))
    if dropout_p and training:
        from .common import dropout
        out = dropout(out, dropout_p, training=training)
    return transpose(out, [0, 2, 1, 3])


def _use_flash_decode(q, k, window):
    """Dispatch gate for the decode step: FLAGS_use_flash_decode + TPU
    platform + single-query shapes + a contiguous [start, end) validity
    window (the kernel masks a window, not an arbitrary dense mask) +
    UNPACKED (B, N, S, H) planes (``supports_decode`` refuses a cache
    whose head count or head_dim differs from the query's): the Pallas
    kernels index heads at axis 1 and were not ported to the packed
    ring planes, so today they can serve head_dim >= 128 and the int8
    cache only."""
    if window is None or not flag("use_flash_decode") \
            or jax.default_backend() != "tpu":
        return False
    from ...ops.pallas.flash_decode import supports_decode
    return supports_decode(unwrap(q).shape, unwrap(k).shape)


def cached_attention(q, k, v, attn_mask=None, window=None, k_scale=None,
                     v_scale=None):
    """Incremental attention: (B, N, Tq, H) new-token queries over the
    full KV ring cache — bf16/f32 planes packed ``(B, ceil(N/g), S,
    g*H)`` as ``gen_ring_cache`` builds them (``g`` is read from the
    plane's minor dim; ``g == 1`` is the plain (B, N, S, H) plane), or
    the int8 cache's unpacked (B, N, S, H) rows.

    ``attn_mask`` is the additive validity+causality mask the caller
    built from cache_position / per-row start offsets.  ``window`` is the
    optional ``(start[B], end[B])`` contiguous form of the same validity
    (decode steps: Tq == 1) — when present and eligible, the Pallas
    flash-decoding kernel (split-K over the cached context) takes over;
    otherwise the one-expression XLA masked attention runs.

    With ``k_scale``/``v_scale`` given (FLAGS_kv_cache_dtype=int8), k/v
    are int8 row planes and the scales are the per-(token, head) f32
    planes: the eligible kernel path fuses the dequant into its split-K
    loop (flash_decode_quant); the XLA fallback dequantizes the cache
    then attends (decode is inference-only, so the raw read costs no
    tape).
    """
    if k_scale is not None:
        if _use_flash_decode(q, k, window):
            from ...ops.pallas import flash_decode_quant
            return flash_decode_quant(q, k, v, k_scale, v_scale,
                                      window[0], window[1])
        from ..layer.transformer import dequantize_kv_rows
        dt = unwrap(q).dtype
        k = Tensor(dequantize_kv_rows(k, k_scale, dtype=dt))
        v = Tensor(dequantize_kv_rows(v, v_scale, dtype=dt))
    if _use_flash_decode(q, k, window):
        from ...ops.pallas import flash_decode
        return flash_decode(q, k, v, window[0], window[1])
    if unwrap(k).shape[-1] != unwrap(q).shape[-1]:
        return _sdpa_packed(q, k, v, attn_mask) if attn_mask is not None \
            else _sdpa_packed(q, k, v)
    if attn_mask is not None:
        return _sdpa_mask(q, k, v, attn_mask)
    return _sdpa(q, k, v)


def attention_bnsh(q, k, v, attn_mask=None, is_causal=False):
    """(B, N, S, H) layout fast path used by our MultiHeadAttention layer."""
    if _use_pallas(q, k, attn_mask, causal=bool(is_causal)):
        from ...ops.pallas import flash_attention
        return flash_attention(q, k, v, bias=attn_mask, causal=is_causal)
    if attn_mask is not None:
        return _sdpa_mask(q, k, v, attn_mask, causal=bool(is_causal))
    return _sdpa(q, k, v, causal=bool(is_causal))
