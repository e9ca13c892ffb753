"""Flash-decoding: single-query attention over a long cached context.

The decode step of autoregressive generation issues ONE query row per
sequence against the whole KV cache — the flash-attention kernel's grid
(parallel over query blocks) collapses to a single program and leaves the
chip idle.  Flash-Decoding (Dao et al. 2023) recovers the parallelism by
splitting the CONTEXT axis instead: the cache is cut into K splits, each
split computes a partial softmax-attention (running max ``m``, normalizer
``l``, unnormalized accumulator ``acc``) independently, and a cheap final
merge rescales the partials into the exact softmax result:

    g      = max_s m_s
    out    = sum_s acc_s * exp(m_s - g)  /  sum_s l_s * exp(m_s - g)

The merge is mathematically the same online-softmax recombination the
flash forward kernel runs sequentially — here the splits are *parallel*
grid cells and the merge is a tiny O(splits * H) epilogue.

Validity window: the ring cache is left-padded per row, so row ``b``'s
valid columns are the contiguous ``[start[b], end[b])`` — the kernel
masks outside the window with a finite ``-1e30`` (exp underflows to
exactly 0), and fully-masked splits contribute ``l_s = 0`` so the merge
ignores them.

Layout: q ``(B, N, 1, H)``, cached k/v ``(B, N, S, H)``; internally
``(B*N, 8, H)`` (the query row broadcast over the 8 sublanes of one tile)
vs ``(B*N, S, H)``.  Decode is inference-only: no VJP.  This is the
UNPACKED plane contract: the bf16/f32 ring cache packs ``g = 128 // H``
heads per row of the minor dimension for H < 128
(nn/layer/transformer.py ``gen_ring_cache``) and these kernels were not
ported to it, so ``supports_decode`` refuses packed planes and the
kernels serve H >= 128 and the int8 cache (``flash_decode_quant``,
unpacked rows + per-(token, head) scales) only.

Gated OFF behind ``FLAGS_use_flash_decode`` / ``PADDLE_TPU_FLASH_DECODE``:
both kernels compile for v5e and match the XLA reference on the chip
(chip_smoke.py's kernels phase, tests/test_tpu_compile.py), but whether
they beat the XLA masked attention is unmeasured (PERF.md); the
interpret-mode tests bit-match the XLA masked-attention reference.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _mode
from .flash_attention import _pick_block

# split-K block: each grid cell streams this many cached keys through VMEM;
# S/bk splits run in parallel (vs the 1-program degenerate flash grid)
DEFAULT_BLOCK_K_DECODE = 512
_NEG_INF = -1e30  # finite mask value: exp(s - m) underflows to exactly 0
_SUBLANES = 8     # the query row is broadcast over one (8, 128) tile's rows


# the per-row [start, end) window bounds are scalars: the whole int32 [B]
# arrays live in SMEM (a (1, 1) VMEM block of them does not tile)
_SMEM_WHOLE = pl.BlockSpec(memory_space=pltpu.SMEM)


def _window(start, end, B, S):
    """int32 ``[B]`` window bounds (defaults: the full cache)."""
    lo = (jnp.zeros((B,), jnp.int32) if start is None
          else jnp.asarray(start, jnp.int32).reshape(B))
    hi = (jnp.full((B,), S, jnp.int32) if end is None
          else jnp.asarray(end, jnp.int32).reshape(B))
    return lo, hi


def supports_decode(q_shape, k_shape, block: int = 128) -> bool:
    """Shape gate: (B, N, 1, H) query vs (B, N, S, H) cache with S a
    multiple of the split block and H MXU-friendly.  A packed ring
    plane (B, ceil(N/g), S, g*H) fails the head-count and head_dim
    equalities below.  Callers fall back to the XLA masked-attention
    path otherwise."""
    if len(q_shape) != 4 or len(k_shape) != 4:
        return False
    if q_shape[-2] != 1:
        return False                      # single-query decode only
    if q_shape[0] != k_shape[0] or q_shape[1] != k_shape[1]:
        return False
    if q_shape[-1] != k_shape[-1] or q_shape[-1] not in (64, 128, 256):
        return False
    return k_shape[-2] % block == 0


def _decode_kernel(q_ref, k_ref, v_ref, s_ref, e_ref,
                   o_ref, m_ref, l_ref, *, scale, bk, n_heads):
    """One (sequence*head, split) cell: partial attention over the split's
    ``bk`` cached columns, masked to the row's [start, end) window
    (``s_ref``/``e_ref``: the whole int32 ``[B]`` arrays in SMEM)."""
    row = pl.program_id(0) // n_heads
    isplit = pl.program_id(1)
    q = q_ref[0]                                        # [8, H]
    k = k_ref[0]                                        # [bk, H]
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    col = lax.broadcasted_iota(jnp.int32, (_SUBLANES, bk), 1) + isplit * bk
    valid = (col >= s_ref[row]) & (col < e_ref[row])
    s = jnp.where(valid, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)              # [8, 1]
    # explicit zeroing (not just the -1e30 mask): a fully-masked split has
    # m == -1e30, where exp(s - m) == 1 would fake a live normalizer
    p = jnp.exp(s - m) * valid.astype(jnp.float32)
    l = jnp.sum(p, axis=-1, keepdims=True)              # [8, 1]
    acc = lax.dot_general(p.astype(v_ref.dtype), v_ref[0],
                          (((1,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32)
    o_ref[0, 0] = acc
    m_ref[0, 0] = jnp.broadcast_to(m, (_SUBLANES, 128))
    l_ref[0, 0] = jnp.broadcast_to(l, (_SUBLANES, 128))


def flash_decode_fn(q, k, v, start=None, end=None, *, scale=None,
                    block_k: int = DEFAULT_BLOCK_K_DECODE):
    """Pure-jax flash decoding.

    q ``(B, N, 1, H)``; k/v ``(B, N, S, H)``; ``start``/``end`` int32
    ``[B]`` bound the valid cache window per row (defaults: full cache).
    Returns ``(B, N, 1, H)`` in q's dtype.
    """
    B, N, Sq, H = q.shape
    S = k.shape[2]
    if Sq != 1:
        raise ValueError(f"flash_decode takes a single query row, got Sq={Sq}")
    if scale is None:
        scale = 1.0 / math.sqrt(H)
    bk = _pick_block(S, block_k)
    nsplit = S // bk
    BN = B * N
    q3 = jnp.broadcast_to(q.reshape(BN, 1, H), (BN, _SUBLANES, H))
    k3 = k.reshape(BN, S, H)
    v3 = v.reshape(BN, S, H)
    start1, end1 = _window(start, end, B, S)

    o_part, m_part, l_part = pl.pallas_call(
        functools.partial(_decode_kernel, scale=float(scale), bk=bk,
                          n_heads=N),
        grid=(BN, nsplit),
        in_specs=[
            pl.BlockSpec((1, _SUBLANES, H), lambda b, s: (b, 0, 0)),
            pl.BlockSpec((1, bk, H), lambda b, s: (b, s, 0)),
            pl.BlockSpec((1, bk, H), lambda b, s: (b, s, 0)),
            _SMEM_WHOLE,
            _SMEM_WHOLE,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, _SUBLANES, H), lambda b, s: (b, s, 0, 0)),
            pl.BlockSpec((1, 1, _SUBLANES, 128), lambda b, s: (b, s, 0, 0)),
            pl.BlockSpec((1, 1, _SUBLANES, 128), lambda b, s: (b, s, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BN, nsplit, _SUBLANES, H), jnp.float32),
            jax.ShapeDtypeStruct((BN, nsplit, _SUBLANES, 128), jnp.float32),
            jax.ShapeDtypeStruct((BN, nsplit, _SUBLANES, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=4 * BN * S * H,
            bytes_accessed=(k3.size + v3.size + q3.size) * 2,
            transcendentals=BN * S),
        interpret=_mode.interpret(),
    )(q3, k3, v3, start1, end1)

    # split-K merge: exact online-softmax recombination of the partials
    m = m_part[:, :, :, 0]                       # (BN, nsplit, 8)
    l = l_part[:, :, :, 0]
    g = jnp.max(m, axis=1)                       # (BN, 8)
    alpha = jnp.exp(m - g[:, None, :])           # empty split: l == 0 anyway
    l_tot = jnp.sum(l * alpha, axis=1)           # (BN, 8)
    o = jnp.sum(o_part * alpha[..., None], axis=1)
    l_safe = jnp.where(l_tot == 0.0, 1.0, l_tot)
    out = (o / l_safe[..., None]).astype(q.dtype)
    return out[:, :1, :].reshape(B, N, 1, H)


def _decode_kernel_quant(q_ref, k_ref, v_ref, ks_ref, vs_ref, s_ref, e_ref,
                         o_ref, m_ref, l_ref, *, scale, bk, n_heads):
    """Quantized-KV variant of one (sequence*head, split) cell: the
    split's ``bk`` int8 cached rows dequantize INSIDE the split-K loop —
    ``int8 row * per-(token, head) f32 scale`` is a rank-1 broadcast
    against the (bk, H) block, so the f32 K/V tile exists only in VMEM
    for the lifetime of this cell and HBM traffic stays int8."""
    row = pl.program_id(0) // n_heads
    isplit = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)                    # [8, H]
    k = k_ref[0].astype(jnp.float32) * ks_ref[0]        # fused dequant
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    col = lax.broadcasted_iota(jnp.int32, (_SUBLANES, bk), 1) + isplit * bk
    valid = (col >= s_ref[row]) & (col < e_ref[row])
    s = jnp.where(valid, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)              # [8, 1]
    p = jnp.exp(s - m) * valid.astype(jnp.float32)
    l = jnp.sum(p, axis=-1, keepdims=True)              # [8, 1]
    v = v_ref[0].astype(jnp.float32) * vs_ref[0]        # fused dequant
    acc = lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32)
    o_ref[0, 0] = acc
    m_ref[0, 0] = jnp.broadcast_to(m, (_SUBLANES, 128))
    l_ref[0, 0] = jnp.broadcast_to(l, (_SUBLANES, 128))


def flash_decode_quant_fn(q, k, v, k_scale, v_scale, start=None, end=None,
                          *, scale=None,
                          block_k: int = DEFAULT_BLOCK_K_DECODE):
    """Pure-jax flash decoding over an int8-quantized KV ring cache.

    q ``(B, N, 1, H)`` float; k/v ``(B, N, S, H)`` int8 rows with
    ``k_scale``/``v_scale`` ``(B, N, S, 1)`` f32 per-(token, head)
    scales; ``start``/``end`` int32 ``[B]`` bound the valid window per
    row.  Must bit-match ``decode_attention_reference`` over the
    dequantized cache (``dequantize_kv`` below) — the dequant moves
    inside the kernel, the math does not change.  Returns
    ``(B, N, 1, H)`` in q's dtype.
    """
    B, N, Sq, H = q.shape
    S = k.shape[2]
    if Sq != 1:
        raise ValueError(f"flash_decode takes a single query row, got Sq={Sq}")
    if scale is None:
        scale = 1.0 / math.sqrt(H)
    bk = _pick_block(S, block_k)
    nsplit = S // bk
    BN = B * N
    q3 = jnp.broadcast_to(q.reshape(BN, 1, H), (BN, _SUBLANES, H))
    k3 = k.reshape(BN, S, H)
    v3 = v.reshape(BN, S, H)
    ks3 = k_scale.reshape(BN, S, 1)
    vs3 = v_scale.reshape(BN, S, 1)
    start1, end1 = _window(start, end, B, S)

    o_part, m_part, l_part = pl.pallas_call(
        functools.partial(_decode_kernel_quant, scale=float(scale), bk=bk,
                          n_heads=N),
        grid=(BN, nsplit),
        in_specs=[
            pl.BlockSpec((1, _SUBLANES, H), lambda b, s: (b, 0, 0)),
            pl.BlockSpec((1, bk, H), lambda b, s: (b, s, 0)),
            pl.BlockSpec((1, bk, H), lambda b, s: (b, s, 0)),
            pl.BlockSpec((1, bk, 1), lambda b, s: (b, s, 0)),
            pl.BlockSpec((1, bk, 1), lambda b, s: (b, s, 0)),
            _SMEM_WHOLE,
            _SMEM_WHOLE,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, _SUBLANES, H), lambda b, s: (b, s, 0, 0)),
            pl.BlockSpec((1, 1, _SUBLANES, 128), lambda b, s: (b, s, 0, 0)),
            pl.BlockSpec((1, 1, _SUBLANES, 128), lambda b, s: (b, s, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BN, nsplit, _SUBLANES, H), jnp.float32),
            jax.ShapeDtypeStruct((BN, nsplit, _SUBLANES, 128), jnp.float32),
            jax.ShapeDtypeStruct((BN, nsplit, _SUBLANES, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=4 * BN * S * H,
            # the point of the fused dequant: K/V stream at 1 byte/elt
            bytes_accessed=(k3.size + v3.size
                            + (ks3.size + vs3.size + q3.size) * 4),
            transcendentals=BN * S),
        interpret=_mode.interpret(),
    )(q3, k3, v3, ks3, vs3, start1, end1)

    m = m_part[:, :, :, 0]                       # (BN, nsplit, 8)
    l = l_part[:, :, :, 0]
    g = jnp.max(m, axis=1)                       # (BN, 8)
    alpha = jnp.exp(m - g[:, None, :])
    l_tot = jnp.sum(l * alpha, axis=1)           # (BN, 8)
    o = jnp.sum(o_part * alpha[..., None], axis=1)
    l_safe = jnp.where(l_tot == 0.0, 1.0, l_tot)
    out = (o / l_safe[..., None]).astype(q.dtype)
    return out[:, :1, :].reshape(B, N, 1, H)


def dequantize_kv(q8, scales, dtype=jnp.float32):
    """Dequantize int8 KV rows with their per-(token, head) scales — the
    XLA fallback read, and the reference the fused kernel must match."""
    return (jnp.asarray(q8).astype(jnp.float32)
            * jnp.asarray(scales)).astype(dtype)


def decode_attention_reference(q, k, v, start=None, end=None, *, scale=None):
    """The XLA reference the kernel must match: one masked softmax
    attention over the full cache, f32 logits/accumulation (the same
    numerics contract as nn.functional's ``_sdpa_mask``)."""
    B, N, Sq, H = q.shape
    S = k.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(H)
    logits = jnp.einsum("bnsh,bnth->bnst", q, k,
                        preferred_element_type=jnp.float32) * scale
    col = jnp.arange(S, dtype=jnp.int32)
    lo = jnp.zeros((B,), jnp.int32) if start is None \
        else jnp.asarray(start, jnp.int32)
    hi = jnp.full((B,), S, jnp.int32) if end is None \
        else jnp.asarray(end, jnp.int32)
    valid = (col[None, :] >= lo[:, None]) & (col[None, :] < hi[:, None])
    logits = jnp.where(valid[:, None, None, :], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bnst,bnth->bnsh", probs.astype(q.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)
