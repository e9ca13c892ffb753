"""Sequence/context parallelism: ring attention over the ``sp`` mesh axis.

The 2.0-rc reference has NO long-context machinery (SURVEY.md §5: no ring
attention / context parallel anywhere in the tree) — its longest-sequence
tools are recompute and pipeline microbatching.  The TPU build makes
sequence sharding first-class per the build plan (§7): activations shard the
sequence dim over ``sp``, and attention runs as a RING — each shard holds
its local Q block, K/V blocks rotate around the ICI ring via
lax.ppermute, and softmax is accumulated online (flash-attention style
m/l/acc carry), so the full S×S score matrix never materializes and
communication overlaps compute around the ring.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .mesh import get_mesh, SP_AXIS, DP_AXIS


def _ring_attention_shard(q, k, v, *, scale, causal, axis):
    """Per-shard ring attention body (inside shard_map).

    q,k,v: [B, H, s_loc, D] local blocks; returns [B, H, s_loc, D].
    """
    S = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    s_loc = q.shape[2]
    perm = [(i, (i + 1) % S) for i in range(S)]

    q_pos = idx * s_loc + jnp.arange(s_loc)  # global positions of my queries

    def step(carry, t):
        k_blk, v_blk, acc, m, l = carry
        # source rank of the kv block currently held: it has been shifted t
        # times from its home rank (idx - t)
        src = (idx - t) % S
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk) * scale
        if causal:
            k_pos = src * s_loc + jnp.arange(s_loc)
            mask = q_pos[:, None] >= k_pos[None, :]
            scores = jnp.where(mask[None, None], scores, -jnp.inf)
        blk_max = jnp.max(scores, axis=-1)
        new_m = jnp.maximum(m, blk_max)
        # guard fully-masked blocks: exp(-inf - -inf) patterns
        safe_m = jnp.where(jnp.isneginf(new_m), 0.0, new_m)
        corr = jnp.exp(jnp.where(jnp.isneginf(m), -jnp.inf, m) - safe_m)
        corr = jnp.where(jnp.isneginf(m), 0.0, corr)
        p = jnp.exp(scores - safe_m[..., None])
        p = jnp.where(jnp.isneginf(scores), 0.0, p)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd",
                                                     p.astype(v_blk.dtype),
                                                     v_blk)
        k_next = lax.ppermute(k_blk, axis, perm)
        v_next = lax.ppermute(v_blk, axis, perm)
        return (k_next, v_next, acc_new, new_m, l_new), None

    # fresh accumulators must carry the same varying-manual-axes type as the
    # ring-shifted values they mix with; deriving them from q (rather than
    # bare zeros) inherits exactly q's VMA set (sp, and dp when batch-sharded)
    acc0 = (q * 0).astype(jnp.float32)
    m0 = jnp.sum(q, axis=-1).astype(jnp.float32) * 0 - jnp.inf
    l0 = jnp.sum(q, axis=-1).astype(jnp.float32) * 0
    (k_f, v_f, acc, m, l), _ = lax.scan(
        step, (k, v, acc0, m0, l0), jnp.arange(S))
    out = acc / jnp.maximum(l[..., None], 1e-20)
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh=None, causal=False, axis=SP_AXIS):
    """Sequence-parallel attention.

    q,k,v: [B, H, S, D] arrays (or Tensors) with S shardable over the sp
    axis. Returns [B, H, S, D]. With sp absent/size 1, falls back to plain
    softmax attention (identical numerics — ring with S=1 is exact).
    """
    from ..framework.tensor import Tensor
    from jax import shard_map

    unwrap = lambda x: x._value if isinstance(x, Tensor) else jnp.asarray(x)
    qa, ka, va = unwrap(q), unwrap(k), unwrap(v)
    mesh = mesh or get_mesh()
    sp = mesh.shape.get(axis, 1)
    scale = 1.0 / math.sqrt(qa.shape[-1])

    if sp <= 1:
        scores = jnp.einsum("bhqd,bhkd->bhqk", qa, ka) * scale
        if causal:
            s = qa.shape[2]
            mask = jnp.tril(jnp.ones((s, s), bool))
            scores = jnp.where(mask[None, None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", p.astype(va.dtype), va)
        return Tensor(out) if isinstance(q, Tensor) else out

    dp = mesh.shape.get(DP_AXIS, 1)
    bspec = DP_AXIS if (dp > 1 and qa.shape[0] % dp == 0) else None
    spec = P(bspec, None, axis, None)
    body = functools.partial(_ring_attention_shard, scale=scale,
                             causal=causal, axis=axis)
    fn = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec)
    qa = jax.device_put(qa, NamedSharding(mesh, spec))
    ka = jax.device_put(ka, NamedSharding(mesh, spec))
    va = jax.device_put(va, NamedSharding(mesh, spec))
    out = fn(qa, ka, va)
    return Tensor(out) if isinstance(q, Tensor) else out
