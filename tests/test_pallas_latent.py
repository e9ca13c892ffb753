"""The latent family's one-kernel per-head attention against the XLA loop
it replaces (``latent_attend_blocked(per_head_products(...))``; for a
window layer's ring mask the one pass ``latent_attend``).

Interpret mode on the CPU backend (conftest), lane-aligned small shapes.
The two agree to float32 rounding, not to the bit: the kernel sums ``q_n .
k_n + q_r . k_r`` where the loop contracts the concatenation.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nn.functional.attention import (
    PerHeadOperands, latent_attend, latent_attend_blocked,
    latent_attend_fused, per_head_products)
from paddle_tpu.ops.pallas.latent_attention import (
    _plan, fused_latent_form, supports_latent)

BLK = 128
# (d_n, d_r, d_v, r_kv, row width): the ratios of the three published
# width sets, at a latent narrow enough for the interpreter
DOTS3 = (128, 64, 128, 256, 384)
GLM5 = (192, 64, 256, 256, 384)
WINDOW = (192, 64, 128, 384, 512)

# name: (widths, H, T, columns, pos, starts (batch 1 each), mask)
#   mask "causal": start <= column <= pos + t, from the two scalars;
#   "selector": a membership that binds (half the valid columns);
#   "selector_all": a membership that keeps every valid column;
#   "ring": a window layer's mask after its plane has wrapped
CASES = {
    "dots3_selector_binds": (DOTS3, 4, 128, 512, 256, (37,), "selector"),
    "dots3_selector_keeps_all": (DOTS3, 4, 128, 512, 256, (37,),
                                 "selector_all"),
    "kimi_causal_start_inside_a_block": (DOTS3, 2, 128, 512, 384, (37,),
                                         "causal"),
    "kimi_lo_above_zero": (DOTS3, 2, 128, 512, 384, (150,), "causal"),
    "kimi_span_of_one_block": (DOTS3, 2, 128, 512, 0, (5,), "causal"),
    "kimi_span_of_all_blocks": (DOTS3, 2, 128, 512, 384, (0,), "causal"),
    "three_rows_starts": (DOTS3, 2, 128, 512, 256, (0, 131, 300), "causal"),
    "glm5_selector_binds": (GLM5, 2, 128, 384, 256, (3,), "selector"),
    "glm5_causal_T256": (GLM5, 2, 256, 512, 256, (64,), "causal"),
    "window_ring_wrapped": (WINDOW, 2, 128, 256, 1024, (700,), "ring"),
    "dead_row": (DOTS3, 2, 128, 256, 128, (10 ** 6,), "causal"),
    "dead_row_under_a_selector": (DOTS3, 2, 128, 256, 128, (10 ** 6,),
                                  "selector"),
}


def _operands(widths, H, T, S, dtype, seed):
    d_n, d_r, d_v, r, K = widths
    ks = jax.random.split(jax.random.key(seed), 5)
    mk = lambda k, shape, s=1.0: (jax.random.normal(k, shape)    # noqa: E731
                                  * s).astype(dtype)
    return (mk(ks[0], (1, T, H, d_n)), mk(ks[1], (1, T, H, d_r)),
            mk(ks[2], (H, r, d_n), r ** -0.5),
            mk(ks[3], (H, r, d_v), r ** -0.5), mk(ks[4], (1, S, K)))


def _both(case, dtype, start, seed):
    """(the kernel's, the XLA form's) output for ONE row that starts at
    ``start``: ``[1, T, H, d_v]`` float32 each."""
    widths, H, T, S, pos, _, mask = CASES[case]
    d_n, d_r, d_v, r, K = widths
    q_n, q_r, w_uk, w_uv, plane = _operands(widths, H, T, S, dtype, seed)
    scale = (d_n + d_r) ** -0.5
    start = jnp.asarray([start], jnp.int32)
    cols = pos + jnp.arange(T, dtype=jnp.int32)
    valid_of = lambda s: ((s[None, None, :] >= start[:, None, None])  # noqa
                          & (s[None, None, :] <= cols[None, :, None]))
    products = per_head_products(q_n, q_r, w_uk, w_uv, r, scale)
    operands = PerHeadOperands(q_n, q_r, w_uk, w_uv, r, scale)
    if mask == "ring":
        window = 100
        j = jnp.arange(S, dtype=jnp.int32)
        c = cols[:, None] - (cols[:, None] - j[None, :]) % S
        keep = (c[None] > cols[None, :, None] - window) \
            & (c[None] >= start[:, None, None])
        ref = latent_attend(products, plane, keep)
        got = latent_attend_fused(operands, plane, 0, S // BLK, BLK, start,
                                  cols[0], keep=keep)
        return got, ref
    lo, hi = jnp.min(start) // BLK, cols[-1] // BLK + 1
    if mask == "causal":
        keep = None
        keep_of = lambda s0: valid_of(                           # noqa: E731
            s0 + jnp.arange(BLK, dtype=jnp.int32))
    else:
        keep = valid_of(jnp.arange(S, dtype=jnp.int32))
        if mask == "selector":
            keep &= jax.random.uniform(jax.random.key(seed + 1),
                                       keep.shape) < 0.5
        keep_of = lambda s0: jax.lax.dynamic_slice(             # noqa: E731
            keep, (0, 0, s0), (1, T, BLK))
    ref = latent_attend_blocked(products, plane, keep_of, lo, hi, BLK)
    # the span is traced in the served program: trace it here too
    got = jax.jit(lambda lo, hi: latent_attend_fused(
        operands, plane, lo, hi, BLK, start, cols[0], keep=keep))(lo, hi)
    return got, ref


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(CASES))
def test_kernel_matches_the_xla_loop(case, dtype):
    """Every case of ``CASES`` in both dtypes; a row at a time (the served
    chunk is batch 1).  float32: 1e-5 of max|out|.  bfloat16: the two
    round the same operands at the same places, so what is left is the
    float32 sums' order and where a probability or an expanded key falls
    on the other side of a bfloat16 rounding: 2^-7 of max|out|.  A dead
    row (nothing kept) is finite in both."""
    for i, start in enumerate(CASES[case][5]):
        got, ref = _both(case, dtype, start, seed=11 + i)
        got, ref = np.asarray(got), np.asarray(ref)
        assert got.shape == ref.shape and got.dtype == np.float32
        assert np.isfinite(got).all() and np.isfinite(ref).all()
        if case.startswith("dead_row"):
            continue
        tol = 1e-5 if dtype == jnp.float32 else 2.0 ** -7
        assert np.abs(got - ref).max() <= tol * np.abs(ref).max(), \
            (case, start, np.abs(got - ref).max() / np.abs(ref).max())


def test_no_live_block_reads_zero():
    """``lo >= hi`` (no block in the span): the loop runs over nothing and
    both forms return zeros, not a stale buffer."""
    widths, H, T, S = DOTS3, 2, 128, 256
    q_n, q_r, w_uk, w_uv, plane = _operands(widths, H, T, S, jnp.float32, 3)
    operands = PerHeadOperands(q_n, q_r, w_uk, w_uv, widths[3], 0.07)
    got = latent_attend_fused(operands, plane, 2, 2, BLK,
                              jnp.zeros((1,), jnp.int32), jnp.int32(0))
    assert not np.asarray(got).any()


@pytest.mark.parametrize("shape,admits", [
    # T, block, d_n, d_r, d_v, r_kv, row width
    ((512, 512, 128, 64, 128, 512, 640), True),       # dots3 full, kimi
    ((512, 512, 192, 64, 256, 512, 640), True),       # glm5
    ((512, 512, 192, 64, 128, 1024, 1152), True),     # dots3 window
    ((1, 512, 128, 64, 128, 512, 640), False),        # a step's one query
    ((500, 512, 128, 64, 128, 512, 640), False),
    ((512, 500, 128, 64, 128, 512, 640), False),
    ((32, 32, 8, 4, 8, 12, 128), False),              # the tiny models
    ((512, 512, 128, 64, 128, 512, 576), False),      # rows off the lanes
    ((512, 512, 128, 64, 96, 512, 640), False),
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_supports_latent_gate(shape, admits):
    """What the kernel supports is what the rule takes, for an even
    number of heads."""
    assert supports_latent(*shape) == admits
    assert fused_latent_form(*shape, heads=128) == admits
    assert fused_latent_form(*shape, heads=64) == admits
    assert not fused_latent_form(*shape, heads=63)


def test_plan_keeps_the_heads_in_vmem():
    """Heads a program at the three published width sets: a power of two
    that divides the layer's heads, one variant for dots3's two full
    layers and kimi's five."""
    for H, (d_n, d_r, d_v, r, K) in ((128, (128, 64, 128, 512, 640)),
                                     (64, (128, 64, 128, 512, 640)),
                                     (64, (192, 64, 256, 512, 640)),
                                     (64, (192, 64, 128, 1024, 1152))):
        G = _plan(H, 512, d_n, d_r, d_v, r, 2)
        assert G == 8 and H % G == 0
