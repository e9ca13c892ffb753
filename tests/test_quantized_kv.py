"""int8-quantized KV ring cache tests.

The quantized ring writes must store int8 rows + per-(token, head) f32
scale planes at the same traced position, the read must equal the exact
masked attention over the dequantized planes, cache plane bytes/token
must halve vs bf16 (plus the scale overhead), and quantization must
compose with both plain and speculative generate() behind
FLAGS_kv_cache_dtype with one Python branch off-path.  (The read under a
decode window: tests/test_decode_blocked_attention.py.)"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.framework.flags import flags_restore, flags_snapshot, \
    set_flags
from paddle_tpu.nn.layer.transformer import (MultiHeadAttention,
                                             dequantize_kv_rows,
                                             kv_heads_per_lane_row,
                                             quantize_kv_rows)
from paddle_tpu.profiler import ledger
from paddle_tpu.text.generation import Generator
from paddle_tpu.text.models.gpt import GPTConfig, GPTModel

V = 64


def _rand(shape, seed=0):
    return np.random.RandomState(seed).randn(*shape).astype(np.float32)


# -- quantize/dequantize row helpers -----------------------------------------

def test_quantize_kv_rows_roundtrip_error_bound():
    x = _rand((2, 3, 8, 16), seed=3)
    q, s = quantize_kv_rows(jnp.asarray(x))
    assert q.dtype == jnp.int8 and q.shape == x.shape
    assert s.dtype == jnp.float32 and s.shape == (2, 3, 8, 1)
    back = np.asarray(dequantize_kv_rows(q, s))
    # symmetric int8: error bounded by half a quantization step per row
    step = np.asarray(s)[..., 0]
    assert (np.abs(back - x).max(-1) <= step * 0.5 + 1e-7).all()


# -- quantized ring cache through the attention layer ------------------------

def test_forward_ring_quant_matches_manual_dequant_reference():
    """One incremental step over a QuantRingCache == quantize the new
    rows, splice them into the dequantized cache, and run the exact XLA
    masked attention — the write and the read are both lossless given
    the stored int8/scale planes."""
    paddle.seed(3)
    mha = MultiHeadAttention(16, 2)
    mha.eval()
    B, C, T = 2, 8, 1
    snap = flags_snapshot()
    try:
        set_flags({"FLAGS_kv_cache_dtype": "int8"})
        cache = mha.gen_ring_cache(B, C)
    finally:
        flags_restore(snap)
    assert isinstance(cache, MultiHeadAttention.QuantRingCache)
    rng = np.random.RandomState(4)
    x = paddle.to_tensor(rng.randn(B, T, 16).astype(np.float32))
    pos = 3
    mask = paddle.to_tensor(
        np.where(np.arange(C)[None, None, None, :] <= pos, 0.0, -1e30)
        .astype(np.float32) * np.ones((B, 1, T, 1), np.float32))
    out, new_cache = mha(x, cache=cache, cache_position=jnp.int32(pos))
    out2, _ = mha(x, attn_mask=mask, cache=cache,
                  cache_position=jnp.int32(pos))
    assert new_cache.k.dtype == "int8" and new_cache.v.dtype == "int8"
    assert tuple(new_cache.k_scale.shape) == (B, 2, C, 1)
    # manual reference: dequantized spliced cache + masked attention
    from paddle_tpu.nn.functional.attention import _sdpa_mask
    q = mha._split_heads(mha.q_proj(x))
    k_new = mha._split_heads(mha.k_proj(x))
    v_new = mha._split_heads(mha.v_proj(x))
    kq, ks = quantize_kv_rows(k_new)
    vq, vs = quantize_kv_rows(v_new)
    kf = np.zeros((B, 2, C, 8), np.float32)
    vf = np.zeros((B, 2, C, 8), np.float32)
    kf[:, :, pos] = np.asarray(dequantize_kv_rows(kq, ks))[:, :, 0]
    vf[:, :, pos] = np.asarray(dequantize_kv_rows(vq, vs))[:, :, 0]
    ref = mha.out_proj(mha._merge_heads(_sdpa_mask(
        q, paddle.to_tensor(kf), paddle.to_tensor(vf), mask)))
    np.testing.assert_allclose(np.asarray(out2.numpy()),
                               np.asarray(ref.numpy()), atol=1e-6)
    assert out.shape == out2.shape


def test_quant_ring_block_write_stores_rows_and_scales_together():
    """A multi-token quantized block write lands int8 rows AND scale
    planes at the same (wrapped) positions."""
    paddle.seed(5)
    mha = MultiHeadAttention(16, 2)
    mha.eval()
    snap = flags_snapshot()
    try:
        set_flags({"FLAGS_kv_cache_dtype": "int8"})
        cache = mha.gen_ring_cache(1, 8)
    finally:
        flags_restore(snap)
    rng = np.random.RandomState(6)
    x = paddle.to_tensor(rng.randn(1, 3, 16).astype(np.float32))
    mask = paddle.to_tensor(np.zeros((1, 1, 3, 8), np.float32))
    # traced position 6: a 3-wide block wraps to columns {6, 7, 0}
    from paddle_tpu.framework.tensor import unwrap

    def step(p):
        _, nc = mha(x, attn_mask=mask, cache=cache, cache_position=p)
        return tuple(unwrap(t) for t in nc)

    got_k, _, got_ks, _ = jax.jit(step)(jnp.int32(6))
    k_new = mha._split_heads(mha.k_proj(x))
    kq, ks = quantize_kv_rows(k_new)
    got_rows = np.asarray(got_k)
    got_scales = np.asarray(got_ks)
    for i, col in enumerate([6, 7, 0]):
        np.testing.assert_array_equal(got_rows[:, :, col],
                                      np.asarray(kq)[:, :, i])
        # jit vs eager reduction order can differ by one ulp in the scale
        np.testing.assert_allclose(got_scales[:, :, col],
                                   np.asarray(ks)[:, :, i], rtol=1e-6)


# -- generate() under FLAGS_kv_cache_dtype=int8 ------------------------------

def _gpt(seed=7):
    paddle.seed(seed)
    m = GPTModel(GPTConfig.tiny(vocab_size=V, hidden_size=32, layers=2,
                                heads=2, seq=64))
    m.eval()
    return m


def test_generate_with_int8_kv_two_executables_and_halved_planes():
    m = _gpt()
    rng = np.random.RandomState(0)
    ids = rng.randint(2, V, (2, 5)).astype(np.int64)
    snap = flags_snapshot()
    try:
        set_flags({"FLAGS_kv_cache_dtype": "int8"})
        gen = Generator(m, site="generate:int8-kv", seq_buckets=(8, 16),
                        max_len=32)
        ledger.clear()
        out = np.asarray(gen.generate(ids, max_new_tokens=4).numpy())
        assert out.shape == (2, 4)
        evs = ledger.compile_events("generate:int8-kv")
        assert [e["kind"] for e in evs] == ["generate_prefill",
                                           "generate_decode"]
        gen.generate(ids, max_new_tokens=4)
        assert len(ledger.compile_events("generate:int8-kv")) == 2
        planes8 = jax.eval_shape(lambda: gen._init_cache_raw(2, 16))
    finally:
        flags_restore(snap)
    gen_bf = Generator(m, site="generate:bf16-kv", seq_buckets=(8, 16),
                       max_len=32)
    planes_f = jax.eval_shape(lambda: gen_bf._init_cache_raw(2, 16))

    def bytes_per_token(layers, C=16):
        return sum(p.size * p.dtype.itemsize for c in layers
                   for p in c) / C

    b8, bf = bytes_per_token(planes8), bytes_per_token(planes_f)
    rows8 = sum(p.size * p.dtype.itemsize for c in planes8
                for p in c if p.dtype == jnp.int8) / 16
    # the row planes shrink by exactly the itemsize ratio (the CPU seed
    # model stores f32 planes, so 4x here; bf16 planes halve on chip)
    # and the only overhead is one f32 scale per (token, head) per k/v
    # plane per layer.  The float planes are PACKED (g heads per lane
    # row, heads padded to a multiple of g: 2 heads of 16 pad to 8);
    # the int8 rows keep the unpacked layout, so compare head for head
    B, heads, layers = 2, 2, 2
    g = kv_heads_per_lane_row(m.config.hidden_size // heads)
    padded_heads = -(-heads // g) * g
    assert planes_f[0][0].shape == (B, padded_heads // g, 16,
                                    g * m.config.hidden_size // heads)
    assert rows8 * padded_heads == \
        bf * heads * (1 / np.dtype(np.float32).itemsize)
    assert b8 - rows8 == layers * 2 * B * heads * 4    # scale planes
    assert b8 * padded_heads < bf * heads


def test_int8_speculative_bit_matches_int8_plain():
    """The composition claim: with quantized caches on BOTH paths, the
    speculative scan still reproduces plain greedy bit-for-bit (the
    block write quantizes exactly like the single-token write)."""
    from paddle_tpu.text.speculative import SpeculativeGenerator
    m = _gpt(seed=11)
    rng = np.random.RandomState(1)
    ids = rng.randint(2, V, (2, 5)).astype(np.int64)
    snap = flags_snapshot()
    try:
        set_flags({"FLAGS_kv_cache_dtype": "int8"})
        plain = Generator(m, site="generate:int8-plain",
                          seq_buckets=(8, 16, 32), max_len=64)
        ref = np.asarray(plain.generate(ids, max_new_tokens=6).numpy())
        spec = SpeculativeGenerator(m, m, site="generate:int8-spec",
                                    seq_buckets=(8, 16, 32), max_len=64,
                                    gamma=2)
        out = np.asarray(spec.generate(ids, max_new_tokens=6).numpy())
        np.testing.assert_array_equal(out, ref)
        assert spec.last_stats["acceptance_rate"] == 1.0
    finally:
        flags_restore(snap)


def test_kv_dtype_is_part_of_the_compile_key():
    """Flipping FLAGS_kv_cache_dtype must recompile (new ledgered pair),
    never silently reuse executables built over the other plane layout."""
    m = _gpt(seed=13)
    rng = np.random.RandomState(2)
    ids = rng.randint(2, V, (1, 5)).astype(np.int64)
    gen = Generator(m, site="generate:kv-key", seq_buckets=(8, 16),
                    max_len=32)
    ledger.clear()
    gen.generate(ids, max_new_tokens=4)
    assert len(ledger.compile_events("generate:kv-key")) == 2
    snap = flags_snapshot()
    try:
        set_flags({"FLAGS_kv_cache_dtype": "int8"})
        gen.generate(ids, max_new_tokens=4)
        evs = ledger.compile_events("generate:kv-key")
        assert len(evs) == 4               # a fresh prefill+decode pair
    finally:
        flags_restore(snap)
    gen.generate(ids, max_new_tokens=4)    # back to bf16: warm again
    assert len(ledger.compile_events("generate:kv-key")) == 4
