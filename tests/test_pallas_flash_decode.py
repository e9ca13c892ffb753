"""Flash-decoding kernel tests (interpret mode on CPU).

The kernel must reproduce the XLA masked-attention reference — including
the split-K online-softmax merge across parallel context splits, the
per-row [start, end) validity window, and fully-masked (empty) splits —
plus the dispatch gate (FLAGS_use_flash_decode, OFF by default)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.framework.flags import flags_restore, flags_snapshot, \
    set_flags
from paddle_tpu.ops.pallas.flash_decode import (decode_attention_reference,
                                                flash_decode_fn,
                                                supports_decode)


def _rand(shape, dtype=np.float32, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape)
                       .astype(dtype))


def _check(B, N, H, S, start, end, block_k, atol=2e-6, dtype=np.float32,
           seed=0):
    q = _rand((B, N, 1, H), dtype, seed)
    k = _rand((B, N, S, H), dtype, seed + 1)
    v = _rand((B, N, S, H), dtype, seed + 2)
    s = None if start is None else jnp.asarray(start, jnp.int32)
    e = None if end is None else jnp.asarray(end, jnp.int32)
    out = flash_decode_fn(q, k, v, s, e, block_k=block_k)
    ref = decode_attention_reference(q, k, v, s, e)
    assert out.shape == (B, N, 1, H) and out.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol,
                               rtol=1e-6)


def test_fwd_matches_reference_full_window():
    _check(2, 3, 64, 256, None, None, block_k=128)


def test_fwd_matches_reference_windowed():
    # per-row windows crossing split boundaries both ways
    _check(2, 2, 64, 512, [3, 200], [380, 512], block_k=128)


def test_split_k_merge_matches_single_split():
    """The split-K merge is exact: many splits and one split agree with
    the reference (and with each other) to float accumulation noise."""
    q = _rand((2, 2, 1, 64))
    k = _rand((2, 2, 256, 64), seed=1)
    v = _rand((2, 2, 256, 64), seed=2)
    s = jnp.asarray([10, 64], jnp.int32)
    e = jnp.asarray([200, 256], jnp.int32)
    many = flash_decode_fn(q, k, v, s, e, block_k=128)     # 2 splits
    one = flash_decode_fn(q, k, v, s, e, block_k=256)      # 1 split
    ref = decode_attention_reference(q, k, v, s, e)
    np.testing.assert_allclose(np.asarray(many), np.asarray(ref),
                               atol=2e-6, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(many), np.asarray(one),
                               atol=2e-6, rtol=1e-6)


def test_empty_splits_are_ignored_by_merge():
    # start in the LAST split: every earlier split is fully masked and
    # must contribute l == 0 (not a fake exp(0) normalizer) to the merge
    _check(1, 2, 64, 512, [400], [512], block_k=128)
    # window entirely inside one middle split
    _check(1, 1, 64, 512, [140], [250], block_k=128)


def test_single_valid_column():
    _check(2, 1, 64, 256, [17, 255], [18, 256], block_k=128)


def test_head_dim_128():
    _check(2, 2, 128, 256, [0, 30], [256, 100], block_k=128)


def test_bf16_matches_reference_within_one_ulp():
    q = _rand((2, 2, 1, 64)).astype(jnp.bfloat16)
    k = _rand((2, 2, 256, 64), seed=1).astype(jnp.bfloat16)
    v = _rand((2, 2, 256, 64), seed=2).astype(jnp.bfloat16)
    s = jnp.asarray([5, 100], jnp.int32)
    out = flash_decode_fn(q, k, v, s, None, block_k=128)
    ref = decode_attention_reference(q, k, v, s, None)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=4e-3,
                               rtol=2e-2)


def test_supports_decode_gate():
    assert supports_decode((2, 4, 1, 64), (2, 4, 256, 64))
    assert supports_decode((1, 1, 1, 128), (1, 1, 1024, 128))
    # multi-row query, unaligned cache, odd head dim, mismatched B/N
    assert not supports_decode((2, 4, 2, 64), (2, 4, 256, 64))
    assert not supports_decode((2, 4, 1, 64), (2, 4, 200, 64))
    assert not supports_decode((2, 4, 1, 96), (2, 4, 256, 96))
    assert not supports_decode((2, 4, 1, 64), (2, 2, 256, 64))
    assert not supports_decode((2, 4, 1, 64), (2, 4, 256, 128))


def test_sq_must_be_one():
    q = _rand((1, 1, 2, 64))
    k = _rand((1, 1, 128, 64))
    with pytest.raises(ValueError, match="single query"):
        flash_decode_fn(q, k, k)


def test_dispatch_gate_defaults_off_and_respects_platform(monkeypatch):
    """cached_attention routes to the kernel only when the flag is ON and
    the backend is a TPU; the CPU test backend always takes the XLA
    path (ships gated OFF — PERF.md pending-measurement provenance)."""
    from paddle_tpu.nn.functional import attention as A
    q = paddle.to_tensor(np.zeros((1, 2, 1, 64), "float32"))
    k = paddle.to_tensor(np.zeros((1, 2, 256, 64), "float32"))
    win = (paddle.to_tensor(np.zeros((1,), "int32")),
           paddle.to_tensor(np.full((1,), 256, "int32")))
    snap = flags_snapshot()
    try:
        assert not A._use_flash_decode(q, k, win)        # flag off
        set_flags({"FLAGS_use_flash_decode": True})
        assert not A._use_flash_decode(q, k, win)        # CPU platform
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert A._use_flash_decode(q, k, win)            # tpu + flag
        assert not A._use_flash_decode(q, k, None)       # no window
        # ineligible shape falls back even on TPU with the flag on
        k_bad = paddle.to_tensor(np.zeros((1, 2, 200, 64), "float32"))
        assert not A._use_flash_decode(q, k_bad, win)
    finally:
        flags_restore(snap)
