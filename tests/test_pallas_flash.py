"""Pallas flash-attention kernel vs the plain XLA softmax-attention path.

Runs in interpret mode on the CPU backend (conftest). Mirrors the grad-check
style of the reference op tests (op_test.py check_grad) but compares against
the framework's own XLA attention instead of numeric differentiation — the
two paths must agree to float tolerance in both passes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas.flash_attention import (
    MIN_SEQ_BLOCKED, MIN_SEQ_SINGLE_BLOCK, flash_attention_fn, fused_form,
    packed_attention_fn, supports, supports_packed, _pick_block)
from paddle_tpu.nn.functional.attention import _sdpa_fn, _sdpa_mask_fn

rng = np.random.RandomState(7)


def _qkv(B=2, N=2, S=256, H=64, dtype=jnp.float32):
    mk = lambda: jnp.asarray(rng.randn(B, N, S, H), dtype)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("H", [64, 128])
def test_forward_matches_xla(causal, H):
    q, k, v = _qkv(H=H)
    out = flash_attention_fn(q, k, v, causal=causal)
    ref = _sdpa_fn(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_xla(causal):
    q, k, v = _qkv(S=256)
    w = jnp.asarray(rng.randn(*q.shape), jnp.float32)

    gf = jax.grad(lambda *a: (flash_attention_fn(*a, causal=causal) * w)
                  .sum(), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: (_sdpa_fn(*a, causal=causal) * w)
                  .sum(), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=1e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("mask_shape", [(2, 1, 1, 256), (2, 2, 256, 256),
                                        (1, 1, 256, 256)])
def test_bias_variants(mask_shape):
    q, k, v = _qkv(S=256)
    mask = jnp.asarray(
        np.where(rng.rand(*mask_shape) < 0.2, -1e9, 0.0), jnp.float32)
    out = flash_attention_fn(q, k, v, bias=mask)
    ref = _sdpa_mask_fn(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-5)


def test_bias_grad_matches():
    q, k, v = _qkv(S=128)
    mask = jnp.asarray(rng.randn(2, 2, 128, 128), jnp.float32)
    gf = jax.grad(lambda q: (flash_attention_fn(q, k, v, bias=mask) ** 2)
                  .sum())(q)
    gr = jax.grad(lambda q: (_sdpa_mask_fn(q, k, v, mask) ** 2).sum())(q)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                               atol=5e-4, rtol=1e-4)


def test_cross_attention_lengths():
    q = jnp.asarray(rng.randn(2, 2, 128, 64), jnp.float32)
    k = jnp.asarray(rng.randn(2, 2, 384, 64), jnp.float32)
    v = jnp.asarray(rng.randn(2, 2, 384, 64), jnp.float32)
    out = flash_attention_fn(q, k, v)
    ref = _sdpa_fn(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-5)


def test_causal_cross_lengths_bottom_right():
    """Sq < Sk causal must be bottom-right aligned like _sdpa_fn's
    tril(k=Sk-Sq) (chunked-decode shape)."""
    q = jnp.asarray(rng.randn(1, 2, 128, 64), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, 512, 64), jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, 512, 64), jnp.float32)
    out = flash_attention_fn(q, k, v, causal=True)
    ref = _sdpa_fn(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-5)
    gf = jax.grad(lambda *a: (flash_attention_fn(*a, causal=True) ** 2)
                  .sum(), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: (_sdpa_fn(*a, causal=True) ** 2)
                  .sum(), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=1e-4, err_msg=f"d{name}")
    with pytest.raises(ValueError):
        flash_attention_fn(k, q, q, causal=True)  # Sq > Sk rejected


def test_mask_plus_causal_consistent():
    """attn_mask + is_causal must mean the same thing on both paths."""
    from paddle_tpu.nn.functional.attention import _sdpa_mask_fn as mf
    q, k, v = _qkv(S=256)
    mask = jnp.asarray(
        np.where(rng.rand(2, 1, 1, 256) < 0.2, -1e9, 0.0), jnp.float32)
    out = flash_attention_fn(q, k, v, bias=mask, causal=True)
    ref = mf(q, k, v, mask, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-5)


def test_tensor_primitive_tape():
    """flash_attention through the Primitive tape (eager Tensor autograd)."""
    from paddle_tpu.ops.pallas import flash_attention
    from paddle_tpu.framework.tensor import Tensor

    qa, ka, va = _qkv(S=128)
    q = Tensor(qa, stop_gradient=False)
    k = Tensor(ka, stop_gradient=False)
    v = Tensor(va, stop_gradient=False)
    out = flash_attention(q, k, v, causal=True)
    loss = (out * out).sum()
    loss.backward()
    gr = jax.grad(lambda q: (_sdpa_fn(q, ka, va, causal=True) ** 2).sum())(qa)
    np.testing.assert_allclose(np.asarray(q.grad._value), np.asarray(gr),
                               atol=5e-4, rtol=1e-4)


def test_supports_gate():
    assert supports((2, 4, 256, 64), (2, 4, 256, 64))
    assert not supports((2, 4, 200, 64), (2, 4, 256, 64))   # seq % 128
    assert not supports((2, 4, 256, 80), (2, 4, 256, 80))   # head_dim
    assert supports((2, 4, 256, 64), (2, 4, 256, 64), (2, 1, 1, 256))
    assert not supports((2, 4, 256, 64), (2, 4, 256, 64), (3, 1, 1, 256))
    assert supports((2, 4, 128, 64), (2, 4, 256, 64), causal=True)
    assert not supports((2, 4, 256, 64), (2, 4, 128, 64), causal=True)
    assert _pick_block(640, 512) == 128
    assert _pick_block(1024, 512) == 512
    assert _pick_block(4096, 1024) == 1024
    assert _pick_block(128, 512) == 128
    assert _pick_block(384, 512) == 384


def test_causal_block_unification_no_dropped_keys():
    """Sq=768, Sk=1024 causal: unified block must divide BOTH lengths
    (regression: gcd-based pick, no silently dropped trailing key blocks)."""
    q = jnp.asarray(rng.randn(1, 2, 768, 64), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, 1024, 64), jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, 1024, 64), jnp.float32)
    out = flash_attention_fn(q, k, v, causal=True)
    ref = _sdpa_fn(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-5)
    gf = jax.grad(lambda *a: (flash_attention_fn(*a, causal=True) ** 2)
                  .sum(), argnums=(1, 2))(q, k, v)
    gr = jax.grad(lambda *a: (_sdpa_fn(*a, causal=True) ** 2)
                  .sum(), argnums=(1, 2))(q, k, v)
    for name, a, b in zip("kv", gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=1e-4, err_msg=f"d{name}")


# --------------------------------------------------------------------------
# the single-block form: [B, S, N*H] operands, heads side by side on the lanes
# --------------------------------------------------------------------------

def _split(x, N):
    B, S, E = x.shape
    return x.reshape(B, S, N, E // N).transpose(0, 2, 1, 3)


def _merge(x):
    B, N, S, H = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B, S, N * H)


def _ref_bse(q, k, v, N, bias=None, causal=False):
    """``_sdpa_fn`` on ``[B, S, N*H]`` operands, in float32."""
    a = [_split(t.astype(jnp.float32), N) for t in (q, k, v)]
    out = _sdpa_fn(*a, causal=causal) if bias is None \
        else _sdpa_mask_fn(*a, bias, causal=causal)
    return _merge(out)


def _bse(B, S, N, H, dtype, Sk=None):
    mk = lambda s: jnp.asarray(rng.randn(B, s, N * H), dtype)   # noqa: E731
    return mk(S), mk(Sk or S), mk(Sk or S)


# (atol, rtol) forward / backward: float32 as the blocked form's cases
# above, bfloat16 as chip_smoke.py's KERNEL_TOL states them
PACKED_TOL = {jnp.float32: ((2e-5, 1e-5), (5e-4, 1e-4)),
              jnp.bfloat16: ((2e-2, 2e-2), (5e-2, 5e-2))}
# B, N, Sq, Sk, H, causal, chunk, lane_rows; the first is BERT-large's head
# shape at the benchmark's sequence (benchmark/configs/bert-large-pretrain)
PACKED_SHAPES = [
    (1, 2, 512, 512, 64, False, 128, 1),
    (2, 4, 256, 256, 64, True, 128, 2),
    (1, 2, 128, 384, 64, True, 128, 1),
    (2, 2, 256, 256, 128, False, 256, 2),
]
_ids = lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else None  # noqa: E731


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", PACKED_SHAPES, ids=_ids)
def test_packed_forward_matches_xla(shape, dtype):
    B, N, Sq, Sk, H, causal, cq, lr = shape
    q, k, v = _bse(B, Sq, N, H, dtype, Sk)
    out = packed_attention_fn(q, k, v, N, causal=causal, chunk=cq,
                              lane_rows=lr)
    assert out.dtype == dtype and out.shape == q.shape
    atol, rtol = PACKED_TOL[dtype][0]
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(_ref_bse(q, k, v, N, None, causal)),
                               atol=atol, rtol=rtol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", PACKED_SHAPES, ids=_ids)
def test_packed_grads_match_xla(shape, dtype):
    B, N, Sq, Sk, H, causal, cq, lr = shape
    q, k, v = _bse(B, Sq, N, H, dtype, Sk)
    w = jnp.asarray(rng.randn(*q.shape), jnp.float32)
    gf = jax.grad(lambda *a: (packed_attention_fn(
        *a, N, causal=causal, chunk=cq, lane_rows=lr).astype(jnp.float32)
        * w).sum(), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: (_ref_bse(*a, N, None, causal) * w).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    atol, rtol = PACKED_TOL[dtype][1]
    for name, a, b in zip("qkv", gf, gr):
        assert a.dtype == dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=atol * (4 if dtype == jnp.bfloat16
                                                else 1),
                                   rtol=rtol, err_msg=f"d{name}")


@pytest.mark.parametrize("mask_shape", [(2, 1, 1, 256), (2, 4, 256, 256),
                                        (1, 1, 256, 256), (1, 4, 1, 256)])
@pytest.mark.parametrize("causal", [False, True])
def test_packed_bias_variants(mask_shape, causal):
    q, k, v = _bse(2, 256, 4, 64, jnp.float32)
    # (column 0 stays open: a causal row with every column it can see at
    # -1e9 has no softmax worth comparing, in either form)
    hole = np.where((rng.rand(*mask_shape) < 0.2)
                    & (np.arange(256) > 0), -1e9, 0.0)
    mask = jnp.asarray(hole + rng.randn(*mask_shape), jnp.float32)
    w = jnp.asarray(rng.randn(*q.shape), jnp.float32)
    f = lambda *a: packed_attention_fn(*a, 4, mask, causal=causal,  # noqa: E731
                                       lane_rows=2)
    r = lambda *a: _ref_bse(*a, 4, mask, causal)                    # noqa: E731
    np.testing.assert_allclose(np.asarray(f(q, k, v)), np.asarray(r(q, k, v)),
                               atol=2e-5, rtol=1e-5)
    gf = jax.grad(lambda *a: (f(*a) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: (r(*a) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=1e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("causal", [False, True])
def test_single_block_backward_matches_two_kernel_backward(causal):
    """One backward kernel that recomputes ``p`` once against the blocked
    form's ``_dq_kernel`` + ``_dkv_kernel`` on a shape the latter cuts in
    two blocks each way: the same three gradients."""
    N = 2
    q, k, v = _bse(2, 256, N, 64, jnp.float32)
    w = jnp.asarray(rng.randn(*q.shape), jnp.float32)
    one = jax.grad(lambda *a: (packed_attention_fn(*a, N, causal=causal)
                               * w).sum(), argnums=(0, 1, 2))(q, k, v)
    two = jax.grad(lambda *a: (_merge(flash_attention_fn(
        *(_split(t, N) for t in a), causal=causal, block_q=128,
        block_k=128)) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", one, two):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=1e-5, err_msg=f"d{name}")


def test_supports_packed_gate():
    bert = (16, 16, 512, 64)
    assert supports_packed(bert, bert)
    assert supports_packed(bert, bert, (16, 1, 1, 512))
    assert supports_packed((2, 8, 128, 128), (2, 8, 1024, 128), causal=True)
    assert not supports_packed((2, 25, 512, 64), (2, 25, 512, 64))  # half a lane row
    assert not supports_packed((2, 4, 512, 256), (2, 4, 512, 256))  # head_dim
    assert not supports_packed((2, 4, 2048, 64), (2, 4, 2048, 64))  # too long
    assert not supports_packed((2, 4, 500, 64), (2, 4, 500, 64))    # odd length
    assert not supports_packed((2, 4, 512, 64), (2, 2, 512, 64))    # fewer kv heads


@pytest.fixture()
def one_device():
    """The process's mesh held to one device for the test: another test
    file of this worker may have left a wider one, and a mesh of several
    devices keeps every call site on the XLA path."""
    from paddle_tpu.parallel.mesh import MeshGuard, make_mesh
    with MeshGuard(make_mesh({"dp": 1}, jax.devices()[:1])):
        yield


def _form_cases():
    S1, SB = MIN_SEQ_SINGLE_BLOCK, MIN_SEQ_BLOCKED
    bert = (16, 16, 512, 64)
    long = (2, 16, 2 * max(SB, 1024), 64)
    # name, q, k, mask shape, trainable mask, causal, packed operands, form
    return [
        ("bert_s512", bert, bert, None, False, False, True, "single_block"),
        ("bert_s512_padding_mask", bert, bert, (16, 1, 1, 512), False, False,
         True, "single_block"),
        ("bert_s512_causal", bert, bert, None, False, True, True,
         "single_block"),
        ("trainable_mask", bert, bert, (1, 16, 512, 512), True, False, True,
         None),
        ("odd_length", (16, 16, 500, 64), (16, 16, 500, 64), None, False,
         False, True, None),
        ("below_the_crossover", (64, 16, S1 // 2, 64), (64, 16, S1 // 2, 64),
         None, False, False, True, None),
        ("odd_head_count_long", (2, 25, long[2], 64), (2, 25, long[2], 64),
         None, False, True, True, "blocked"),
        ("long", long, long, None, False, False, True, "blocked"),
        ("split_heads_in_hand", bert, bert, None, False, False, False,
         "blocked" if SB <= 512 else None),
        ("one_query_over_a_concat_cache", (16, 16, 1, 64), (16, 16, 513, 64),
         None, False, False, False, None),
        ("head_dim_80", (2, 4, 512, 80), (2, 4, 512, 80), None, False, False,
         True, None),
    ]


@pytest.mark.parametrize("case", _form_cases(), ids=lambda c: c[0])
def test_use_pallas_rule(case, monkeypatch, one_device):
    """The dispatch's rule as a table: which form a call site takes, from
    its shapes, its mask and nothing else (the backend steered here: on
    the CPU every site takes the XLA path)."""
    from paddle_tpu.framework.tensor import Tensor
    from paddle_tpu.nn.functional import attention as A
    _, q, k, mshape, trainable, causal, packed, want = case
    mask = None
    if mshape is not None:
        mask = Tensor(jnp.zeros(mshape, jnp.float32),
                      stop_gradient=not trainable)
    with A.count_attention_forms() as forms:
        assert A._use_pallas(q, k, mask, causal=causal, packed=packed) is None
    assert forms == {"fused": 0, "xla": 1}
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    with A.count_attention_forms() as forms:
        assert A._use_pallas(q, k, mask, causal=causal, packed=packed) == want
    assert forms == ({"fused": 1, "xla": 0} if want
                     else {"fused": 0, "xla": 1})
    # a mesh of several devices: the kernels have no partitioning rule
    from paddle_tpu.parallel.mesh import MeshGuard, make_mesh
    with MeshGuard(make_mesh({"dp": 2}, jax.devices()[:2])):
        assert A._use_pallas(q, k, mask, causal=causal, packed=packed) is None
    with MeshGuard(make_mesh({"dp": 1}, jax.devices()[:1])):
        assert A._use_pallas(q, k, mask, causal=causal, packed=packed) == want
    if not trainable:          # (the rule itself sees shapes, not masks)
        assert fused_form(q, k, mshape, causal=causal, packed=packed) == want


def test_cached_paths_never_ask(monkeypatch, one_device):
    """A ring cache's attention is not an un-cached call site: it takes
    neither kernel and counts in no tally, on any backend."""
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.nn.functional import attention as A
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    mha = nn.MultiHeadAttention(128, 2)
    mha.eval()
    x = paddle.to_tensor(rng.randn(2, 128, 128).astype("float32"))
    cache = mha.gen_ring_cache(2, 256)
    with A.count_attention_forms() as forms:
        mha(x, attn_mask=paddle.to_tensor(
            np.zeros((2, 1, 128, 256), "float32")), cache=cache,
            cache_position=0)
    assert forms == {"fused": 0, "xla": 0}
    with A.count_attention_forms() as forms:
        mha(x)
    assert forms == {"fused": 0, "xla": 1} if MIN_SEQ_SINGLE_BLOCK > 128 \
        else forms == {"fused": 1, "xla": 0}


@pytest.mark.parametrize("masked", [False, True], ids=["plain", "mask"])
def test_layer_takes_the_kernel_and_agrees(masked, monkeypatch, one_device):
    """``MultiHeadAttention``'s un-cached branch through the kernel (the
    backend steered, the kernel interpreted) against its XLA path: output
    and the input's gradient."""
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.nn.functional import attention as A
    S = max(MIN_SEQ_SINGLE_BLOCK, 128)
    layer = nn.TransformerEncoderLayer(128, 2, 256, dropout=0.0)
    xv = rng.randn(2, S, 128).astype("float32")
    mask = paddle.to_tensor(np.where(rng.rand(2, 1, 1, S) < 0.3, -1e4, 0.0)
                            .astype("float32")) if masked else None

    def run():
        x = paddle.to_tensor(xv, stop_gradient=False)
        with A.count_attention_forms() as forms:
            y = layer(x, mask)
        (y * y).sum().backward()
        return forms, np.asarray(y._value), np.asarray(x.grad._value)

    f0, y0, g0 = run()
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    f1, y1, g1 = run()
    assert f0 == {"fused": 0, "xla": 1} and f1 == {"fused": 1, "xla": 0}
    np.testing.assert_allclose(y1, y0, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(g1, g0, atol=5e-4, rtol=1e-4)
