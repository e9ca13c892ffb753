"""The hybrid decoder as ``gigachat3_5`` (gated delta-rule linear layers
beside gated latent attention, norms with sigmoid gains before and after
each half, clamped SwiGLUs, a share of the experts) at tiny widths on the
CPU, float32, seeded weights: (a) the chunked delta rule against the
token-by-token recurrence, (b) prefill in chunks + steps, alone and through
the slot loop, against the reference's one forward pass
(benchmark/reference/gigachat3_5.py, which imports nothing of the program),
(c) the shares of an expert layer against the uncut layer, (d) a bfloat16
state, (e) the clamp and the two gate forms, (f) the counts.  Logits are
compared, never sampled tokens; each control must FAIL the tolerance its
test passes.
"""
import dataclasses
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import chip_smoke                                              # noqa: E402
from benchmark.counts import gigachat3_5 as counts             # noqa: E402
from benchmark.models import gigachat3_5 as bench_models       # noqa: E402
from benchmark.reference import gigachat3_5 as ref             # noqa: E402
from benchmark.reference.common import Arith                   # noqa: E402
from paddle_tpu.framework.enforce import InvalidArgumentError  # noqa: E402
from paddle_tpu.framework.tensor import Tensor, unwrap         # noqa: E402
from paddle_tpu.nn.layer.gated_delta import (                  # noqa: E402
    GatedDeltaNet, delta_mix, unit_lower_inverse)
from paddle_tpu.nn.layer.latent_attention import (             # noqa: E402
    LatentAttention, SigmoidGainRMSNorm)
from paddle_tpu.nn.layer.moe import DroplessMoE, SwiGLU        # noqa: E402
from paddle_tpu.serving.slots import SlotLoop                  # noqa: E402
from paddle_tpu.text.generation import Generator               # noqa: E402
from paddle_tpu.text.models.hybrid_conv import (               # noqa: E402
    HybridConvDecoder)

# (a) float32 on the CPU: the chunked form (a triangular inverse by halves,
# products over scan chunks) and the recurrence differ by summation order
# only; outputs are ~0.1-1 wide, states ~1
SCAN_TOL = 1e-5
# (b) the whole tiny model: logits ~1 wide through five layers.  Nearly
# every position reads 2-6e-6; ONE early position of one row reads 7.5e-5
# in chunks and 1.4e-5 in the cache-less pass (bit-equal in a fresh and in
# a used slot: float32 summation order at an ill-conditioned token, not
# state left behind), and every control below fails by 1e-3 or more
LOGIT_TOL = 1e-4
GAP_TOL = 1e-4
VOCAB = 96
CHUNK = 16          # the slot loop's prefill chunk: two scan chunks of 8
# 5 requests over 2-3 slots: every slot is reused, rows wait between chunks
REQUESTS = [(37, 6), (18, 8), (44, 4), (9, 8), (29, 5)]


def _tiny():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "gigachat3.5-ep16-serve.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "tests", "data",
                           "gigachat3_5_tiny.json")) as f:
        over = json.load(f)["over"]
    cfg["serve"].update(over.pop("serve"))
    cfg.update(over)
    return cfg


def _build(cfg, seed=5):
    from benchmark import harness
    mapped = bench_models.to_program(ref.init_weights(cfg, seed))
    model = bench_models.build(cfg, mapped)
    return model, harness.canonical_view(mapped, bench_models.leaf_ids(cfg))


@pytest.fixture(scope="module")
def served():
    """ONE tiny model with the reference's seeded weights, and its view of
    them for the reference (shared by the whole module: one build)."""
    cfg = _tiny()
    return (cfg,) + _build(cfg)


def _reference_logits(cfg, view, ids):
    """The reference's logits at every position of ``ids [T]``."""
    return np.asarray(ref.served_logits(
        cfg, view, ids[:1], np.concatenate([ids[1:], [0]])))


def test_tiny_is_the_published_model_in_small(served):
    cfg, model, _ = served
    kinds = [type(l.mixer).__name__ for l in model.layers]
    assert kinds == ["GatedDeltaNet", "LatentAttention", "GatedDeltaNet",
                     "GatedDeltaNet", "GatedDeltaNet"]
    assert [type(l.ffn).__name__ for l in model.layers] == [
        "SwiGLU"] + ["DroplessMoE"] * 4
    c = model.config
    assert (c.block_norms, c.norm_gain, c.norm_gain_scale, c.ffn_limit) == (
        "pre_post", "sigmoid", 2.0, 10.0)
    assert isinstance(model.norm, SigmoidGainRMSNorm)
    full = model.layers[1].mixer
    assert full.gate_features and tuple(unwrap(full.gate).shape) == (64, 32)
    assert isinstance(full.q_a_norm, SigmoidGainRMSNorm)
    # YaRN: m = 0.1 ln 8 + 1, its square on the softmax scale
    assert full.scale == pytest.approx((0.1 * math.log(8) + 1) ** 2 / 4.0)
    moe = model.layers[2].ffn
    assert (moe.lo, moe.hi, moe.num_experts, moe.top_k, moe.limit) == (
        4, 8, 16, 3, 10.0)
    assert moe.shared.limit == 10.0 and model.layers[0].ffn.limit == 10.0
    # a 16-token chunk reads the latent plane per head, a step absorbed
    assert (model.latent_form(1), model.latent_form(CHUNK)) == (
        "absorbed", "per_head")


# -- (a) the delta rule alone ----------------------------------------------------

def _operands(seed, B=2, T=24, G=2, H=4, N=16, P=16, repeat=False):
    """Unit keys, queries at ``N^-1/2``, decays in (0.74, 1), beta in (0,
    1); ``repeat`` gives every token of a row the SAME key."""
    rng = np.random.default_rng(seed)

    def unit(shape, scale):
        x = rng.normal(0, 1, shape)
        return (x / np.linalg.norm(x, axis=-1, keepdims=True)
                * scale).astype(np.float32)
    q, k = unit((B, T, G, N), N ** -0.5), unit((B, T, G, N), 1.0)
    if repeat:
        k[:] = k[:, :1]
    v = rng.normal(0, 1, (B, T, H, P)).astype(np.float32)
    a = -0.3 * rng.random((B, T, H)).astype(np.float32)
    beta = rng.random((B, T, H)).astype(np.float32)
    return q, k, v, a, beta


def _recurrence(q, k, v, a, beta, h0=None, state_dtype=None):
    """The reference's recurrence, a row at a time: (o ``[B, T, H, P]``,
    the last state ``[B, H, P, N]``); ``h0`` is folded in as a first token
    cannot be, so only zero states are taken."""
    assert h0 is None
    rep = v.shape[2] // k.shape[2]
    out = [ref.recurrence(
        *(jnp.asarray(np.repeat(t[b], rep, axis=1)) for t in (q, k)),
        jnp.asarray(v[b]), jnp.exp(jnp.asarray(a[b])), jnp.asarray(beta[b]),
        state_dtype, final_state=True) for b in range(v.shape[0])]
    return (np.stack([np.asarray(o) for o, _ in out]),
            np.stack([np.asarray(s) for _, s in out]))


def _scan(ops, chunk, splits=None, inverse=unit_lower_inverse):
    """``delta_mix`` over the tokens in blocks of ``splits`` (one block by
    default), the state carried from block to block."""
    q, k, v, a, beta = (jnp.asarray(t) for t in ops)
    B, T, H, P = v.shape
    h = jnp.zeros((B, H, P, k.shape[3]), jnp.float32)
    out, t0 = [], 0
    for w in splits or (T,):
        o, h = delta_mix(*(t[:, t0:t0 + w] for t in (q, k, v, a, beta)), h,
                         chunk, inverse)
        out.append(np.asarray(o))
        t0 += w
    return np.concatenate(out, 1), np.asarray(h)


@pytest.mark.parametrize("chunk,splits", [
    (8, None),                  # one block, three scan chunks
    (8, (16, 8)),               # the state carried across blocks
    (8, (5, 16, 3)),            # blocks that are no whole scan chunk
    (16, (24,)),                # a block padded to whole scan chunks
    (8, (8,) + (1,) * 16),      # a chunk, then one-token updates
    (4, (1,) * 24),             # the update alone
])
def test_the_chunked_delta_rule_equals_the_recurrence(chunk, splits):
    ops = _operands(0)
    want_o, want_h = _recurrence(*ops)
    got_o, got_h = _scan(ops, chunk, splits)
    np.testing.assert_allclose(got_o, want_o, atol=SCAN_TOL)
    np.testing.assert_allclose(got_h, want_h, atol=SCAN_TOL)


def test_a_token_with_beta_0_and_decay_1_passes_the_state_through():
    """Tokens 5..11 of row 0 and 0..2 of row 1 neither read nor write
    (``beta = 0``, ``a = 0``: what a token before a row's ``start`` is
    given): the others' outputs and the last state are those of the
    recurrence over the live tokens alone."""
    q, k, v, a, beta = _operands(1)
    dead = np.zeros(beta.shape[:2], bool)
    dead[0, 5:12], dead[1, :3] = True, True
    a[dead], beta[dead] = 0.0, 0.0
    got_o, got_h = _scan((q, k, v, a, beta), 8)
    for b in range(2):
        keep = ~dead[b]
        o, h = _recurrence(*(t[b:b + 1, keep] for t in (q, k, v, a, beta)))
        np.testing.assert_allclose(got_o[b, keep], o[0], atol=SCAN_TOL)
        np.testing.assert_allclose(got_h[b], h[0], atol=SCAN_TOL)


def _substitution_inverse(a):
    """Row by row: row ``i`` of ``(I + A)^-1`` from the rows above it."""
    a = np.asarray(a, np.float64)
    t = np.broadcast_to(np.eye(a.shape[-1]), a.shape).copy()
    for i in range(1, a.shape[-1]):
        t[..., i, :i] = -np.einsum("...j,...jk->...k", a[..., i, :i],
                                   t[..., :i, :i])
    return t


@pytest.mark.parametrize("repeat", [False, True])
def test_the_triangular_inverse_by_halves_is_the_substitutions(repeat):
    """Against forward substitution in float64, at the served scan chunk's
    width: random keys, and 64 tokens with ONE key, ``beta`` 0.9 and a
    decay near 1, where ``A`` is nearly 0.9 below the diagonal everywhere
    and the powers of ``A`` that the product form sums grow like
    binomials."""
    rng = np.random.default_rng(2)
    L = 64
    k = rng.normal(0, 1, (3, L, 16))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    if repeat:
        k[:] = k[:, :1]
    beta = np.full((3, L), 0.9) if repeat else rng.random((3, L))
    g = np.cumsum(-0.01 * rng.random((3, L)), -1)
    a = np.tril(beta[..., None] * np.exp(g[..., :, None] - g[..., None, :])
                * np.einsum("bld,bsd->bls", k, k), -1).astype(np.float32)
    want = _substitution_inverse(a)
    got = np.asarray(unit_lower_inverse(jnp.asarray(a)))
    np.testing.assert_allclose(got, want, atol=1e-5)
    if repeat:
        # the control: the product form loses the sum in float32 here,
        # which is why the program does not use it
        lost = np.abs(np.asarray(
            chip_smoke._product_inverse(jnp.asarray(a))) - want)
        assert lost.max() > 1e3 * 1e-5


def test_repeated_keys_pass_the_tolerance_by_halves_and_fail_it_by_product():
    """(a) again where every token of a row has the same key (a prompt
    that repeats one token), at the served scan chunk of 64: by halves
    inside the tolerance, by the product of six factors far outside it."""
    q, k, v, a, beta = _operands(3, T=64, repeat=True)
    a, beta = 0.03 * a, np.full_like(beta, 0.9)
    want_o, want_h = _recurrence(q, k, v, a, beta)
    got_o, got_h = _scan((q, k, v, a, beta), 64)
    np.testing.assert_allclose(got_o, want_o, atol=SCAN_TOL)
    np.testing.assert_allclose(got_h, want_h, atol=SCAN_TOL)
    bad_o, _ = _scan((q, k, v, a, beta), 64,
                     inverse=chip_smoke._product_inverse)
    assert not np.abs(bad_o - want_o).max() < 100 * SCAN_TOL


# -- (d) a state in bfloat16 -----------------------------------------------------

def test_a_state_rounded_to_bfloat16_after_every_token_fails():
    """The same recurrence with its state rounded to bfloat16 after every
    token lies two orders outside the tolerance that the chunked form
    keeps: the state has to be float32."""
    ops = _operands(0)
    got_o, got_h = _scan(ops, 8)
    low_o, low_h = _recurrence(*ops, state_dtype=jnp.bfloat16)
    assert np.abs(got_o - low_o).max() > 100 * SCAN_TOL
    assert np.abs(got_h - low_h).max() > 100 * SCAN_TOL


def test_the_state_plane_is_float32_whatever_the_loop_asks_for(served):
    _, model, _ = served
    cache = model.init_cache(3, 192, "bfloat16")
    assert [type(c).__name__ for c in cache] == [
        "SsmStateCache", "LatentPlane", "SsmStateCache", "SsmStateCache",
        "SsmStateCache"]
    assert str(unwrap(cache[0].state).dtype) == "float32"
    assert unwrap(cache[0].state).shape == (3, 4, 16, 16)
    # the convolution's last 3 inputs over the 128 channels of [q | k | v]
    assert str(unwrap(cache[0].conv).dtype) == "bfloat16"
    assert unwrap(cache[0].conv).shape == (3, 1, 3, 128)
    # ONE latent plane: 32 + 8 numbers a token padded to the lane grid
    assert unwrap(cache[1].latent).shape == (3, 1, 192, 128)


# -- the mixer in its layer ------------------------------------------------------

def _mixer(seed=0):
    m = GatedDeltaNet(32, 2, 4, 16, 16, taps=4, chunk=8)
    rng = np.random.default_rng(seed)
    for name, p in m.named_parameters():
        shape = p._value.shape
        if len(shape) == 2:
            std = shape[1 if name == "conv" else 0] ** -0.5
            p.set_value(jnp.asarray(rng.normal(0, std, shape), p._value.dtype))
        elif name in ("A_log", "dt_bias"):
            p.set_value(jnp.asarray(rng.normal(0, 1, shape), jnp.float32))
        else:
            p.set_value(jnp.asarray(rng.normal(0, 0.1, shape),
                                    p._value.dtype))
    return m


def _feed(m, x, widths, start, rows=None, cache=None):
    """``x [B, T, hidden]`` through ``forward_cached`` in blocks of
    ``widths`` from column 0."""
    cache = cache or m.gen_cache(x.shape[0], 64)
    out, pos = [], 0
    for w in widths:
        y, cache = m.forward_cached(jnp.asarray(x[:, pos:pos + w]), cache,
                                    jnp.int32(pos), jnp.asarray(start), rows)
        out.append(np.asarray(y))
        pos += w
    return np.concatenate(out, 1), cache


@pytest.mark.parametrize("widths,start", [
    ((24,), (0, 0)),
    ((16, 8), (5, 19)),                 # a start inside / past a scan chunk
    ((5, 16, 3), (2, 7)),
    ((8,) + (1,) * 16, (3, 0)),
])
def test_the_mixers_blocks_equal_one_token_at_a_time(widths, start):
    """Whatever the blocks, each row's outputs from its ``start`` on, its
    state and its convolution inputs are those of one token at a time."""
    m = _mixer()
    x = np.random.default_rng(1).normal(0, 1, (2, 24, 32)).astype(np.float32)
    got, cache = _feed(m, x, widths, np.asarray(start, np.int32))
    want, wcache = _feed(m, x, (1,) * 24, np.asarray(start, np.int32))
    for b, s in enumerate(start):
        np.testing.assert_allclose(got[b, s:], want[b, s:], atol=SCAN_TOL)
    np.testing.assert_allclose(np.asarray(unwrap(cache.state)),
                               np.asarray(unwrap(wcache.state)),
                               atol=SCAN_TOL)
    np.testing.assert_array_equal(np.asarray(unwrap(cache.conv)),
                                  np.asarray(unwrap(wcache.conv)))


def test_a_slots_previous_occupant_leaves_nothing_behind():
    """The state handed to a block counts iff ``pos > start`` and a
    convolution input iff its column is at or after ``start``: a row whose
    request begins at column 16 or 18 starts from nothing whatever the
    slot's last occupant left, and a row outside ``write_rows`` keeps what
    it has."""
    m = _mixer()
    x = np.random.default_rng(2).normal(0, 1, (2, 32, 32)).astype(np.float32)
    _, dirty = _feed(m, x[:, :16], (16,), np.zeros(2, np.int32))
    start = np.asarray([16, 18], np.int32)
    y0, c0 = m.forward_cached(jnp.asarray(x[:, 16:]), dirty, jnp.int32(16),
                              jnp.asarray(start))
    y1, c1 = m.forward_cached(jnp.asarray(x[:, 16:]), m.gen_cache(2, 64),
                              jnp.int32(16), jnp.asarray(start))
    np.testing.assert_array_equal(np.asarray(y0)[0], np.asarray(y1)[0])
    np.testing.assert_array_equal(np.asarray(y0)[1, 2:], np.asarray(y1)[1, 2:])
    for a, b in zip(c0, c1):
        np.testing.assert_array_equal(np.asarray(unwrap(a)),
                                      np.asarray(unwrap(b)))
    held = jnp.asarray([True, False])
    _, c2 = m.forward_cached(jnp.asarray(x[:, :1]), c0, jnp.int32(32),
                             jnp.asarray(start), held)
    for a, b in zip(c2, c0):
        np.testing.assert_array_equal(np.asarray(unwrap(a))[1],
                                      np.asarray(unwrap(b))[1])
    assert np.any(np.asarray(unwrap(c2.state))[0]
                  != np.asarray(unwrap(c0.state))[0])


def test_the_mixer_equals_the_reference_layer():
    """The mixer (chunks of 16 over 48 tokens) against the reference's
    linear layer: the convolution written as shifted copies, the delta rule
    token by token."""
    m = _mixer()
    cfg = {"linear_num_key_heads": 2, "linear_num_value_heads": 4,
           "linear_key_head_dim": 16, "linear_value_head_dim": 16,
           "rms_norm_eps": 1e-6, "layernorm_gating_weight": 2,
           "linear_attn_o_norm_eps": 1e-6, "linear_sigmoid_gate_scale": 2}
    lw = {"in_norm": jnp.zeros(32), "in_post": jnp.zeros(32),
          "qkv": unwrap(m.qkv_proj), "z": unwrap(m.z_proj),
          "b": unwrap(m.b_proj), "a": unwrap(m.a_proj),
          "conv": unwrap(m.conv), "dt_bias": unwrap(m.dt_bias),
          "A_log": unwrap(m.A_log), "o_norm": unwrap(m.norm),
          "out": unwrap(m.out_proj)}
    x = np.random.default_rng(3).normal(0, 1, (1, 48, 32)).astype(np.float32)
    u = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6)
    got, _ = _feed(m, u, (16, 16, 16), np.zeros(1, np.int32))
    want = np.asarray(ref._linear(
        jnp.asarray(x[0]), lw, cfg_key=tuple(sorted(cfg.items())),
        precision="float32")) - x[0]
    # the reference norms the branch once more (gain 1 at w = 0)
    post = got[0] / np.sqrt((got[0] ** 2).mean(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(post, want, atol=2e-5)


# -- (b) the whole model ---------------------------------------------------------

def _jit_feed(model, start):
    types = [type(c) for c in model.init_cache(1, 1)]

    @jax.jit
    def feed(cache, block, pos, rows):
        ring = [cls(*(Tensor(p) for p in c)) for cls, c in zip(types, cache)]
        logits, new = model.forward_cached(Tensor(block), ring, pos,
                                           Tensor(start), rows)
        return unwrap(logits), [tuple(unwrap(p) for p in c) for c in new]
    return feed


def test_chunks_then_steps_equal_the_reference_in_used_slots(served):
    """Two rows of 45 and 22 prompt tokens, left-padded to 48 columns of a
    cache whose rows an earlier request FILLED (its states, convolution
    inputs and latent columns are all still there), prefilled in chunks of
    16 from column 64 on and decoded 6 single steps through the cache:
    every logit of every valid position against the reference's full
    forward of that row."""
    cfg, model, view = served
    rng = np.random.default_rng(1)
    lens, base, P, steps = (45, 22), 64, 48, 6
    rows = [rng.integers(0, VOCAB, n + steps).astype(np.int32) for n in lens]
    ids = np.zeros((2, base + P + steps), np.int32)
    ids[:, :base] = rng.integers(0, VOCAB, (2, base))
    for b, (n, r) in enumerate(zip(lens, rows)):
        ids[b, base + P - n:] = r
    cache = [tuple(unwrap(p) for p in c) for c in model.init_cache(2, 128)]
    live = jnp.ones((2,), bool)
    # the earlier occupant: 64 tokens a row from column 0
    before = _jit_feed(model, jnp.zeros((2,), jnp.int32))
    for pos in range(0, base, CHUNK):
        _, cache = before(cache, jnp.asarray(ids[:, pos:pos + CHUNK]),
                          jnp.int32(pos), live)
    feed = _jit_feed(model, jnp.asarray([base + P - n for n in lens],
                                        jnp.int32))
    got = []
    for pos in list(range(base, base + P, CHUNK)) \
            + list(range(base + P, base + P + steps)):
        w = CHUNK if pos < base + P else 1
        out, cache = feed(cache, jnp.asarray(ids[:, pos:pos + w]),
                          jnp.int32(pos), live)
        got.append(np.asarray(out))
    got = np.concatenate(got, 1)
    for b, (n, r) in enumerate(zip(lens, rows)):
        np.testing.assert_allclose(got[b, P - n:],
                                   _reference_logits(cfg, view, r),
                                   atol=LOGIT_TOL)


def test_the_cacheless_forward_equals_the_reference(served):
    cfg, model, view = served
    ids = np.random.default_rng(7).integers(0, VOCAB, 40).astype(np.int32)
    got = np.asarray(unwrap(model(Tensor(jnp.asarray(ids[None])))))[0]
    np.testing.assert_allclose(got, _reference_logits(cfg, view, ids),
                               atol=LOGIT_TOL)


def _serve(model, requests, slots=2, cache_len=192, seed=1):
    """``requests`` [(prompt length, new tokens)] through a SlotLoop.
    Returns (prompts, tokens, stats)."""
    gen = Generator(model, max_len=cache_len, seq_buckets=[cache_len])
    loop = SlotLoop(gen, slots=slots, cache_len=cache_len, chunk=CHUNK)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, VOCAB, p).astype(np.int32)
               for p, _ in requests]
    futs = [loop.submit(p, k) for p, (_, k) in zip(prompts, requests)]
    out = [np.asarray(f.result(timeout=600)) for f in futs]
    stats = loop.stats()
    loop.close()
    return prompts, out, stats


def _served_gaps(cfg, view, prompts, tokens):
    """Per request, the widest gap by which a served token's reference
    logit lies below the reference's best, relative to max|logit| (single
    tokens, and no tie is resolved: ``tie`` 0)."""
    return [float(np.max(np.asarray(ref.token_gaps(cfg, view, p, t[0],
                                                   tie=0.0))))
            for p, t in zip(prompts, tokens)]


def test_slot_loop_equals_the_reference_and_counts_in_one_piece(served):
    """Prefill by chunks + decoding through SlotLoop, rows joining, waiting
    and retiring (5 requests over 2 slots: three are admitted into a used
    slot), equals the reference's full forward; and the counters say what
    ran, the latent plane's columns under the name the hybrid readers
    take."""
    cfg, model, view = served
    prompts, tokens, st = _serve(model, REQUESTS)
    assert max(_served_gaps(cfg, view, prompts, tokens)) < GAP_TOL
    assert st["plane_kinds"] == ["latent", "ssm_state"]
    assert st["chunk_row"] == "sliced" and "step_read" not in st
    assert st["latent_form"] == {"step": "absorbed", "chunk": "per_head"}
    assert st["chunk_tokens"] == sum(n for n, _ in REQUESTS)
    # four summed states a row: counted once a row / a token, as ever
    assert st["ssm_rows_updated"] == st["emitted_tokens"]
    assert st["chunk_ssm_tokens"] == st["chunk_tokens"]
    assert st["state_rows_held"] > 0
    # the latent plane's valid columns: once a dispatch, and a layer (one)
    assert st["chunk_kv_columns_valid"] == sum(
        n * (n + 1) // 2 for n, _ in REQUESTS)
    assert st["kv_columns_valid"] == st["attn_columns_valid"] > 0
    assert st["chunk_attn_columns_valid"] == st["chunk_kv_columns_valid"]
    # 3 of 16 experts a token in 4 layers; 4 of the 16 are held
    assert st["moe_assignments"] == 4 * 3 * (
        st["chunk_tokens"] + st["emitted_tokens"])
    assert 0 < st["moe_assignments_held"] < st["moe_assignments"]


def test_through_the_server(served):
    """``Server`` -> ``register_decode`` -> ``submit_decode`` with the slot
    loop on: the served tokens' reference gaps are as small."""
    from paddle_tpu import serving
    from paddle_tpu.framework.flags import get_flags, set_flags
    cfg, model, view = served
    names = ["FLAGS_decode_slots", "FLAGS_prefill_chunk",
             "FLAGS_decode_max_len"]
    before = get_flags(names)
    set_flags({"FLAGS_decode_slots": 2, "FLAGS_prefill_chunk": CHUNK,
               "FLAGS_decode_max_len": 192})
    try:
        srv = serving.Server(serving.ServingConfig(workers=4,
                                                   queue_capacity=64))
        srv.register_decode("m", model, batch_buckets=(1,),
                            seq_buckets=(64, 192), max_new_tokens=8,
                            max_len=192)
        srv.start()
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, VOCAB, n).astype(np.int32)
                   for n in (40, 17, 33)]
        futs = [srv.submit_decode("m", [p], max_new_tokens=6)
                for p in prompts]
        tokens = [np.asarray(f.result(timeout=600)[0]) for f in futs]
        srv.stop(drain=False)
    finally:
        set_flags(before)
    assert max(_served_gaps(cfg, view, prompts, tokens)) < GAP_TOL


@pytest.mark.parametrize("dropped", [
    "post_norms", "norm_gain", "gate", "clamp", "yarn"])
def test_a_dropped_option_fails(served, dropped):
    """One control an option: the same weights in a program built with
    norms before each half only, with plain gains, with a gate a head in
    the latent layer, without the clamp, or without YaRN; each FAILS the
    logit tolerance that the whole model passes by an order or more (the
    clamp: at a limit that binds at this size).  (The linear layers' gate
    scale of 2 has no such control: the norm after the mixer divides any
    constant out again.)"""
    cfg, _, view = served
    if dropped == "clamp":
        # (the tiny model's gates stay far inside 10: both sides are given
        # a limit that binds, and the program then loses its own)
        cfg = dict(cfg, swiglu_limit=0.25)
    pc = bench_models.program_config(cfg)
    over = {"post_norms": {"block_norms": "pre"},
            "norm_gain": {"norm_gain": "plain"},
            "gate": {"latent_gate": True},
            "clamp": {"ffn_limit": None},
            "yarn": {"rope_scaling": None},
            "gate_scale": {"delta_gate_scale": 1.0}}[dropped]
    model = HybridConvDecoder(dataclasses.replace(pc, **over))
    model.eval()
    mapped = bench_models.to_program(ref.init_weights(cfg, 5))
    for name, p in model.named_parameters():
        have = jnp.asarray(mapped[name])
        if dropped == "norm_gain" and name.endswith("norm.weight"):
            have = jnp.ones_like(have)      # a plain gain of 1 = sigmoid's 0
        if have.shape != p._value.shape:
            # the gate a head: the first feature's gate of each head
            have = have.reshape(have.shape[0], 4, -1)[:, :, 0]
        p.set_value(jnp.asarray(have, p._value.dtype))
    ids = np.random.default_rng(0).integers(0, VOCAB, 48).astype(np.int32)
    cache = model.init_cache(1, 64)
    got = []
    for pos in range(0, 48, CHUNK):
        out, cache = model.forward_cached(
            Tensor(jnp.asarray(ids[None, pos:pos + CHUNK])), cache, pos,
            Tensor(jnp.zeros(1, jnp.int32)))
        got.append(np.asarray(unwrap(out)))
    err = np.abs(np.concatenate(got, 1)[0]
                 - _reference_logits(cfg, view, ids)).max()
    assert err > 10 * LOGIT_TOL


# -- (c) the shares add up -------------------------------------------------------

def test_sixteen_shares_add_up_to_the_uncut_layer():
    """A small expert layer of 32 experts, 8 a token, clamped, one shared
    expert: the routed parts of all 16 shares of 2 experts each, with the
    shared expert's part counted ONCE, are the uncut layer's output (the
    same router, the same weights: what expert parallelism asks of the
    layer, here without the exchange)."""
    E, k, h, F = 32, 8, 32, 16
    rng = np.random.default_rng(0)
    whole = DroplessMoE(h, F, E, k, shared=1, scaling=2.5, limit=1.0)
    for name, p in whole.named_parameters():
        p.set_value(jnp.asarray(
            rng.normal(0, 0.05 if name == "router_bias" else
                       p._value.shape[-2] ** -0.5 if p._value.ndim > 1
                       else 1.0, p._value.shape), p._value.dtype))
    u = jnp.asarray(rng.normal(0, 2.0, (1, 24, h)), jnp.float32)
    want = np.asarray(unwrap(whole(u)))
    shared = np.asarray(unwrap(whole.shared(u)))
    total = np.zeros_like(want)
    for s in range(16):
        lo, hi = 2 * s, 2 * s + 2
        part = DroplessMoE(h, F, E, k, held=(lo, hi), shared=1, scaling=2.5,
                           limit=1.0)
        for name, p in part.named_parameters():
            src = dict(whole.named_parameters())[name]._value
            if name in ("w_gate", "w_up", "w_down"):
                src = src[lo:hi]
            p.set_value(src)
        total += np.asarray(unwrap(part(u))) - shared
    # two differently ordered float32 sums of 8 experts' parts ~1 wide
    np.testing.assert_allclose(total + shared, want, atol=2e-5)
    # the clamp binds: the same layer without it says otherwise
    free = DroplessMoE(h, F, E, k, shared=1, scaling=2.5)
    for name, p in free.named_parameters():
        p.set_value(dict(whole.named_parameters())[name]._value)
    assert np.abs(np.asarray(unwrap(free(u))) - want).max() > 1e-2


# -- (e) the clamp and the gates -------------------------------------------------

def test_the_clamp_binds_and_is_the_references():
    """A SwiGLU whose gate input passes the limit somewhere: the program's
    clamp is the reference's ``W_down(silu(min(g, L)) * clip(v, -L, L))``,
    and leaving it out changes the output."""
    rng = np.random.default_rng(0)
    m, free = SwiGLU(16, 32, limit=2.0), SwiGLU(16, 32)
    for (name, p), (_, q) in zip(m.named_parameters(),
                                 free.named_parameters()):
        p.set_value(jnp.asarray(rng.normal(0, 0.5, p._value.shape),
                                jnp.float32))
        q.set_value(p._value)
    u = jnp.asarray(rng.normal(0, 2.0, (5, 16)), jnp.float32)
    g = np.asarray(u) @ np.asarray(unwrap(m.w_gate))
    assert (g > 2.0).any() and (g < -2.0).any()
    want = ref.swiglu(Arith("float32"), u, unwrap(m.w_gate), unwrap(m.w_up),
                      unwrap(m.w_down), 2.0)
    np.testing.assert_allclose(np.asarray(unwrap(m(u))), np.asarray(want),
                               atol=1e-5)
    assert np.abs(np.asarray(unwrap(free(u))) - np.asarray(want)).max() > 0.1


def test_a_clamp_is_refused_on_experts_without_a_gate():
    with pytest.raises(InvalidArgumentError, match="clamp"):
        DroplessMoE(16, 8, 4, 2, activation="relu2", limit=10.0)


def test_the_gate_a_feature_is_not_the_gate_a_head():
    """Two latent layers with the same weights but for the gate: a number
    a feature (``hidden -> heads x v_dim``) against a number a head; the
    first equals the second only where a head's features share one gate."""
    kw = dict(window=None, index_topk=0, cache_block=8, attn_block=8,
              rescale=False, dtype="float32")
    rng = np.random.default_rng(0)
    feat = LatentAttention(32, 2, 8, 4, 8, 16, 12, 1e4, gate="feature", **kw)
    head = LatentAttention(32, 2, 8, 4, 8, 16, 12, 1e4, gate=True, **kw)
    assert tuple(unwrap(feat.gate).shape) == (32, 16)
    assert tuple(unwrap(head.gate).shape) == (32, 2)
    for name, p in feat.named_parameters():
        p.set_value(jnp.asarray(rng.normal(0, 0.3, p._value.shape),
                                jnp.float32))
    for name, p in head.named_parameters():
        src = dict(feat.named_parameters())[name]._value
        p.set_value(src.reshape(32, 2, 8)[:, :, 0] if name == "gate" else src)
    x = jnp.asarray(rng.normal(0, 1, (1, 6, 32)), jnp.float32)
    a, b = np.asarray(unwrap(feat(x))), np.asarray(unwrap(head(x)))
    assert np.abs(a - b).max() > 1e-3
    # one gate repeated over a head's features: the gate a head
    rep = np.repeat(np.asarray(unwrap(head.gate)), 8, axis=1)
    feat.gate.set_value(jnp.asarray(rep))
    np.testing.assert_allclose(np.asarray(unwrap(feat(x))), b, atol=1e-6)
    with pytest.raises(ValueError, match="gate"):
        LatentAttention(32, 2, 8, 4, 8, 16, 12, 1e4, gate="row", **kw)


def test_the_sigmoid_gain_norm():
    n = SigmoidGainRMSNorm(8, 1e-6, 2.0, dtype="float32")
    x = jnp.asarray(np.random.default_rng(0).normal(0, 3, (4, 8)),
                    jnp.float32)
    rms = np.asarray(x) / np.sqrt((np.asarray(x) ** 2).mean(-1,
                                                            keepdims=True))
    np.testing.assert_allclose(np.asarray(n(x)), rms, atol=1e-5)   # w = 0
    n.weight.set_value(jnp.full((8,), 30.0))
    np.testing.assert_allclose(np.asarray(n(x)), 2 * rms, atol=1e-5)


# -- (f) the counts, and what the loop is told ----------------------------------

def test_counts_equal_the_leaf_shapes_at_the_published_widths():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "gigachat3.5-ep16-serve.json")) as f:
        cfg = json.load(f)
    leaves = sum(math.prod(s) for s, _ in ref.leaf_shapes(cfg).values())
    assert counts.params(cfg) == leaves
    assert leaves == pytest.approx(4731.6e6, rel=5e-5)
    p = counts.parameters(cfg)
    assert p["linear"] // 4 == pytest.approx(235.86e6, rel=1e-4)
    assert p["full"] == pytest.approx(159.84e6, rel=1e-4)
    assert counts.state_bytes_per_row_layer(cfg) == 4_194_304
    assert counts.conv_bytes_per_row_layer(cfg) == 98_304
    assert counts.linear_state_plane(cfg) == "f32[%d,64,128,128]" \
        % cfg["serve"]["slots"]


def test_the_model_says_what_its_layers_keep(served):
    _, model, _ = served
    spec = model.cache_spec(192)
    assert [s["kind"] for s in spec] == [
        "ssm_state", "latent", "ssm_state", "ssm_state", "ssm_state"]
    assert [s["columns"] for s in spec] == [0, 192, 0, 0, 0]
    assert spec[1]["select_top"] is None and not spec[1]["wraps"]


@pytest.mark.parametrize("feature", ["prefix_cache", "session_store"])
def test_kv_movers_refuse_the_planes(served, feature):
    """The prefix cache, and the session store, refuse the summed states:
    this configuration runs without them."""
    from paddle_tpu.serving.prefix_cache import PrefixCache
    from paddle_tpu.serving.sessions import SessionStore
    _, model, _ = served
    gen = Generator(model, max_len=192, seq_buckets=[192])
    kw = {"prefix_cache": PrefixCache(CHUNK, 1 << 20)} \
        if feature == "prefix_cache" else {"session_store": SessionStore(4)}
    with pytest.raises(InvalidArgumentError, match="ssm_state"):
        SlotLoop(gen, slots=2, cache_len=192, chunk=CHUNK, **kw)
