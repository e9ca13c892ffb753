"""The hybrid decoder in its one-part-a-layer form (``nemotron_h``: Mamba-2
state-space layers, relu^2 experts with a shared expert, grouped-query
attention without per-head norm and positions, an untied head) at tiny
widths on the CPU, float32, seeded weights: the chunked scan against the
token-by-token recurrence; the whole-sequence forward, chunks and steps
through the cache, and the slot loop against the plain reference's full
forward (benchmark/reference/nemotron_h.py, which imports nothing of the
program); the summed state's rules (a reused slot, a row that waits while
others step, who shares the step, a ring restart); the expert layer's
shares.  Logits are compared, never sampled tokens.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.models import nemotron_h as bench_models       # noqa: E402
from benchmark.reference import nemotron_h as ref              # noqa: E402
from benchmark.reference.common import Arith                   # noqa: E402
from paddle_tpu.framework.enforce import InvalidArgumentError  # noqa: E402
from paddle_tpu.framework.functional import _bound_state       # noqa: E402
from paddle_tpu.framework.tensor import Tensor, unwrap         # noqa: E402
from paddle_tpu.nn.layer.mamba2 import Mamba2Mixer             # noqa: E402
from paddle_tpu.nn.layer.moe import DroplessMoE                # noqa: E402
from paddle_tpu.serving.slots import SlotLoop                  # noqa: E402
from paddle_tpu.text.generation import Generator               # noqa: E402
from paddle_tpu.text.models.hybrid_conv import (               # noqa: E402
    GroupedQueryAttention, HybridConvConfig, HybridConvDecoder)

# float32 on the CPU: the program (chunked scan, cache, padded expert
# rows) and the reference (token by token, one head and one expert at a
# time) differ by summation order only
LOGIT_TOL = 3e-5
GAP_TOL = 1e-4
VOCAB = 96
CHUNK = 16          # the slot loop's prefill chunk: two scan chunks of 8
# prompts of 5-45 tokens in chunks of 16 (1-3 chunks), 8 requests over 3
# slots: every slot is reused, rows wait between their chunks while their
# neighbours step
REQUESTS = [(21, 6), (37, 8), (5, 4), (45, 8), (16, 8), (33, 5), (14, 7),
            (40, 6)]


def _tiny():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron-3-nano-ep8-serve.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "tests", "data",
                           "nemotron_tiny.json")) as f:
        over = json.load(f)["over"]
    cfg["serve"].update(over.pop("serve"))
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def served():
    """ONE tiny model with the reference's seeded weights, and its view of
    them for the reference (shared by the whole module: one build)."""
    from benchmark import harness
    cfg = _tiny()
    mapped = bench_models.to_program(ref.init_weights(cfg, 5))
    model = bench_models.build(cfg, mapped)
    return cfg, model, harness.canonical_view(mapped,
                                              bench_models.leaf_ids(cfg))


def _reference_logits(cfg, view, ids):
    """The reference's logits at every position of ``ids [T]``."""
    return np.asarray(ref.served_logits(
        cfg, view, ids[:1], np.concatenate([ids[1:], [0]])))


def test_tiny_covers_every_kind_of_layer(served):
    cfg, model, _ = served
    kinds = [(type(l.mixer).__name__, type(l.ffn).__name__)
             for l in model.layers]
    assert kinds == [{"M": ("Mamba2Mixer", "NoneType"),
                      "E": ("NoneType", "DroplessMoE"),
                      "*": ("GroupedQueryAttention", "NoneType")}[c]
                     for c in "MEMEM*EME"]
    names = {n for n, _ in model.named_parameters()}
    assert "lm_head" in names                                   # untied
    assert not any("q_norm" in n or "w_gate" in n for n in names)
    assert model.layers[5].mixer.rep == 2
    assert model.layers[1].ffn.shared.w_up.shape == [64, 64]
    # the state's own scalars are float32 whatever the matrices are
    assert str(model.layers[0].mixer.A_log.dtype).endswith("float32")


# -- the mixer: one recurrence in two forms --------------------------------------

def _mixer(seed=0, dtype="float32"):
    m = Mamba2Mixer(32, heads=4, head_dim=8, state=16, groups=2, taps=4,
                    chunk=8, dtype=dtype)
    key = jax.random.key(seed)
    for i, (name, p) in enumerate(m.named_parameters()):
        k = jax.random.fold_in(key, i)
        if name == "A_log":
            v = jnp.log(jax.random.uniform(k, p.shape, minval=1., maxval=16.))
        elif name == "dt_bias":
            v = jax.random.uniform(k, p.shape, minval=-4.0, maxval=0.0)
        elif name in ("D", "norm"):
            v = 1.0 + 0.1 * jax.random.normal(k, p.shape)
        else:
            v = jax.random.normal(k, p.shape) * p.shape[0] ** -0.5
        p.set_value(jnp.asarray(v, p._value.dtype))
    m.eval()
    return m


def _feed(m, x, widths, start, rows=None):
    """``x [B, T, hidden]`` through ``forward_cached`` in blocks of
    ``widths`` from column 0; returns (outputs, the last cache)."""
    cache = m.gen_cache(x.shape[0], 0, "float32")
    out, pos = [], 0
    for w in widths:
        y, cache = m.forward_cached(x[:, pos:pos + w], cache, jnp.int32(pos),
                                    jnp.asarray(start, jnp.int32), rows)
        out.append(y)
        pos += w
    return jnp.concatenate(out, 1), cache


@pytest.mark.parametrize("widths,start", [
    ([32], (0, 0)),                 # four scan chunks in one block
    ([16, 16], (0, 0)),             # a state carried from block to block
    ([16, 16], (5, 11)),            # left padding: start inside a chunk
    ([16, 16], (3, 19)),            # row 1's first block is all padding
    ([8, 24], (8, 9)),              # start on a chunk's edge and just past
    ([12, 20], (0, 7)),             # blocks that are no multiple of a chunk
    ([1] * 6 + [26], (2, 0)),       # steps first, then a block
], ids=["one-block", "carried", "start-inside", "all-padding", "on-the-edge",
        "ragged-blocks", "steps-then-block"])
def test_the_chunked_scan_equals_the_token_by_token_update(widths, start):
    """Blocks of several scan chunks from a carried state give what the
    one-token update gives when fed the same tokens one by one, on every
    valid token; and a row's state after it is the same."""
    m = _mixer()
    x = jax.random.normal(jax.random.key(3), (2, 32, 32))
    with jax.default_matmul_precision("highest"):
        got, c1 = _feed(m, x, widths, start)
        want, c2 = _feed(m, x, [1] * 32, start)
    for b, s in enumerate(start):
        np.testing.assert_allclose(got[b, s:], want[b, s:], atol=2e-5)
    np.testing.assert_allclose(unwrap(c1.state), unwrap(c2.state), atol=2e-5)
    np.testing.assert_allclose(unwrap(c1.conv), unwrap(c2.conv), atol=2e-5)


def test_the_mixer_equals_the_reference_layer():
    """The whole mixer (chunked scan from a zero state) against the
    reference's ``M`` layer: the same weights, its residual taken off."""
    m = _mixer(1)
    w = {k: unwrap(v) for k, v in m.named_parameters()}
    lw = {"norm": jnp.ones(32), "in_proj": w["in_proj"], "conv": w["conv"],
          "conv_b": w["conv_bias"], "dt_bias": w["dt_bias"],
          "A_log": w["A_log"], "D": w["D"], "norm_g": w["norm"],
          "out_proj": w["out_proj"]}
    cfg = {"mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2,
           "ssm_state_size": 16, "norm_eps": 1e-5}
    x = jax.random.normal(jax.random.key(4), (1, 27, 32))
    from paddle_tpu.nn.layer.latent_attention import _rms
    with jax.default_matmul_precision("highest"):
        got = m(_rms(x, 1e-5))
        want = ref._mamba(x[0], lw, cfg_key=tuple(sorted(cfg.items())),
                          precision="float32") - x[0]
    np.testing.assert_allclose(got[0], want, atol=2e-5)


def test_a_row_outside_write_rows_keeps_state_and_inputs():
    m = _mixer()
    x = jax.random.normal(jax.random.key(5), (2, 17, 32))
    _, before = _feed(m, x[:, :16], [16], (0, 0))
    y, after = m.forward_cached(x[:, 16:], before, jnp.int32(16),
                                jnp.zeros(2, jnp.int32),
                                jnp.asarray([True, False]))
    for kept, new in zip(before, after):
        np.testing.assert_array_equal(unwrap(kept)[1], unwrap(new)[1])
        assert float(jnp.abs(unwrap(kept)[0] - unwrap(new)[0]).max()) > 0


def test_the_state_is_float32_beside_planes_of_the_weights_dtype():
    model = HybridConvDecoder(HybridConvConfig.tiny_ssm(dtype="bfloat16"))
    cache = model.init_cache(2, 32)
    assert [type(c).__name__ for c in cache] == [
        "SsmStateCache"] * 3 + ["RingCache", "SsmStateCache"]
    assert str(cache[0].state.dtype).endswith("float32") \
        and tuple(cache[0].state.shape) == (2, 4, 16, 16)
    assert str(cache[0].conv.dtype).endswith("bfloat16") \
        and tuple(cache[0].conv.shape) == (2, 1, 3, 128)
    assert str(cache[3].k.dtype).endswith("bfloat16")
    # ... and stays so through a feed
    ids = Tensor(jnp.zeros((2, 8), jnp.int32))
    _, new = model.forward_cached(ids, cache, 0, Tensor(jnp.zeros(2, jnp.int32)))
    assert [str(p.dtype) for p in new[0]] == [str(p.dtype) for p in cache[0]]


def _state_errors(seed):
    """A mixer in bfloat16 (as served: float32 state) fed 1,024 tokens in
    blocks of 128 and 64 more one by one, with the family's drawn decays
    (step sizes 0.001-0.1, ``A`` 1-16: the slowest head remembers ~1,000
    tokens); beside it the reference's token-by-token recurrence on the
    same rounded weights in float32, and that recurrence with the state
    rounded to bfloat16 after every token.  Per head: the relative error
    of the served state and of the rounded one against the float32 state."""
    H, P, N, G, hid, T, steps = 8, 16, 32, 2, 64, 1024, 64
    f32, hi = jnp.float32, jax.lax.Precision.HIGHEST
    m = Mamba2Mixer(hid, heads=H, head_dim=P, state=N, groups=G, taps=4,
                    chunk=32, dtype="bfloat16")
    key = jax.random.key(seed)
    step0 = jnp.exp(jnp.linspace(np.log(1e-3), np.log(1e-1), H))
    for i, (name, p) in enumerate(m.named_parameters()):
        k = jax.random.fold_in(key, i)
        if name == "A_log":
            v = jnp.log(jnp.linspace(1.0, 16.0, H))
        elif name == "dt_bias":
            v = step0 + jnp.log(-jnp.expm1(-step0))    # softplus^-1
        elif name in ("D", "norm"):
            v = jnp.ones(p.shape)
        elif name == "conv":
            v = jax.random.normal(k, p.shape) * 0.5
        else:
            v = jax.random.normal(k, p.shape) * p.shape[0] ** -0.5
        p.set_value(jnp.asarray(v, p._value.dtype))
    m.eval()
    x = jax.random.normal(jax.random.fold_in(key, 99),
                          (1, T + steps, hid)).astype(jnp.bfloat16)
    cache, pos = m.gen_cache(1, 0, "bfloat16"), 0
    for width in [128] * (T // 128) + [1] * steps:
        _, cache = m.forward_cached(x[:, pos:pos + width], cache,
                                    jnp.int32(pos), jnp.zeros(1, jnp.int32))
        pos += width
    assert str(cache.state.dtype).endswith("float32")
    # the reference's pieces, from the same (rounded) weights in float32
    w = {k: unwrap(v).astype(f32) for k, v in m.named_parameters()}
    z, xbc, dt = jnp.split(jnp.dot(x[0].astype(f32), w["in_proj"],
                                   precision=hi), [H * P, H * P + m.conv_dim],
                           -1)
    conv = jnp.zeros_like(xbc) + w["conv_bias"]
    for j in range(4):
        conv = conv + w["conv"][:, j] * jnp.pad(xbc, ((3 - j, 0), (0, 0)))[:pos]
    xs, b, c = jnp.split(jax.nn.silu(conv), [H * P, H * P + G * N], -1)
    b, c = (jnp.repeat(t.reshape(pos, G, N), H // G, 1) for t in (b, c))
    args = (xs.reshape(pos, H, P), jax.nn.softplus(dt + w["dt_bias"]), b, c,
            -jnp.exp(w["A_log"]))
    _, exact = ref.recurrence(*args, final_state=True)
    _, rounded = ref.recurrence(*args, state_dtype=jnp.bfloat16,
                                final_state=True)

    def by_head(h):
        return np.asarray(
            jnp.linalg.norm((h - exact).reshape(H, -1), axis=1)
            / jnp.linalg.norm(exact.reshape(H, -1), axis=1))
    return by_head(unwrap(cache.state)[0]), by_head(rounded)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_served_state_lies_nearer_the_reference_than_a_rounded_one(seed):
    """What the cell's comparison of tokens cannot see (PERF.md section 2:
    the state control reads 0 there) is read here from the state itself.
    A state rounded to bfloat16 after every token walks away from the
    float32 one on the heads that decay slowly (the rounding adds up over
    the ~1,000 tokens such a head remembers); the served state, float32
    and updated in float32 from bfloat16 projections, does not: its error
    on every head is that of its inputs' rounding."""
    served, rounded = _state_errors(seed)
    assert served.max() < 0.008 < 0.024 < rounded.max()
    # the slowest head is where the rounded state is lost
    assert rounded[0] > 3 * served[0]


# -- the model against the reference ----------------------------------------------

def test_whole_sequence_forward_equals_the_reference(served):
    cfg, model, view = served
    ids = np.random.default_rng(0).integers(0, VOCAB, 37).astype(np.int32)
    got = np.asarray(unwrap(model(Tensor(jnp.asarray(ids[None])))))[0]
    np.testing.assert_allclose(got, _reference_logits(cfg, view, ids),
                               atol=LOGIT_TOL)


def test_chunks_then_steps_equal_the_reference_for_unequal_starts(served):
    """Two rows of 43 and 21 prompt tokens, left-padded to 48 columns (so
    ``start`` is 5 and 27), prefilled in chunks of 16 (row 1's first chunk
    is all padding and its second begins with it; every chunk is two scan
    chunks of 8) and then decoded 5 single steps through the cache: every
    logit of every valid position against the reference's full forward of
    that row."""
    cfg, model, view = served
    rng = np.random.default_rng(1)
    lens, P, steps = (43, 21), 48, 5
    rows = [rng.integers(0, VOCAB, n + steps).astype(np.int32) for n in lens]
    ids = np.zeros((2, P + steps), np.int32)
    for b, (n, r) in enumerate(zip(lens, rows)):
        ids[b, P - n:] = r
    start = jnp.asarray([P - n for n in lens], jnp.int32)

    @jax.jit
    def feed(cache, block, pos):
        ring = [cls(*(Tensor(p) for p in c)) for cls, c in zip(types, cache)]
        logits, new = model.forward_cached(Tensor(block), ring, pos,
                                           Tensor(start))
        return unwrap(logits), [tuple(unwrap(p) for p in c) for c in new]

    types = [type(c) for c in model.init_cache(1, 1)]
    cache = [tuple(unwrap(p) for p in c) for c in model.init_cache(2, 64)]
    got = []
    for pos in range(0, P, CHUNK):
        out, cache = feed(cache, jnp.asarray(ids[:, pos:pos + CHUNK]),
                          jnp.int32(pos))
        got.append(np.asarray(out))
    for pos in range(P, P + steps):
        out, cache = feed(cache, jnp.asarray(ids[:, pos:pos + 1]),
                          jnp.int32(pos))
        got.append(np.asarray(out))
    got = np.concatenate(got, 1)
    for b, (n, r) in enumerate(zip(lens, rows)):
        np.testing.assert_allclose(got[b, P - n:],
                                   _reference_logits(cfg, view, r),
                                   atol=LOGIT_TOL)


def _serve(model, requests, slots=3, cache_len=96, one_by_one=False, seed=1):
    """``requests`` [(prompt or its length, new tokens)] through a SlotLoop,
    all at once or ``one_by_one``.  Returns (prompts, tokens, stats, the
    logits the step program handed back at every step)."""
    gen = Generator(model, max_len=cache_len, seq_buckets=[cache_len])
    loop = SlotLoop(gen, slots=slots, cache_len=cache_len, chunk=CHUNK)
    step, seen = loop._step, []

    def recording(*args):
        out = step(*args)
        seen.append(np.asarray(out[1]))
        return out
    loop._step = recording
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, VOCAB, p).astype(np.int32)
               if isinstance(p, int) else p for p, _ in requests]
    if one_by_one:
        out = [np.asarray(loop.submit(p, k).result(timeout=300))
               for p, (_, k) in zip(prompts, requests)]
    else:
        futs = [loop.submit(p, k) for p, (_, k) in zip(prompts, requests)]
        out = [np.asarray(f.result(timeout=300)) for f in futs]
    stats = loop.stats()
    loop.close()
    return prompts, out, stats, seen


def _widest_gap(cfg, view, prompts, tokens):
    return max(float(jnp.max(ref.served_gaps(cfg, view, p, t)))
               for p, t in zip(prompts, tokens))


def test_slot_loop_equals_the_reference_and_counts_in_one_piece(served):
    """Prefill by chunks + decoding through SlotLoop, rows joining,
    waiting and retiring (8 requests over 3 slots), equals the reference's
    full forward; and the counters, committed with ``steps`` in one piece,
    say what ran."""
    cfg, model, view = served
    prompts, tokens, st, _ = _serve(model, REQUESTS)
    assert _widest_gap(cfg, view, prompts, tokens) < GAP_TOL
    assert st["plane_kinds"] == ["kv", "ssm_state"]
    # this family's chunk still runs on a row cut out of the planes
    assert st["chunk_row"] == "sliced"
    expert_layers, k = 4, cfg["num_experts_per_tok"]
    fed = sum(n for n, _ in REQUESTS) + st["emitted_tokens"]
    assert st["chunk_tokens"] == sum(n for n, _ in REQUESTS)
    assert st["moe_assignments"] == fed * k * expert_layers
    assert st["moe_assignments_held"] == st["moe_assignments"]  # all held
    # every live row of a step updates its summed state, every valid
    # token of a chunk is scanned into one: rows and tokens, not layers
    assert st["ssm_rows_updated"] == st["emitted_tokens"]
    assert st["chunk_ssm_tokens"] == st["chunk_tokens"]
    # rows lay between two of their chunks while a step passed them by
    assert 0 < st["state_rows_held"] <= st["slot_steps_prefilling"]
    assert sum(st["slot_steps_" + s] for s in (
        "emitting", "prefilling", "drain_blocked", "no_demand")) \
        == st["steps"] * 3
    assert st["attn_blocks_total"] == st["steps"] \
        and 0 < st["attn_blocks_read"] <= st["attn_blocks_total"]


def test_a_reused_slot_gives_the_second_request_a_fresh_start(served):
    """One slot: request A, then request B in the slot A left (A's summed
    states, convolution inputs and K/V columns still lie there).  B's
    logits at every one of its steps are, bit for bit, what they are
    after ANOTHER occupant of A's lengths, and equal those of B alone in a
    fresh loop to the last bits (there B sits at other columns of the one
    attention block, so the softmax adds in another order): B's first
    chunk begins at or below its ``start``, so the state it is handed is
    zeros whatever lies there."""
    _, model, _ = served
    (_, b), both, _, seen = _serve(model, [(37, 7), (26, 6)], slots=1,
                                   one_by_one=True)
    other = np.random.default_rng(9).integers(0, VOCAB, 37).astype(np.int32)
    _, after, _, seen2 = _serve(model, [(other, 7), (b, 6)], slots=1,
                                one_by_one=True)
    _, alone, _, fresh = _serve(model, [(b, 6)], slots=1)
    np.testing.assert_array_equal(both[1], after[1])
    np.testing.assert_array_equal(both[1], alone[0])
    assert len(seen) == len(seen2) == 7 + 6 and len(fresh) == 6
    for got, same, want in zip(seen[7:], seen2[7:], fresh):
        np.testing.assert_array_equal(got, same)
        np.testing.assert_allclose(got, want, atol=2e-6)


def test_an_answer_does_not_depend_on_who_shares_the_step(served):
    """The same request among seven others (rows joining and leaving
    around it) and alone in the same loop: the same tokens, and its
    reference gap as small."""
    cfg, model, view = served
    prompts, crowd, _, _ = _serve(model, REQUESTS)
    _, alone, _, _ = _serve(model, [(prompts[3], REQUESTS[3][1])])
    np.testing.assert_array_equal(crowd[3], alone[0])
    assert _widest_gap(cfg, view, prompts[3:4], alone) < GAP_TOL


_PROGRAMS = {}


def _prefill_beside_a_live_row(model, steps_between):
    """Row 1 prefills a 40-token prompt in 3 chunks of 16 on the slot
    loop's own schedule (admitted at column 61, so ``act = 64``; chunk
    ``k`` goes out once the frontier has passed ``act - 3 + k``), through
    the Generator's own chunk and step programs.  With ``steps_between``
    row 0 decodes meanwhile, so two steps pass row 1 while it holds the
    state its last chunk left.  Returns every chunk's logits."""
    gen = Generator(model, max_len=96, seq_buckets=[96])
    masked = type(model).cached_forward_takes_rows
    if "chunk" not in _PROGRAMS:
        _PROGRAMS["chunk"] = jax.jit(gen._build_chunk(2, CHUNK, 96))
    if steps_between and masked not in _PROGRAMS:
        _PROGRAMS[masked] = jax.jit(gen._build_step(2, 96, -1))
    chunk, step = _PROGRAMS["chunk"], _PROGRAMS.get(masked)
    state, cache = gen._state_args(), gen.init_slot_cache(2, 96)
    prompt = np.random.default_rng(0).integers(1, VOCAB, 40).astype(np.int32)
    padded = np.concatenate([np.zeros(8, np.int32), prompt])
    logits = jnp.zeros((2, VOCAB), jnp.float32)
    start = np.array([0, 96], np.int32)       # row 1 is not generating
    done, live, outs = np.array([False, True]), np.array([True, False]), []
    for k in range(4):
        if k:
            cache, out = chunk(
                *state, cache, padded[None, CHUNK * (k - 1):CHUNK * k],
                np.array([64 - 40], np.int32), np.int32(1),
                np.int32(16 + CHUNK * (k - 1)))[:2]
            outs.append(np.asarray(out))
        if k < 3 and steps_between:
            cache, logits, _, _ = step(*state, cache, logits, start, done,
                                       live, np.zeros(2, bool), np.int32(61 + k))
    return np.stack(outs)


@pytest.mark.parametrize("masked", [True, False])
def test_a_row_that_waits_keeps_its_state(served, monkeypatch, masked):
    """A row that waits between two of its chunks while others step must
    find its summed state as its last chunk left it.  The step hands the
    model its live rows for that; with them ignored the waiting row's next
    chunk starts from a state that its neighbour's steps decayed and added
    a padding token to."""
    _, model, _ = served
    alone = _prefill_beside_a_live_row(model, steps_between=False)
    monkeypatch.setattr(type(model), "cached_forward_takes_rows", masked)
    beside = _prefill_beside_a_live_row(model, steps_between=True)
    if masked:
        np.testing.assert_array_equal(beside, alone)
    else:
        assert np.abs(beside - alone).max() > 1e-3


@pytest.mark.parametrize("slots,resets", [(2, 1), (1, 3)])
def test_a_ring_restart_needs_no_reset_of_the_state(served, slots, resets):
    """A ring of 56 columns: the loop drains and restarts its session at
    column 0 (once with two slots, three times with one), every slot's
    state still holding its last occupant's sum; the answers equal the
    reference's all the same (the restarted row's first chunk begins at or
    below its ``start``)."""
    cfg, model, view = served
    prompts, tokens, st, _ = _serve(model, REQUESTS, slots=slots,
                                    cache_len=56)
    assert st["session_resets"] >= resets
    assert _widest_gap(cfg, view, prompts, tokens) < GAP_TOL


# -- the seam: what the model says it keeps ------------------------------------------

def test_the_model_says_what_its_layers_keep(served):
    _, model, _ = served
    gen = Generator(model, max_len=64, seq_buckets=[64])
    spec = gen.cache_spec(64)
    # one entry a layer that HAS a mixer: the expert layers keep nothing
    assert [(s["kind"], s["columns"]) for s in spec] == [
        ("ssm_state", 0), ("ssm_state", 0), ("ssm_state", 0), ("kv", 64),
        ("ssm_state", 0)]
    assert gen.plane_kinds() == ["kv", "ssm_state"]
    assert gen.kv_heads_per_lane_row() == 8
    planes = gen.slot_cache_avals_all(3, 64)
    assert [(tuple(p.shape), str(p.dtype)) for p in planes[0]] == [
        ((3, 1, 3, 128), "float32"), ((3, 4, 16, 16), "float32")]
    assert tuple(planes[3][0].shape) == (3, 1, 64, 128)      # 2 KV heads of 16


@pytest.mark.parametrize("feature", ["prefix_cache", "session_store",
                                     "handoff"])
def test_kv_movers_refuse_a_summed_state(served, feature):
    _, model, _ = served
    gen = Generator(model, max_len=64, seq_buckets=[64])
    with pytest.raises(InvalidArgumentError, match="ssm_state"):
        if feature == "handoff":
            from paddle_tpu.serving.cluster import handoff
            handoff.require_kv_planes(gen.plane_kinds())
        else:
            SlotLoop(gen, slots=2, cache_len=64, chunk=CHUNK,
                     **{feature: object()})


# -- attention without per-head norm and positions -----------------------------------

@pytest.mark.parametrize("heads,kv,d", [(32, 2, 128), (8, 2, 16)])
def test_plain_grouped_queries_read_their_own_cached_head(heads, kv, d):
    """``rep`` query heads a cached head, no norm, no rotary: the cached
    path in blocks and steps equals the cache-less forward, which equals
    plain per-head attention written out here."""
    layer = GroupedQueryAttention(24, heads, kv, d, None, qk_norm=False)
    assert layer.q_norm is None and layer.base is None
    assert layer.rep == heads // kv
    x = jax.random.normal(jax.random.key(2), (1, 12, 24))
    with jax.default_matmul_precision("highest"):
        whole = unwrap(layer(x))
        w = {k: unwrap(v) for k, v in layer.named_parameters()}
        q = (x[0] @ w["q_proj"]).reshape(12, heads, d)
        k = (x[0] @ w["k_proj"]).reshape(12, kv, d)
        v = (x[0] @ w["v_proj"]).reshape(12, kv, d)
        t = jnp.arange(12)
        o = []
        for h in range(heads):
            s = q[:, h] @ k[:, h // layer.rep].T * d ** -0.5
            p = jax.nn.softmax(jnp.where(t[None] <= t[:, None], s, -1e30), -1)
            o.append(p @ v[:, h // layer.rep])
        plain = jnp.stack(o, 1).reshape(12, heads * d) @ w["o_proj"]
        cache = layer.gen_cache(1, 32)
        got, start = [], jnp.zeros(1, jnp.int32)
        for pos, width in ((0, 8), (8, 1), (9, 1), (10, 2)):
            y, cache = layer.forward_cached(x[:, pos:pos + width], cache,
                                            jnp.int32(pos), start)
            got.append(y)
    np.testing.assert_allclose(whole[0], plain, atol=2e-5)
    np.testing.assert_allclose(jnp.concatenate(got, 1), whole, atol=2e-5)


# -- relu^2 experts, a share of them, a shared expert ---------------------------------

def _moe_fn(layer):
    @jax.jit
    def f(weights, u):
        with _bound_state(layer, weights, {}):
            y = layer(u)
            return y, jnp.stack(layer.last_counts)
    return f


@pytest.mark.parametrize("form,names", [
    ("relu2", {"router", "router_bias", "w_up", "w_down", "shared.w_up",
               "shared.w_down"}),
    ("swiglu", {"router", "router_bias", "w_gate", "w_up", "w_down",
                "shared.w_gate", "shared.w_up", "shared.w_down"})])
def test_an_expert_has_the_matrices_of_its_form(form, names):
    layer = DroplessMoE(32, 16, 8, 3, shared=2, activation=form)
    assert {n for n, _ in layer.named_parameters()} == names
    assert layer.shared.w_up.shape == [32, 32]
    with pytest.raises(InvalidArgumentError, match="form"):
        DroplessMoE(32, 16, 8, 3, activation="gelu")


@pytest.mark.parametrize("tokens,skewed", [(16, False), (128, False),
                                           (128, True), (512, False),
                                           (512, True)])
def test_padded_and_grouped_relu2_products_agree(tokens, skewed):
    """The three ways to an expert's products give the reference's layer:
    the padded batched products at the even cap, the WIDE padded tier (128
    tokens all on three experts: 128 rows an expert where the cap is 48,
    and no dispatch of up to 256 tokens can pass it), and the grouped
    products (512 tokens all on three experts: 512 rows, over the wide
    tier's 256)."""
    layer = DroplessMoE(32, 16, 8, 3, shared=2, scaling=2.5, norm_eps=1e-20,
                        activation="relu2")
    w = {k: unwrap(v) for k, v in layer.named_parameters()}
    w = {k: v * (12.0 if k != "router_bias" else 1.0) for k, v in w.items()}
    if skewed:
        w["router_bias"] = w["router_bias"].at[:3].set(50.0)
    u = jax.random.normal(jax.random.key(7), (tokens, 32))
    with jax.default_matmul_precision("highest"):
        got, made = _moe_fn(layer)(w, u)
        want = _reference_experts(w, u, (0, 8), 8)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-5)
    assert int(made[0]) == int(made[1]) == tokens * 3
    assert int(made[2]) == (tokens if skewed else int(made[2]))


def _reference_experts(w, u, held, published, shared=True):
    cfg = {"num_experts_per_tok": 3, "norm_topk_prob": True,
           "routed_scaling_factor": 2.5}
    lw = {"router": w["router"], "router_b": w["router_bias"],
          "exp_u": w["w_up"], "exp_d": w["w_down"],
          "sh_u": w["shared.w_up"], "sh_d": w["shared.w_down"]}
    assert w["router"].shape[1] == published
    return ref.experts(Arith("float32"), u, lw, cfg, held, shared)


def test_eight_shares_add_up_to_the_uncut_reference_layer():
    """The guide's test: the parts that the 8 shares ``[0, 2) .. [14, 16)``
    of 16 experts give, with the shared expert (which every chip computes
    alike) counted ONCE, add up to what the uncut REFERENCE gives for the
    whole layer.  Share ``i`` is the layer that holds experts ``[0, 2)``
    of a router whose columns are rolled by ``2 i``: one program for
    every share."""
    kw = dict(shared=2, scaling=2.5, norm_eps=1e-20, activation="relu2")
    whole = DroplessMoE(32, 16, 16, 3, **kw)
    whole.router_bias.set_value(jnp.linspace(-0.1, 0.1, 16))
    w = {k: unwrap(v) * (1.0 if k == "router_bias" else 12.0)
         for k, v in whole.named_parameters()}
    u = jax.random.normal(jax.random.key(8), (2, 11, 32))
    part = _moe_fn(DroplessMoE(32, 16, 16, 3, held=(0, 2), **kw))
    with jax.default_matmul_precision("highest"):
        parts = [part({**w, "router": jnp.roll(w["router"], -2 * i, 1),
                       "router_bias": jnp.roll(w["router_bias"], -2 * i),
                       "w_up": w["w_up"][2 * i:2 * i + 2],
                       "w_down": w["w_down"][2 * i:2 * i + 2]}, u)
                 for i in range(8)]
        with _bound_state(whole, w, {}):
            once = unwrap(whole.shared(u.reshape(-1, 32))).reshape(u.shape)
        uncut = _reference_experts(w, u.reshape(-1, 32), (0, 16), 16)
    total = sum(p for p, _ in parts) - 7 * once
    np.testing.assert_allclose(total.reshape(-1, 32), uncut, atol=2e-4,
                               rtol=2e-5)
    assert float(jnp.abs(parts[0][0].reshape(-1, 32) - uncut).max()) > 1e-3
    assert sum(int(c[1]) for _, c in parts) == 2 * 11 * 3
