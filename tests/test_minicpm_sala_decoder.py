"""The hybrid decoder as ``minicpm_sala`` (lightning linear-attention layers
beside block-sparse grouped-query layers, muP scalings, output gates) at
tiny widths on the CPU, float32, seeded weights: (a) the linear mixer's
chunked form against the token-by-token recurrence, (b) the sparse mixer's
block choice and logits against the plain reference, rows at different
``start`` included, (c) the whole model through ``serving.Server`` and the
slot loop against the reference's full forward
(benchmark/reference/minicpm_sala.py, which imports nothing of the
program).  Logits are compared, never sampled tokens; each control (a
bfloat16 state, blocks cut on the absolute column grid, a dropped scaling)
must FAIL the tolerance its test passes.
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

from benchmark.models import minicpm_sala as bench_models     # noqa: E402
from benchmark.reference import minicpm_sala as ref            # noqa: E402
from benchmark.reference.common import Arith                   # noqa: E402
from paddle_tpu.framework.enforce import InvalidArgumentError  # noqa: E402
from paddle_tpu.framework.tensor import Tensor, unwrap         # noqa: E402
from paddle_tpu.nn.functional import attention as attn_fn      # noqa: E402
from paddle_tpu.nn.layer.linear_attention import (             # noqa: E402
    LightningAttention, decay_slopes)
from paddle_tpu.serving.slots import SlotLoop                  # noqa: E402
from paddle_tpu.text.generation import Generator               # noqa: E402
from paddle_tpu.text.models import hybrid_conv                 # noqa: E402
from paddle_tpu.text.models.hybrid_conv import (               # noqa: E402
    BlockSparseAttention, HybridConvDecoder)

# float32 on the CPU: the program (chunked scan, cache, masked blocks) and the reference (token by token, one cached head and one block
# of queries at a time) differ by summation order only; the logits are
# ~0.1 wide (the head reads the normed state divided by 4)
LOGIT_TOL = 2e-6
GAP_TOL = 1e-4
VOCAB = 96
CHUNK = 16          # the slot loop's prefill chunk: two scan chunks of 8
# prompts of 40-118 tokens (3-8 chunks): every one is past dense_len (32)
# before its prefill ends, so every step chooses 4 of 6-16 blocks; 6
# requests over 3 slots: every slot is reused, rows wait between chunks
REQUESTS = [(61, 6), (97, 8), (40, 4), (118, 8), (76, 8), (53, 5)]


def _tiny():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "minicpm-sala-pp2-serve.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "tests", "data",
                           "minicpm_sala_tiny.json")) as f:
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


def test_tiny_covers_both_kinds_of_layer(served):
    cfg, model, _ = served
    kinds = [type(l.mixer).__name__ for l in model.layers]
    assert kinds == ["BlockSparseAttention", "LightningAttention",
                     "LightningAttention", "BlockSparseAttention",
                     "LightningAttention", "BlockSparseAttention"]
    c = model.config
    assert (c.embed_scale, c.logit_divisor) == (12.0, 4.0)
    assert c.residual_scale == pytest.approx(1.4 / 4.0)
    # a linear layer's decay is of its PUBLISHED index (3 + local)
    np.testing.assert_allclose(
        np.exp(model.layers[1].mixer.log_slopes),
        decay_slopes(4, 4 / 15), rtol=1e-6)


# -- (a) the linear mixer alone --------------------------------------------------

def _linear(seed=0, dtype="float32"):
    m = LightningAttention(32, 4, 16, decay_slopes(4, 3 / 15), chunk=8,
                           dtype=dtype)
    rng = np.random.default_rng(seed)
    for name, p in m.named_parameters():
        if p._value.ndim == 2:
            p.set_value(jnp.asarray(rng.normal(
                0, p._value.shape[0] ** -0.5, p._value.shape), p._value.dtype))
    return m


def _feed(m, x, widths, start, rows=None, cache=None):
    """``x [B, T, hidden]`` through ``forward_cached`` in blocks of
    ``widths`` from column 0."""
    cache = cache or m.gen_cache(x.shape[0], 64)
    out, pos = [], 0
    for w in widths:
        y, cache = m.forward_cached(jnp.asarray(x[:, pos:pos + w]), cache,
                                    jnp.int32(pos), jnp.asarray(start),
                                    rows)
        out.append(np.asarray(y))
        pos += w
    return np.concatenate(out, 1), cache


@pytest.mark.parametrize("widths,start", [
    ((24,), (0, 0)),                    # one block, three scan chunks
    ((16, 8), (0, 0)),                  # the state carried across blocks
    ((16, 8), (5, 19)),                 # left padding inside / over a block
    ((5, 16, 3), (2, 7)),               # blocks that are no whole scan chunk
    ((8,) + (1,) * 16, (3, 0)),         # a chunk, then one-token updates
])
def test_the_chunked_scan_equals_the_token_by_token_update(widths, start):
    """Whatever the blocks, each row's outputs from its ``start`` on are
    those of one token at a time from a zero state."""
    m = _linear()
    x = np.random.default_rng(1).normal(0, 1, (2, 24, 32)).astype(np.float32)
    got, cache = _feed(m, x, widths, np.asarray(start, np.int32))
    want, wcache = _feed(m, x, (1,) * 24, np.asarray(start, np.int32))
    for b, s in enumerate(start):
        np.testing.assert_allclose(got[b, s:], want[b, s:], atol=2e-6)
    np.testing.assert_allclose(np.asarray(unwrap(cache.state)),
                               np.asarray(unwrap(wcache.state)), atol=1e-5)


def test_a_slots_previous_occupant_leaves_nothing_behind():
    """The state handed to a block counts iff ``pos > start``: a row whose
    request begins inside the block at column 16 starts from zeros whatever
    the slot's last occupant left, and a row outside ``write_rows`` keeps
    what it has."""
    m = _linear()
    x = np.random.default_rng(2).normal(0, 1, (2, 32, 32)).astype(np.float32)
    _, dirty = _feed(m, x[:, :16], (16,), np.zeros(2, np.int32))
    start = np.asarray([16, 18], np.int32)
    y0, c0 = m.forward_cached(jnp.asarray(x[:, 16:]), dirty, jnp.int32(16),
                              jnp.asarray(start))
    y1, c1 = m.forward_cached(jnp.asarray(x[:, 16:]), m.gen_cache(2, 64),
                              jnp.int32(16), jnp.asarray(start))
    np.testing.assert_array_equal(np.asarray(y0)[0], np.asarray(y1)[0])
    np.testing.assert_array_equal(np.asarray(y0)[1, 2:], np.asarray(y1)[1, 2:])
    np.testing.assert_array_equal(np.asarray(unwrap(c0.state)),
                                  np.asarray(unwrap(c1.state)))
    held = jnp.asarray([True, False])
    _, c2 = m.forward_cached(jnp.asarray(x[:, :1]), c0, jnp.int32(32),
                             jnp.asarray(start), held)
    np.testing.assert_array_equal(np.asarray(unwrap(c2.state))[1],
                                  np.asarray(unwrap(c0.state))[1])
    assert np.any(np.asarray(unwrap(c2.state))[0]
                  != np.asarray(unwrap(c0.state))[0])


def test_the_mixer_equals_the_reference_layer_and_a_rounded_state_does_not():
    """The mixer (chunks of 16 over 48 tokens) against the reference's
    layer, token by token in float32; the control rounds the reference's
    state to bfloat16 after every token and FAILS the same tolerance by
    two orders."""
    m = _linear()
    cfg = {"lightning_nh": 4, "lightning_head_dim": 16, "rms_norm_eps": 1e-6,
           "rope_theta": 10000, "r": 1.0}
    lw = {"op_norm": jnp.ones(32), "q": unwrap(m.q_proj),
          "k": unwrap(m.k_proj), "v": unwrap(m.v_proj),
          "gate": unwrap(m.gate_proj), "o": unwrap(m.o_proj),
          "q_norm": unwrap(m.q_norm.weight), "k_norm": unwrap(m.k_norm.weight),
          "o_norm": unwrap(m.norm)}
    x = np.random.default_rng(3).normal(0, 1, (1, 48, 32)).astype(np.float32)
    u = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6)
    got, _ = _feed(m, u, (16, 16, 16), np.zeros(1, np.int32))
    key = tuple(sorted(cfg.items()))
    slopes = jnp.asarray(decay_slopes(4, 3 / 15))

    def layer(state_dtype=None):
        return np.asarray(ref._lightning(
            jnp.asarray(x[0]), lw, slopes, cfg_key=key, precision="float32",
            state_dtype=state_dtype)) - x[0]
    np.testing.assert_allclose(got[0], layer(), atol=3e-6)
    assert np.abs(got[0] - layer(jnp.bfloat16)).max() > 3e-4


# -- (b) the sparse mixer alone --------------------------------------------------

SPARSE = attn_fn.BlockSparse(kernel=4, stride=2, block=8, top=4,
                             init_blocks=1, window=16, dense_len=32)


def _sparse(seed=0):
    m = BlockSparseAttention(32, 4, 2, 16, None, 1e-6, qk_norm=True,
                             gate=True, sparse=SPARSE)
    rng = np.random.default_rng(seed)
    for name, p in m.named_parameters():
        if p._value.ndim == 2:
            p.set_value(jnp.asarray(rng.normal(
                0, p._value.shape[0] ** -0.5, p._value.shape), p._value.dtype))
    return m


def _sparse_reference(m, x):
    """The reference's sparse layer over ``x [T, 32]`` (already normed: a
    unit ``op_norm`` over rows of unit rms), without the residual."""
    cfg = {"num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
           "rms_norm_eps": 1e-6, "r": 1.0}
    sp = {"kernel_size": 4, "kernel_stride": 2, "block_size": 8, "topk": 4,
          "init_blocks": 1, "window_size": 16, "dense_len": 32}
    lw = {"op_norm": jnp.ones(32), "q": unwrap(m.q_proj),
          "k": unwrap(m.k_proj), "v": unwrap(m.v_proj),
          "gate": unwrap(m.gate_proj), "o": unwrap(m.o_proj),
          "q_norm": unwrap(m.q_norm.weight), "k_norm": unwrap(m.k_norm.weight)}
    return np.asarray(ref._sparse(
        jnp.asarray(x), lw, cfg_key=tuple(sorted(cfg.items())),
        sparse_key=tuple(sorted(sp.items())), precision="float32")) - x


def _unit_rows(rng, shape):
    x = rng.normal(0, 1, shape).astype(np.float32)
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6)


def _two_rows(m, lens, P, steps, seed=4, C=256):
    """Rows of ``lens`` tokens left-padded to ``P`` columns (``start = P -
    len``), prefilled in chunks of 16 and then decoded ``steps`` single
    steps: (outputs ``[2, P + steps, 32]``, the rows' inputs, starts)."""
    rng = np.random.default_rng(seed)
    rows = [_unit_rows(rng, (n + steps, 32)) for n in lens]
    x = np.zeros((2, P + steps, 32), np.float32)
    for b, (n, r) in enumerate(zip(lens, rows)):
        x[b, P - n:] = r
    start = np.asarray([P - n for n in lens], np.int32)
    cache = m.gen_cache(2, C)
    got = []
    for pos in list(range(0, P, CHUNK)) + list(range(P, P + steps)):
        w = CHUNK if pos < P else 1
        y, cache = m.forward_cached(jnp.asarray(x[:, pos:pos + w]), cache,
                                    jnp.int32(pos), jnp.asarray(start))
        got.append(np.asarray(y))
    return np.concatenate(got, 1), rows, start


@pytest.mark.parametrize("lens", [(120, 93), (77, 41), (112, 40)])
def test_two_rows_at_different_starts_equal_the_reference(lens):
    """Two rows of one session whose block edges lie at different columns
    (``start`` 8 and 35, 51 and 87, ...), chunks then steps: every output
    of every valid position is the reference's for that row alone."""
    m = _sparse()
    got, rows, start = _two_rows(m, lens, 128, 6)
    for b, r in enumerate(rows):
        np.testing.assert_allclose(got[b, start[b]:],
                                   _sparse_reference(m, _pad8(r))[:len(r)],
                                   atol=2e-6)


def _pad8(r):
    out = np.zeros((-(-len(r) // 8) * 8, r.shape[1]), np.float32)
    out[:len(r)] = r
    return out


def test_blocks_cut_on_the_absolute_column_grid_fail(monkeypatch):
    """The control: the same two rows with blocks and windows counted from
    column 0 and not from each row's ``start``.  The row whose ``start`` is
    a multiple of the block (and of the stride) still agrees; the other
    FAILS the tolerance by three orders."""
    real_choose, real_keep = attn_fn.choose_blocks, attn_fn.block_keep

    def choose(q, pooled, pos, start, sp, rep):
        return real_choose(q, pooled, pos, jnp.zeros_like(start), sp, rep)

    def keep(member, start, block, C):
        return real_keep(member, jnp.zeros_like(start), block, C)
    monkeypatch.setattr(hybrid_conv, "choose_blocks", choose)
    monkeypatch.setattr(hybrid_conv, "block_keep", keep)
    m = _sparse()
    got, rows, start = _two_rows(m, (128, 93), 128, 4)
    err = [np.abs(got[b, start[b]:]
                  - _sparse_reference(m, _pad8(r))[:len(r)]).max()
           for b, r in enumerate(rows)]
    assert start[0] == 0 and err[0] < 2e-6
    assert err[1] > 2e-3


def _membership(m, x_row):
    """The reference's chosen blocks ``[KV, T, nb]`` for one row."""
    u = jnp.asarray(x_row)
    T = u.shape[0]
    ar = Arith("float32")
    q = ref.rms_norm(ar.einsum("th,hk->tk", u, unwrap(m.q_proj))
                     .reshape(T, 4, 16), unwrap(m.q_norm.weight), 1e-6)
    k = ref.rms_norm(ar.einsum("th,hk->tk", u, unwrap(m.k_proj))
                     .reshape(T, 2, 16), unwrap(m.k_norm.weight), 1e-6)
    sp = {"kernel_size": 4, "kernel_stride": 2, "block_size": 8, "topk": 4,
          "init_blocks": 1, "window_size": 16, "dense_len": 32,
          "positions": T}
    out = []
    for g in range(2):
        c = ref.pooled_keys(k[:, g], 4, 2)
        qg = jnp.moveaxis(q[:, 2 * g:2 * g + 2], 0, 1)
        out.append(np.asarray(ref.chosen_blocks(ar, qg, c, jnp.arange(T), sp)))
    return np.stack(out)


def test_the_chosen_block_sets_equal_the_references():
    """``choose_blocks`` over the pooled-key plane that the chunks wrote,
    for a row at ``start`` 11: the same sets of 4 blocks, query by query
    past ``dense_len``, as the reference's explicit pooling and choice."""
    m = _sparse()
    n, start, C = 104, 11, 128
    x = _unit_rows(np.random.default_rng(6), (n, 32))
    padded = np.zeros((1, 128, 32), np.float32)
    padded[0, start:start + n] = x
    cache = m.gen_cache(1, C)
    st = jnp.asarray([start], jnp.int32)
    for pos in range(0, 128, CHUNK):
        _, cache = m.forward_cached(jnp.asarray(padded[:, pos:pos + CHUNK]),
                                    cache, jnp.int32(pos), st)
    want = _membership(m, x)                               # [2, n, 13]
    q, _, _ = m._heads(jnp.asarray(padded), jnp.maximum(
        jnp.arange(128)[None] - start, 0))
    got = np.asarray(attn_fn.choose_blocks(
        q, unwrap(cache.pooled), jnp.int32(0), st, SPARSE, 2))[0]
    sparse = np.arange(n) + 1 > 32
    np.testing.assert_array_equal(
        got[:, start:start + n][:, sparse][..., :want.shape[-1]],
        want[:, sparse])
    assert (want[:, sparse].sum(-1) == 4).all()
    # past dense_len a query reads 4 blocks of 8: at most 32 of its columns
    assert want[:, sparse].any() and not want[:, sparse].all()


def test_the_step_masks_what_it_did_not_choose():
    """Rows past ``dense_len``, chunks then 8 single steps: a step's read
    over the span under the chosen blocks' mask gives the reference's
    outputs (which attend over the chosen blocks' tokens only).  What the
    step READS is every valid column of the span: the gather of the chosen
    blocks was measured slower on the chip and is not in the tree (PERF.md
    section 6, PR 44); what it ATTENDS to is counted by the slot loop
    (``sparse_blocks_selected``,
    ``test_slot_loop_equals_the_reference_and_counts_in_one_piece``)."""
    m = _sparse()
    got, rows, st = _two_rows(m, (90, 61), 96, 8)
    for b, r in enumerate(rows):
        np.testing.assert_allclose(got[b, st[b]:],
                                   _sparse_reference(m, _pad8(r))[:len(r)],
                                   atol=2e-6)


def test_the_pooled_plane_is_written_once_an_entry_and_under_each_rows_mask():
    """Entry ``col // 2`` of a row holds the mean of the 4 keys of the
    row's window that ends in that pair of columns, whether a chunk or a
    step completed it, and nothing where no window of the row ends."""
    m = _sparse()
    got, rows, start = _two_rows(m, (50, 33), 64, 6)
    # replay to read the planes
    cache = m.gen_cache(2, 256)
    x = np.zeros((2, 70, 32), np.float32)
    for b, r in enumerate(rows):
        x[b, start[b]:] = r
    for pos in list(range(0, 64, CHUNK)) + list(range(64, 70)):
        w = CHUNK if pos < 64 else 1
        _, cache = m.forward_cached(jnp.asarray(x[:, pos:pos + w]), cache,
                                    jnp.int32(pos), jnp.asarray(start))
    k, pooled = (np.asarray(unwrap(p)) for p in (cache.k, cache.pooled))
    for b, s in enumerate(start):
        n = 70 - s
        for j in range((n - 4) // 2 + 1):
            end = s + 2 * j + 3
            np.testing.assert_allclose(
                pooled[b, :, end // 2],
                k[b, :, end - 3:end + 1].mean(1), atol=1e-6)
        first = (s + 3) // 2
        assert not pooled[b, :, :first].any()
        assert not pooled[b, :, 35:].any()


# -- (c) the whole model ---------------------------------------------------------

def test_chunks_then_steps_equal_the_reference_for_unequal_starts(served):
    """Two rows of 109 and 58 prompt tokens, left-padded to 112 columns,
    prefilled in chunks of 16 and decoded 6 single steps through the
    cache: every logit of every valid position against the reference's
    full forward of that row."""
    cfg, model, view = served
    rng = np.random.default_rng(1)
    lens, P, steps = (109, 58), 112, 6
    rows = [rng.integers(0, VOCAB, n + steps).astype(np.int32) for n in lens]
    ids = np.zeros((2, P + steps), np.int32)
    for b, (n, r) in enumerate(zip(lens, rows)):
        ids[b, P - n:] = r
    start = jnp.asarray([P - n for n in lens], jnp.int32)
    types = [type(c) for c in model.init_cache(1, 1)]

    @jax.jit
    def feed(cache, block, pos):
        ring = [cls(*(Tensor(p) for p in c)) for cls, c in zip(types, cache)]
        logits, new = model.forward_cached(Tensor(block), ring, pos,
                                           Tensor(start))
        return unwrap(logits), [tuple(unwrap(p) for p in c) for c in new]

    cache = [tuple(unwrap(p) for p in c) for c in model.init_cache(2, 128)]
    got = []
    for pos in list(range(0, P, CHUNK)) + list(range(P, P + steps)):
        w = CHUNK if pos < P else 1
        out, cache = feed(cache, jnp.asarray(ids[:, pos:pos + w]),
                          jnp.int32(pos))
        got.append(np.asarray(out))
    got = np.concatenate(got, 1)
    for b, (n, r) in enumerate(zip(lens, rows)):
        np.testing.assert_allclose(got[b, P - n:],
                                   _reference_logits(cfg, view, r),
                                   atol=LOGIT_TOL)


def _serve(model, requests, slots=3, cache_len=192, seed=1):
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


def _served_logit_errors(cfg, view, prompts, tokens):
    """Per request, the widest gap by which a served token's reference
    logit lies below the reference's best, relative to max|logit|."""
    return [float(np.max(np.asarray(ref.token_gaps(cfg, view, p, t[0]))))
            for p, t in zip(prompts, tokens)]


def test_slot_loop_equals_the_reference_and_counts_in_one_piece(served):
    """Prefill by chunks + decoding through SlotLoop, rows joining,
    waiting and retiring (6 requests over 3 slots), equals the reference's
    full forward; and the counters, committed with ``steps`` in one piece,
    say what ran."""
    cfg, model, view = served
    prompts, tokens, st = _serve(model, REQUESTS)
    assert max(_served_logit_errors(cfg, view, prompts, tokens)) < GAP_TOL
    assert st["plane_kinds"] == ["kv+pooled_key", "ssm_state"]
    # this family's chunk still runs on a row cut out of the planes
    assert st["chunk_row"] == "sliced"
    # its sparse layers read under a mask of chosen blocks: no read form
    # of plain K/V planes to name (ISSUE 47)
    assert "step_read" not in st
    assert st["chunk_tokens"] == sum(n for n, _ in REQUESTS)
    assert st["ssm_rows_updated"] == st["emitted_tokens"]
    assert st["chunk_ssm_tokens"] == st["chunk_tokens"]
    assert st["kv_columns_valid"] > 0 and st["chunk_kv_columns_valid"] > 0
    # every step of every row is past dense_len 32: 4 blocks of the 6-16
    # its context has, a layer; the chunks' tokens choose past column 32
    # (a layer: the tiny model has 3 sparse layers)
    assert st["sparse_blocks_selected"] == 3 * 4 * st["emitted_tokens"]
    valid = sum(-(-(n + i + 1) // 8) for n, k in REQUESTS for i in range(k))
    assert st["sparse_blocks_valid"] == 3 * valid
    assert st["pooled_entries_scored"] == 3 * sum(
        (n + i + 1 - 4) // 2 + 1 for n, k in REQUESTS for i in range(k))
    chunk_valid = sum(-(-(t + 1) // 8) for n, _ in REQUESTS for t in range(n))
    assert st["chunk_sparse_blocks_valid"] == 3 * chunk_valid
    assert st["chunk_sparse_blocks_selected"] == 3 * sum(
        min(-(-(t + 1) // 8), 4) if t + 1 > 32 else -(-(t + 1) // 8)
        for n, _ in REQUESTS for t in range(n))
    assert st["chunk_pooled_entries_scored"] == 3 * sum(
        (t + 1 - 4) // 2 + 1 if t + 1 > 32 else 0
        for n, _ in REQUESTS for t in range(n))


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
                   for n in (70, 45, 101)]
        futs = [srv.submit_decode("m", [p], max_new_tokens=6)
                for p in prompts]
        tokens = [np.asarray(f.result(timeout=600)[0]) for f in futs]
        srv.stop(drain=False)
    finally:
        set_flags(before)
    assert max(_served_logit_errors(cfg, view, prompts, tokens)) < GAP_TOL


@pytest.mark.parametrize("dropped", ["scale_emb", "residual_scale",
                                     "logit_divisor", "gate"])
def test_a_dropped_scaling_fails(served, dropped):
    """One control a scaling: the same weights in a program built without
    the embedding's 12, without the residual branches' 1.4 / 4, without
    the head's divisor 4, or without the output gates; each FAILS the
    logit tolerance that the whole model passes by three orders or more."""
    cfg, _, view = served
    over = {"scale_emb": {"embed_scale": 1.0},
            "residual_scale": {"residual_scale": 1.0},
            "logit_divisor": {"logit_divisor": 1.0},
            "gate": {"sparse_gate": False}}[dropped]
    import dataclasses
    pc = dataclasses.replace(bench_models.program_config(cfg), **over)
    model = HybridConvDecoder(pc)
    model.eval()
    mapped = bench_models.to_program(ref.init_weights(cfg, 5))
    params = dict(model.named_parameters())
    for name, p in params.items():
        p.set_value(jnp.asarray(mapped[name], p._value.dtype))
    if dropped == "gate":
        for layer in model.layers:
            if isinstance(layer.mixer, LightningAttention):
                layer.mixer.gate_proj.set_value(
                    jnp.zeros_like(layer.mixer.gate_proj._value))
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
    assert err > 1e3 * LOGIT_TOL


def test_the_model_says_what_its_layers_keep(served):
    _, model, _ = served
    spec = model.cache_spec(192)
    assert [s["kind"] for s in spec] == [
        "kv+pooled_key", "ssm_state", "ssm_state", "kv+pooled_key",
        "ssm_state", "kv+pooled_key"]
    s = spec[0]
    assert (s["columns"], s["pooled_stride"], s["heads_per_lane_row"]) \
        == (192, 2, 1)
    assert s["select_blocks"]["top"] == 4 and s["select_blocks"]["block"] == 8
    cache = model.init_cache(3, 192, "bfloat16")
    assert [tuple(unwrap(p).shape) for p in cache[0]] == [
        (3, 2, 192, 16), (3, 2, 192, 16), (3, 2, 96, 16)]
    assert str(unwrap(cache[1].state).dtype) == "float32"
    assert unwrap(cache[1].state).shape == (3, 4, 16, 16)


@pytest.mark.parametrize("feature", ["prefix_cache", "session_store"])
def test_kv_movers_refuse_the_planes(served, feature):
    """The prefix cache refuses the pooled-key plane by what it is (an
    entry every ``pooled_stride`` columns is not a column a token) and the
    summed states; the session store refuses both by kind."""
    from paddle_tpu.serving.prefix_cache import PrefixCache
    from paddle_tpu.serving.sessions import SessionStore
    _, model, _ = served
    gen = Generator(model, max_len=192, seq_buckets=[192])
    kw = {"prefix_cache": PrefixCache(CHUNK, 1 << 20)} \
        if feature == "prefix_cache" else {"session_store": SessionStore(4)}
    with pytest.raises(InvalidArgumentError, match="pooled_key|ssm_state"):
        SlotLoop(gen, slots=2, cache_len=192, chunk=CHUNK, **kw)
