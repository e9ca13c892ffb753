"""The short-convolution / grouped-query MoE decoder (text/models/
hybrid_conv.py) at tiny widths on the CPU, float32, seeded weights: the
whole-sequence forward, chunks and steps through the cache, and the slot
loop against the plain reference's full forward (benchmark/reference/
lfm2.py, which imports nothing of the program); the state plane's rules
(a reused slot, a row that waits while others step, a ring restart);
grouped queries over the packed ring planes; the expert layer that holds
every expert.  Logits are compared, never sampled tokens.
"""
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

from benchmark.models import lfm2 as bench_models             # noqa: E402
from benchmark.reference import lfm2 as ref                    # noqa: E402
from paddle_tpu.framework.enforce import InvalidArgumentError  # noqa: E402
from paddle_tpu.framework.functional import _bound_state       # noqa: E402
from paddle_tpu.framework.tensor import Tensor, unwrap         # noqa: E402
from paddle_tpu.nn.functional import attention as A            # noqa: E402
from paddle_tpu.nn.layer.moe import DroplessMoE                # noqa: E402
from paddle_tpu.nn.layer.transformer import pack_heads         # noqa: E402
from paddle_tpu.serving.slots import SlotLoop                  # noqa: E402
from paddle_tpu.text.generation import Generator               # noqa: E402

# float32 on the CPU: the program (cache, chunks, packed planes, padded
# expert rows) and the reference (one pass, one head and one expert at a
# time) differ by summation order only
LOGIT_TOL = 2e-5
GAP_TOL = 1e-4
VOCAB = 96
# prompts of 5-17 tokens in chunks of 4 (2-5 chunks, none a multiple of
# 4 but one), 8 requests over 3 slots: every slot is reused, every row
# waits between its chunks while its neighbours step
REQUESTS = [(9, 6), (13, 8), (5, 4), (17, 8), (7, 8), (11, 5), (14, 7),
            (16, 6)]


def _tiny():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2-8b-a1b-pp2-serve.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "tests", "data",
                           "lfm2_tiny.json")) as f:
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


def test_tiny_covers_every_pairing(served):
    cfg, model, _ = served
    kinds = [(type(l.mixer).__name__, type(l.ffn).__name__)
             for l in model.layers]
    assert set(kinds) == {("ShortConv", "SwiGLU"), ("ShortConv", "DroplessMoE"),
                          ("GroupedQueryAttention", "DroplessMoE")}
    assert model.layers[2].mixer.rep == 2 and len(model.layers) == 6
    assert not any(n == "head" for n, _ in model.named_parameters())  # tied


def test_whole_sequence_forward_equals_the_reference(served):
    cfg, model, view = served
    ids = np.random.default_rng(0).integers(0, VOCAB, 19).astype(np.int32)
    got = np.asarray(unwrap(model(Tensor(jnp.asarray(ids[None])))))[0]
    np.testing.assert_allclose(got, _reference_logits(cfg, view, ids),
                               atol=LOGIT_TOL)


def test_chunks_then_steps_equal_the_reference_for_unequal_starts(served):
    """Two rows of 11 and 6 prompt tokens, left-padded to 12 columns (so
    ``start`` is 1 and 6), prefilled in chunks of 4 (shorter than either
    prompt; neither a multiple of it; row 1's first chunk is all padding
    and its second begins with it) and then decoded 5 single steps through
    the cache: every logit of every valid position against the reference's
    full forward of that row."""
    cfg, model, view = served
    rng = np.random.default_rng(1)
    lens, P, steps = (11, 6), 12, 5
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
    cache = [tuple(unwrap(p) for p in c) for c in model.init_cache(2, 32)]
    got = []
    for pos in range(0, P, 4):
        out, cache = feed(cache, jnp.asarray(ids[:, pos:pos + 4]),
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


def _serve(model, requests, slots=3, cache_len=64, one_by_one=False,
           read_at_once=False, in_one_admission=False):
    """``requests`` [(prompt or its length, new tokens)] through a SlotLoop,
    all at once (``in_one_admission``: all in the FIFO before the driver
    looks) or ``one_by_one`` (each sent once the one before it has
    resolved).  ``read_at_once``: every step is read before anything else
    is dispatched, as before the loop kept one in flight.  Returns
    (prompts, tokens, stats, the logits the step program handed back at
    every step)."""
    import contextlib
    gen = Generator(model, max_len=cache_len, seq_buckets=[cache_len])
    loop = SlotLoop(gen, slots=slots, cache_len=cache_len, chunk=4)
    if read_at_once:
        plain = loop._plain_step

        def plain_then_read(gen_slots, split):
            plain(gen_slots, split)
            loop._settle()
        loop._plain_step = plain_then_read
    step, seen = loop._step, []

    def recording(*args):
        out = step(*args)
        seen.append(np.asarray(out[1]))
        return out
    loop._step = recording
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, VOCAB, p).astype(np.int32)
               if isinstance(p, int) else p for p, _ in requests]
    if one_by_one:
        out = [np.asarray(loop.submit(p, k).result(timeout=300))
               for p, (_, k) in zip(prompts, requests)]
    else:
        with loop._cond if in_one_admission else contextlib.nullcontext():
            futs = [loop.submit(p, k) for p, (_, k) in zip(prompts, requests)]
        out = [np.asarray(f.result(timeout=300)) for f in futs]
    loop.close()
    return prompts, out, loop.stats(), seen


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
    assert st["plane_kinds"] == ["conv_state", "kv"]
    # this family's chunk still runs on a row cut out of the planes
    assert st["chunk_row"] == "sliced"
    # the grouped-query layers' step read: the XLA loops on the CPU
    assert st["step_read"] == "span"
    assert st["kv_heads_per_lane_row"] == 8            # 128 / head size 16
    moe_layers, k = 4, cfg["num_experts_per_tok"]
    fed = sum(n for n, _ in REQUESTS) + st["emitted_tokens"]
    assert st["chunk_tokens"] == sum(n for n, _ in REQUESTS)
    assert st["moe_assignments"] == fed * k * moe_layers
    # every expert is held here
    assert st["moe_assignments_held"] == st["moe_assignments"]
    assert st["chunk_moe_assignments_held"] == \
        st["chunk_tokens"] * k * moe_layers
    assert 1 <= st["moe_expert_tokens_max"] <= 4 * k
    assert sum(st["slot_steps_" + s] for s in (
        "emitting", "prefilling", "drain_blocked", "no_demand")) \
        == st["steps"] * 3
    # rows lay between two of their chunks while a step passed them by
    assert 0 < st["state_rows_held"] <= st["slot_steps_prefilling"]
    # the span counters run for a model whose COLUMN planes are all kv
    assert st["attn_blocks_total"] == st["steps"] \
        and 0 < st["attn_blocks_read"] <= st["attn_blocks_total"]


def test_one_step_in_flight_serves_what_reading_every_step_at_once_does(
        served):
    """The churn of the test above (rows held between two of their chunks
    while their neighbours step, every slot reused, rows that leave by
    count with their last token in flight) with the next step dispatched
    before the last is read, against the same schedule read step by step:
    the same tokens, the same logits out of every step, bit for bit, and
    the same counts of what ran."""
    _, model, _ = served
    _, tokens, st, seen = _serve(model, REQUESTS, in_one_admission=True)
    _, want, st0, seen0 = _serve(model, REQUESTS, in_one_admission=True,
                                 read_at_once=True)
    for got, ref_ in zip(tokens, want):
        np.testing.assert_array_equal(got, ref_)
    assert len(seen) == len(seen0) == st["steps"]
    for got, ref_ in zip(seen, seen0):
        np.testing.assert_array_equal(got, ref_)
    assert st["state_rows_held"] == st0["state_rows_held"] > 0
    assert st["slot_steps_retire_lag"] == 0
    same = [k for k in st0 if k not in ("phase_s", "phases_ms",
                                        "occupancy_ewma", "ttft_p50_ms",
                                        "ttft_p99_ms", "steps_read_ready")]
    assert {k: st[k] for k in same} == {k: st0[k] for k in same}


def _spy_counts(loop):
    """A blocking read of every dispatch's own counts, as it returns: the
    chunks' and the steps', each a list of int rows by
    ``decode_count_names``."""
    chunk, step, S = loop._chunk, loop._step, loop.S
    chunks, steps = [], []

    def chunk_read(*args):
        out = chunk(*args)
        chunks.append(np.asarray(out[2]).astype(np.int64))
        return out

    def step_read(*args):
        out = step(*args)
        steps.append(np.asarray(out[3])[S:].astype(np.int64))
        return out

    loop._chunk, loop._step = chunk_read, step_read
    return chunks, steps


def _assert_counts_equal_the_blocking_read(st, chunks, steps):
    chunks, steps = np.array(chunks), np.array(steps).reshape(-1, 3)
    assert st["chunks"] == len(chunks)
    assert st["chunk_moe_assignments"] == chunks[:, 0].sum()
    assert st["chunk_moe_assignments_held"] == chunks[:, 1].sum()
    assert st["moe_assignments"] == chunks[:, 0].sum() + steps[:, 0].sum()
    assert st["moe_expert_tokens_max"] == max(chunks[:, 2].max(),
                                              steps[:, 2].max(initial=0))


@pytest.mark.parametrize("requests,slots", [
    (REQUESTS, 3),
    # its final chunk, its activation and its only step in one iteration:
    # the row is gone before any further step
    ([(9, 1)], 1),
    # the short row retires (column 14) while the long one is between its
    # chunks 1 and 2: the next iteration dispatches chunk 2, finds no row
    # generating and steps nothing
    ([(5, 6), (13, 4)], 2),
], ids=["churn", "retires-at-its-first-step", "a-chunk-and-no-step"])
def test_chunk_counts_read_a_step_later_equal_a_blocking_read(
        served, requests, slots):
    """The loop reads a chunk's counts behind the NEXT step's tokens, not
    where it dispatched the chunk; what it commits equals what a read at
    the dispatch gives, over the whole window."""
    _, model, _ = served
    gen = Generator(model, max_len=64, seq_buckets=[64])
    loop = SlotLoop(gen, slots=slots, cache_len=64, chunk=4)
    chunks, steps = _spy_counts(loop)
    rng = np.random.default_rng(3)
    try:
        futs = [loop.submit(rng.integers(0, VOCAB, n).astype(np.int32), k)
                for n, k in requests]
        for f in futs:
            f.result(timeout=300)
        # every chunk was followed by a step, and a step's commit comes
        # before its rows' replies: nothing is left to read
        assert loop._chunk_counts == []
        replied = dict(loop.counters)
    finally:
        loop.close()
    st = loop.stats()       # the driver's last phases are committed now
    assert {k: st[k] for k in replied} == replied   # its end added nothing
    _assert_counts_equal_the_blocking_read(st, chunks, steps)
    assert st["chunk_tokens"] == sum(n for n, _ in requests)
    assert st["rows_activated"] == len(requests)
    assert st["logits_bytes_via_host"] == 0
    assert st["phase_s"]["chunk_fetch"] > 0
    if len(requests) == 2:
        # the iteration without a step did happen: a step for every token
        # of the longer answer and the shorter's, none for chunk 2
        assert st["steps"] == 6 + 4


def test_a_loop_that_closes_right_after_a_chunk_counts_it(served):
    """A chunk that no step follows (its row left before it activated,
    as a parked session's does): the driver reads its counts when it
    ends, so that the window still holds every chunk it ran.  Driven by
    hand, on the test's thread: admission, two of three chunks, the row
    taken away, the closed loop's last pass."""
    from paddle_tpu.serving.slots import SlotRequest
    _, model, _ = served
    gen = Generator(model, max_len=64, seq_buckets=[64])
    loop = SlotLoop(gen, slots=2, cache_len=64, chunk=4)
    chunks, _ = _spy_counts(loop)
    prompt = np.random.default_rng(4).integers(0, VOCAB, 11).astype(np.int32)
    loop._pending.append(SlotRequest(prompt=prompt, max_new=3))
    assert loop._admit() is False and loop._slots[0].act == 12
    loop.pos = 11                  # chunks 0 and 1 are due, chunk 2 is not
    loop._dispatch_chunks()
    assert len(chunks) == 2 and len(loop._chunk_counts) == 2
    assert loop.stats()["chunk_moe_assignments"] == 0       # not yet read
    loop._vacate(0)
    loop._closed = True
    loop._drive()                  # nothing live: it ends, and reads them
    st = loop.stats()
    assert loop._chunk_counts == []
    assert st["chunk_moe_assignments"] == np.array(chunks)[:, 0].sum() > 0
    assert st["moe_expert_tokens_max"] == np.array(chunks)[:, 2].max()
    assert st["chunk_tokens"] == 11 - 4      # the columns of two chunks,
    #                                          less one of left padding


def test_a_reused_slot_gives_the_second_request_a_fresh_start(served):
    """One slot: request A, then request B in the slot A left (its conv
    states and its K/V columns still lie there).  B's logits at every one
    of its steps equal, bit for bit, those of B alone in a fresh loop: the
    leftovers lie before B's ``start`` and count for nothing."""
    _, model, _ = served
    (_, b), both, _, seen = _serve(model, [(13, 7), (10, 6)], slots=1,
                                   one_by_one=True)
    _, alone, _, fresh = _serve(model, [(b, 6)], slots=1)
    np.testing.assert_array_equal(both[1], alone[0])
    assert len(seen) == 7 + 6 and len(fresh) == 6
    for got, want in zip(seen[7:], fresh):
        np.testing.assert_array_equal(got, want)


_PROGRAMS = {}


def _prefill_beside_a_live_row(model, steps_between):
    """Row 1 prefills a 14-token prompt in 4 chunks of 4 on the slot
    loop's own schedule (admitted at column 20, so ``act = 24``; chunk
    ``k`` goes out once the frontier has passed ``act - 4 + k``), through
    the Generator's own chunk and step programs.  With ``steps_between``
    row 0 decodes meanwhile, so three steps pass row 1 while it holds the
    state its last chunk left.  Returns every chunk's logits."""
    gen = Generator(model, max_len=64, seq_buckets=[64])
    masked = type(model).cached_forward_takes_rows
    if "chunk" not in _PROGRAMS:
        _PROGRAMS["chunk"] = jax.jit(gen._build_chunk(2, 4, 64))
    if steps_between and masked not in _PROGRAMS:
        _PROGRAMS[masked] = jax.jit(gen._build_step(2, 64, -1))
    chunk, step = _PROGRAMS["chunk"], _PROGRAMS.get(masked)
    state, cache = gen._state_args(), gen.init_slot_cache(2, 64)
    prompt = np.random.default_rng(0).integers(1, VOCAB, 14).astype(np.int32)
    padded = np.concatenate([np.zeros(2, np.int32), prompt])
    logits = jnp.zeros((2, VOCAB), jnp.float32)
    start = np.array([0, 64], np.int32)       # row 1 is not generating
    done, live, outs = np.array([False, True]), np.array([True, False]), []
    for k in range(5):
        if k:
            cache, out = chunk(*state, cache, padded[None, 4 * k - 4:4 * k],
                               np.array([24 - 14], np.int32), np.int32(1),
                               np.int32(8 + 4 * (k - 1)))[:2]
            outs.append(np.asarray(out))
        if k < 4 and steps_between:
            cache, logits, _, _ = step(*state, cache, logits, start, done,
                                       live, np.zeros(2, bool), np.int32(20 + k))
    return np.stack(outs)


@pytest.mark.parametrize("masked", [True, False])
def test_a_row_that_waits_keeps_its_state(served, monkeypatch, masked):
    """The dead-column rule has no meaning for a state that a feed
    overwrites in place: a row that waits between two of its chunks while
    others step must find its state as its last chunk left it.  The step
    hands the model its live rows for that; with them ignored the waiting
    row's next chunk convolves its neighbour's steps."""
    _, model, _ = served
    alone = _prefill_beside_a_live_row(model, steps_between=False)
    monkeypatch.setattr(type(model), "cached_forward_takes_rows", masked)
    beside = _prefill_beside_a_live_row(model, steps_between=True)
    if masked:
        np.testing.assert_array_equal(beside, alone)
    else:
        assert np.abs(beside - alone).max() > 1e-3


def test_a_ring_restart_needs_no_reset_of_the_state(served):
    """A ring of 32 columns: the loop drains and restarts its session at
    column 0 several times, every slot's state still holding what its last
    occupant left at columns ABOVE the new ones; the answers equal the
    reference's all the same (an entry counts iff its column is at or
    after the row's ``start``, and the restarted row's first chunk starts
    at its ``start`` or below it)."""
    cfg, model, view = served
    prompts, tokens, st, _ = _serve(model, REQUESTS, slots=2, cache_len=32)
    assert st["session_resets"] >= 2
    assert _widest_gap(cfg, view, prompts, tokens) < GAP_TOL


# -- grouped queries over the packed ring planes ------------------------------

def _per_head(q, k, v, valid, rep):
    """Plain attention, one query head at a time; ``k``, ``v`` ``[B, KV, C,
    d]`` unpacked, ``valid [B, T, C]``."""
    out = []
    for h in range(q.shape[1]):
        s = jnp.einsum("btd,bcd->btc", q[:, h], k[:, h // rep],
                       precision="highest") / math.sqrt(q.shape[-1])
        p = jax.nn.softmax(jnp.where(valid, s, -1e30), -1)
        out.append(jnp.einsum("btc,bcd->btd", p, v[:, h // rep],
                              precision="highest"))
    return jnp.stack(out, 1)


def _planes(key, B, KV, C, d):
    k, v = jax.random.normal(key, (2, B, KV, C, d))
    g = 128 // d
    return k, v, jnp.asarray(pack_heads(k, g)), jnp.asarray(pack_heads(v, g))


@pytest.mark.parametrize("heads,kv,d", [(32, 8, 64), (8, 2, 16), (6, 3, 32)])
def test_grouped_queries_read_their_own_cached_head(heads, kv, d):
    """4, 4 and 2 query heads a cached head; two, eight and four cached
    heads a lane row (with padding heads where they do not fill one): the
    step's one query a row over ``[start, end)`` against plain per-head
    attention."""
    rep, B, C = heads // kv, 3, 256
    k, v, kp, vp = _planes(jax.random.key(0), B, kv, C, d)
    start = jnp.asarray([3, 130, 40], jnp.int32)
    end = jnp.asarray([200, 200, 200], jnp.int32)
    q = jax.random.normal(jax.random.key(1), (B, heads, 1, d))
    col = jnp.arange(C)
    valid = ((col[None] >= start[:, None]) & (col[None] < end[:, None]))[:, None]
    got = A.span_attention(q, kp, vp, start, jnp.int32(199), rep=rep)
    np.testing.assert_allclose(got, _per_head(q, k, v, valid, rep), atol=2e-6)


@pytest.mark.parametrize("C,first", [(256, 196), (200, 150), (1100, 590)])
def test_a_block_of_queries_reads_the_live_span_only(C, first, monkeypatch):
    """``span_attention`` for a block of 5 queries: column blocks of 64
    from the lowest ``start`` to the last query's column with a running
    softmax, against plain per-head attention; a ring that is no multiple
    of the block; and the one-query form it hands a step to."""
    monkeypatch.setattr(A, "SPAN_BLOCK", 64)
    heads, kv, d, B, T = 8, 2, 64, 3, 5
    k, v, kp, vp = _planes(jax.random.key(9), B, kv, C, d)
    start = jnp.asarray([3, first - 20, first + 2], jnp.int32)
    q = jax.random.normal(jax.random.key(10), (B, heads, T, d))
    col, rows = jnp.arange(C), first + jnp.arange(T)
    valid = (col[None, None] >= start[:, None, None]) \
        & (col[None, None] <= rows[None, :, None])
    got = A.span_attention(q, kp, vp, start, jnp.int32(first), rep=4)
    want = _per_head(q, k, v, valid, 4)
    # row 2's first two queries lie before its start: nobody uses them
    live = np.asarray(valid.any(-1))[:, None, :, None]
    np.testing.assert_allclose(np.where(live, got, 0), np.where(live, want, 0),
                               atol=2e-6)
    one = A.span_attention(q[:, :, :1], kp, vp, start, jnp.int32(first), rep=4)
    np.testing.assert_allclose(np.where(live[:, :, :1], one, 0),
                               np.where(live[:, :, :1], want[:, :, :1], 0),
                               atol=2e-6)


def _spread_queries_pr30(q, groups, lanes):
    """``_spread_queries`` / ``_own_lanes`` as they stood before grouped
    queries (commit 1484fa4), word for word."""
    b, n, t, hd = q.shape
    g = lanes // hd
    qg = jnp.pad(q, ((0, 0), (0, groups * g - n), (0, 0), (0, 0))) \
        .reshape(b, groups, g, t, hd)
    own = (jnp.arange(lanes)[None, :] // hd
           == jnp.arange(g)[:, None])[None, None, :, None, :]
    return jnp.where(own, jnp.tile(qg, (1, 1, 1, 1, g)),
                     jnp.zeros((), q.dtype)), own


def _own_lanes_pr30(out, own, n, hd):
    b, groups, g, t, _ = out.shape
    out = jnp.where(own, out, jnp.zeros((), out.dtype)).sum(axis=2)
    return out.reshape(b, groups, t, g, hd).transpose(0, 1, 3, 2, 4) \
        .reshape(b, groups * g, t, hd)[:, :n]


def test_one_query_a_head_is_bit_equal_to_what_it_was():
    """25 heads of 64 over 13 lane rows (the served GPT's geometry): with
    one query a cached head the spread queries and the kept lanes are
    what they were, and ``span_attention`` is the GPT step's own read."""
    q = jax.random.normal(jax.random.key(3), (2, 25, 1, 64))
    qs, own = A._spread_queries(q, 13, 128)
    qs0, own0 = _spread_queries_pr30(q, 13, 128)
    np.testing.assert_array_equal(qs, qs0)
    np.testing.assert_array_equal(own, own0)
    out = jax.random.normal(jax.random.key(4), qs.shape)
    np.testing.assert_array_equal(A._own_lanes(out, own, 25, 64),
                                  _own_lanes_pr30(out, own0, 25, 64))
    k, v, kp, vp = _planes(jax.random.key(5), 2, 25, 256, 64)
    start, end = jnp.asarray([0, 100]), jnp.asarray([180, 180])
    np.testing.assert_array_equal(
        unwrap(A.cached_attention(q, kp, vp, window=(start, end))),
        A.span_attention(q, kp, vp, start, jnp.int32(179)))


# -- the expert layer that holds every expert ---------------------------------

def _moe_fn(layer):
    @jax.jit
    def f(params, u):
        with _bound_state(layer, params, {}):
            return layer(u), layer.last_counts
    return f


def _dense_moe(w, u, k, eps, bias=True):
    """Every expert computed densely, weighted by the routing written out:
    sigmoid scores, top-k of score + bias, ``s / (sum + eps)``."""
    s = jax.nn.sigmoid(u @ w["router"])
    _, ids = jax.lax.top_k(s + (w["router_bias"] if bias else 0.0), k)
    chosen = jnp.take_along_axis(s, ids, 1)
    wt = chosen / (chosen.sum(-1, keepdims=True) + eps)
    out = 0.0
    for e in range(w["router"].shape[1]):
        g, v, d = (w[n][e] for n in ("w_gate", "w_up", "w_down"))
        we = jnp.where(ids == e, wt, 0.0).sum(-1, keepdims=True)
        out = out + we * ((jax.nn.silu(u @ g) * (u @ v)) @ d)
    return out, ids


def test_the_bias_chooses_only_and_the_denominator_is_an_argument():
    """``held=None`` holds all 8 experts.  A bias that lifts experts 6 and
    7 over every score changes WHICH experts are chosen and nothing of
    their weights (those are the bare scores over ``sum + 1e-6``); without
    ``norm_eps`` the denominator is the sum alone, as dots3 keeps it."""
    layer = DroplessMoE(32, 16, 8, 2, norm_eps=1e-6)
    w = {k: unwrap(v) for k, v in layer.named_parameters()}
    w["router"] = w["router"] * 40.0          # scores well apart
    w["router_bias"] = jnp.zeros(8).at[jnp.array([6, 7])].set(5.0)
    u = jax.random.normal(jax.random.key(6), (24, 32))
    want, ids = _dense_moe(w, u, 2, 1e-6)
    assert set(np.asarray(ids).ravel()) == {6, 7}
    got, counts = _moe_fn(layer)(w, u)
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert int(counts[0]) == int(counts[1]) == 48         # all held
    unbiased, _ = _dense_moe(w, u, 2, 1e-6, bias=False)
    assert float(jnp.abs(unbiased - want).max()) > 1e-3
    # logits of -20, scores of 2e-9 each: there the 1e-6 in the denominator
    # decides the weights (2e-3 each where the bare sum gives 0.5 each)
    faint = dict(w, router=jnp.full((32, 8), -0.625), router_bias=jnp.zeros(8))
    ones = jnp.ones((24, 32))
    outs = []
    for moe, eps in ((layer, 1e-6), (DroplessMoE(32, 16, 8, 2), 0.0)):
        outs.append(_moe_fn(moe)(faint, ones)[0])
        np.testing.assert_allclose(outs[-1], _dense_moe(faint, ones, 2, eps)[0],
                                   rtol=1e-4, atol=1e-9)
    assert float(jnp.abs(outs[1]).max()) > 100 * float(jnp.abs(outs[0]).max())
    assert layer.norm_eps == 1e-6 and DroplessMoE(32, 16, 8, 2).norm_eps is None


@pytest.mark.parametrize("tokens,skewed", [(16, False), (128, False),
                                           (128, True), (4096, False)])
def test_padded_and_grouped_products_agree_with_every_expert_held(tokens,
                                                                   skewed):
    """32 experts, all held, 4 a token.  A 128-row step's 512 assignments
    pad each expert to 64 rows and take the one batched product; a router
    that sends every token to the same 4 experts passes the padding and
    takes the grouped products; 4,096 tokens pass ``PADDED_ROWS_MAX`` and
    take them too.  The same numbers every way, against every expert
    computed densely; nothing is dropped."""
    layer = DroplessMoE(32, 16, 32, 4, norm_eps=1e-6)
    w = {k: unwrap(v) for k, v in layer.named_parameters()}
    w["router"] = w["router"] * 40.0
    if skewed:
        w["router"] = jnp.zeros((32, 32))
        w["router_bias"] = jnp.zeros(32).at[jnp.arange(4)].set(5.0)
    u = jax.random.normal(jax.random.key(7), (tokens, 32))
    want, ids = _dense_moe(w, u, 4, 1e-6)
    got, counts = _moe_fn(layer)(w, u)
    np.testing.assert_allclose(got, want, atol=3e-6)
    made, held, most = (int(c) for c in counts)
    assert made == held == 4 * tokens
    assert most == int(np.bincount(np.asarray(ids).ravel(), minlength=32).max())
    even = -(-4 * tokens // 32)
    cap = max(8, -(-min(4 * even, even + 64) // 8) * 8)
    if tokens == 4096:
        assert cap > DroplessMoE.PADDED_ROWS_MAX
    else:
        assert cap <= DroplessMoE.PADDED_ROWS_MAX and (most > cap) == skewed


def test_expert_shares_of_the_32_add_up_to_the_whole_layer():
    """The guide's test, kept for the next cut: the parts that the shares
    ``[0, 8) .. [24, 32)`` of the 32 experts give add up to what the layer
    that holds them all gives (no shared expert to count once).  Share
    ``i`` is the layer that holds experts ``[0, 8)`` of a router whose
    columns are rolled by ``8 i``: one program for every share."""
    whole = DroplessMoE(32, 16, 32, 4, norm_eps=1e-6)
    whole.router_bias.set_value(jnp.linspace(-0.1, 0.1, 32))
    w = {k: unwrap(v) for k, v in whole.named_parameters()}
    w["router"] = w["router"] * 40.0
    u = jax.random.normal(jax.random.key(8), (2, 11, 32))
    part = _moe_fn(DroplessMoE(32, 16, 32, 4, held=(0, 8), norm_eps=1e-6))
    parts = [part({"router": jnp.roll(w["router"], -8 * i, 1),
                   "router_bias": jnp.roll(w["router_bias"], -8 * i),
                   **{k: w[k][8 * i:8 * i + 8]
                      for k in ("w_gate", "w_up", "w_down")}}, u)
             for i in range(4)]
    uncut, made = _moe_fn(whole)(w, u)
    np.testing.assert_allclose(sum(p for p, _ in parts), uncut, atol=1e-6)
    assert float(jnp.abs(parts[0][0] - uncut).max()) > 1e-4
    assert int(made[0]) == int(made[1]) == 2 * 11 * 4
    assert sum(int(c[1]) for _, c in parts) == int(made[1])


# -- the seam: what the model says it keeps ------------------------------------

def test_the_model_says_what_its_layers_keep(served):
    _, model, _ = served
    gen = Generator(model, max_len=64, seq_buckets=[64])
    spec = gen.cache_spec(64)
    assert [(s["kind"], s["columns"]) for s in spec] == [
        ("conv_state", 0), ("conv_state", 0), ("kv", 64),
        ("conv_state", 0), ("conv_state", 0), ("kv", 64)]
    assert gen.plane_kinds() == ["conv_state", "kv"]
    # the packing is that of the first layer that keeps columns
    assert gen.kv_heads_per_lane_row() == 8
    assert gen.decode_count_names() == (
        "moe_assignments", "moe_assignments_held", "moe_expert_tokens_max")
    planes = gen.slot_cache_avals_all(3, 64)
    assert tuple(planes[0][0].shape) == (3, 1, 2, 64)        # the state
    assert tuple(planes[2][0].shape) == (3, 1, 64, 128)      # 2 KV heads of 16


@pytest.mark.parametrize("feature", ["prefix_cache", "session_store"])
def test_kv_movers_refuse_a_state_without_columns(served, feature):
    _, model, _ = served
    gen = Generator(model, max_len=64, seq_buckets=[64])
    with pytest.raises(InvalidArgumentError, match="conv_state"):
        SlotLoop(gen, slots=2, cache_len=64, chunk=4, **{feature: object()})


def test_handoff_refuses_a_state_without_columns():
    from paddle_tpu.serving.cluster import handoff
    with pytest.raises(InvalidArgumentError, match="conv_state"):
        handoff.require_kv_planes(["conv_state", "kv"])
