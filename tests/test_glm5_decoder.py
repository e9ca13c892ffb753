"""GLM-5's language model (``glm_moe_dsa``: every layer a full latent layer
WITH the learned column selector, values wider than the keys' content part,
no gate, no latent rescale, one dense layer; text/models/latent_moe.py) at
tiny widths on the CPU, seeded weights: the whole forward and the slot
loop's chunks and steps against the plain reference
(benchmark/reference/glm_moe_dsa.py, which imports nothing of the program),
a whole layer out of its shares, the prefix cache over latent AND
selector-key planes (a row served from restored blocks selects what the
whole prefill selects, a constructed tie included), and the names and the
counter this configuration added.  The cases it shares with Kimi-K2.5's
(the expert shares, a hit against the plain prefill through the loop) run
for both families in tests/test_kimi_decoder.py, whose helpers these are.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmark.counts import glm_moe_dsa as counts              # noqa: E402
from benchmark.reference import glm_moe_dsa as ref              # noqa: E402
from paddle_tpu.framework.enforce import InvalidArgumentError   # noqa: E402
from paddle_tpu.framework.tensor import Tensor, unwrap          # noqa: E402
from paddle_tpu.nn.layer import latent_attention                # noqa: E402
from paddle_tpu.profiler import ledger                          # noqa: E402
from paddle_tpu.serving.slots import SlotLoop                   # noqa: E402
from paddle_tpu.text.generation import (Generator,              # noqa: E402
                                        require_prefix_planes)
from test_kimi_decoder import (GAP_TOL, GAP_TOL_BF16, REQUESTS,  # noqa: E402
                               _build, _cpu_bf16_products, _load, _serve,
                               _tiny, _widest_gap, share_parts)


@pytest.fixture(scope="module")
def served():
    """ONE tiny float32 model with the reference's seeded weights, and its
    view of them for the reference."""
    cfg = _tiny("glm5")
    return (cfg,) + _build(cfg)


# -- (a) the program against the reference -------------------------------------

def test_the_model_is_what_the_configuration_says(served):
    """Every layer full with a selector, two planes a layer as long as the
    session, values wider than the keys' content part, no gate, no rescale,
    one dense layer: all from the configuration's keys."""
    cfg, model, _ = served
    gen = Generator(model, max_len=64, seq_buckets=[64])
    spec = gen.cache_spec(64)
    assert [s["kind"] for s in spec] == ["latent+selector_key"] * 3
    assert all(s["columns"] == 64 and not s["wraps"] and s["window"] is None
               and s["select_top"] == cfg["index_topk"] == 6 for s in spec)
    planes = gen.slot_cache_avals_all(3, 64)
    # latent 12 + rotary key 8 = 20 numbers, padded to the lane count, and
    # the selector's key of 16 beside it
    assert [[tuple(p.shape) for p in c] for c in planes] \
        == [[(3, 1, 64, 128), (3, 1, 64, 16)]] * 3
    attn = model.layers[2].attn
    assert attn.selects and attn.gate is None and attn.s_q == attn.s_kv == 1.0
    assert attn.inv is None and attn.base == 100.0
    assert tuple(unwrap(attn.w_uk).shape) == (4, 12, 8)
    assert tuple(unwrap(attn.w_uv).shape) == (4, 12, 12)        # v > nope
    assert tuple(unwrap(attn.o_proj).shape) == (4 * 12, 32)
    from paddle_tpu.nn.layer.moe import DroplessMoE
    assert [isinstance(l.ffn, DroplessMoE) for l in model.layers] \
        == [False, True, True]


def test_whole_forward_equals_the_reference(served):
    """``LatentMoEDecoder.forward`` (no cache, one pass) against the
    reference's logits at every position of a 40-token sequence: the
    selector binds from the 7th token on, in all three layers."""
    cfg, model, view = served
    ids = np.random.default_rng(3).integers(0, 96, 40).astype(np.int32)
    mine = np.asarray(unwrap(model(Tensor(jnp.asarray(ids)[None]))))[0]
    want, _ = ref._logits_at(cfg, view, jnp.asarray(ids),
                             jnp.arange(40, dtype=jnp.int32), "float32")
    np.testing.assert_allclose(mine, np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("dtype,tol", [("float32", GAP_TOL),
                                       ("bfloat16", GAP_TOL_BF16)])
def test_slot_loop_equals_the_reference(served, dtype, tol, monkeypatch):
    """Prefill by chunks + decoding through SlotLoop, rows joining and
    retiring (8 requests over 3 slots), equals the reference's full
    forward; the loop's counters say what ran: two planes a layer, six of
    a token's causal columns read once it has more."""
    cfg, model, view = served
    if dtype != "float32":
        _cpu_bf16_products(monkeypatch)
        cfg = _tiny("glm5", dtype=dtype)
        model, view = _build(cfg)
    prompts, tokens, st = _serve(model, REQUESTS)
    if dtype == "float32":
        assert _widest_gap(cfg, view, prompts, tokens) < tol
    else:
        # the selection is not continuous: where the sixth column chosen
        # and the first left out score closer than bfloat16 operands
        # resolve, the program takes either, and one column of six moves
        # a logit by tenths (with index_topk out of reach the same run
        # reads 0.033 at most).  Held to the limit: the typical token, and
        # four tokens in five (7 of 52 lie over it, 0.11-0.29)
        gaps = np.concatenate([np.asarray(ref.served_gaps(cfg, view, p, t))
                               for p, t in zip(prompts, tokens)])
        assert np.median(gaps) < 1e-3 and (gaps < tol).mean() > 0.8
    assert st["plane_kinds"] == ["latent+selector_key"]
    # this family's chunk still runs on a row cut out of the planes
    assert st["chunk_row"] == "sliced"
    assert st["latent_form"] == {"step": "absorbed", "chunk": "absorbed"}
    n = [p.size for p in prompts]
    assert st["chunk_tokens"] == sum(n)
    # three layers, each reading min(context, 6) of a token's context
    assert st["chunk_attn_columns_valid"] == 3 * sum(
        k * (k + 1) // 2 for k in n)
    assert st["chunk_attn_columns_selected"] == 3 * sum(
        sum(min(c, 6) for c in range(1, k + 1)) for k in n)
    assert st["attn_columns_selected"] < 0.7 * st["attn_columns_valid"]
    assert "window_wraps" not in st and "kv_columns_valid" not in st


def test_wide_chunks_equal_the_reference():
    """Prompts of 5-150 tokens prefilled in chunks of 64 (per head: over
    the rule's threshold of 60 queries at these widths, as the published
    widths' 512-token chunk is over theirs of 398) and decoded by absorbed
    steps over the rows and the selector keys those chunks wrote."""
    cfg = _tiny("glm5")
    cfg["serve"]["prefill_chunk"] = 64
    model, view = _build(cfg)
    requests = [(70, 6), (130, 8), (5, 4), (150, 8), (64, 5)]
    prompts, tokens, st = _serve(model, requests, chunk=64, columns=256)
    assert _widest_gap(cfg, view, prompts, tokens) < GAP_TOL
    assert st["latent_form"] == {"step": "absorbed", "chunk": "per_head"}
    at_published = latent_attention.LatentAttention(
        64, 2, 192, 64, 256, 16, 512, 1e6, index_heads=2, index_dim=128,
        index_topk=2048, gate=False, rescale=False)
    assert [at_published.cached_form(T) for T in (1, 398, 399, 512)] \
        == ["absorbed", "absorbed", "per_head", "per_head"]


# -- (b) a whole layer out of its shares ---------------------------------------

@pytest.mark.parametrize("shares", [8, 2])
def test_a_whole_layer_adds_up_from_its_shares(shares):
    """One expert layer of the uncut 8-expert reference, attention and
    all: the attention and the shared expert, which every chip computes
    alike, counted ONCE, plus the routed parts that all the shares give,
    is the layer (float32: to rounding of another summation order)."""
    cfg = _tiny("glm5")
    E = cfg["n_routed_experts_published"]
    uncut = dict(cfg, experts_held=[0, E], n_routed_experts=E)
    whole = ref._layer_weights(ref.init_weights(uncut, 7), 1)
    key, inv = ref._cfg_key(uncut), ref.rotary_frequencies(uncut)
    x = jax.random.normal(jax.random.key(1), (24, cfg["hidden_size"]))
    h = ref._attention(x, whole, inv, cfg_key=key, precision="float32")
    want, _ = ref._moe_ffn(h, whole, jnp.zeros(24, bool), cfg_key=key,
                           precision="float32")
    u = ref.rms_norm(h, whole["post_norm"], cfg["rms_norm_eps"])
    n = E // shares
    each = [share_parts(cfg, ref, whole, u, i * n, (i + 1) * n)
            for i in range(shares)]
    got = h + sum(p[0] for p in each) + each[0][1]
    np.testing.assert_allclose(got, want,
                               atol=1e-5 * float(jnp.abs(want).max()))
    assert float(jnp.abs(h + each[0][0] + each[0][1] - want).max()) > 1e-3


# -- (c) restored blocks select what the whole prefill selects ------------------

TIED = (1, 2, 4, 6, 7, 9, 11, 13, 14, 15)     # tokens that share ONE input


def _selector_layer(model):
    """Layer 1's attention with the rotated features of the selector's
    queries zeroed: a column's score then depends on its token's input
    alone, not on its position, so tokens with one input TIE exactly,
    wherever their columns lie."""
    attn = model.layers[1].attn
    w = np.array(unwrap(attn.idx_q)).reshape(attn.rq, attn.J, attn.D)
    w[:, :, :attn.dr] = 0.0
    return attn, jnp.asarray(w.reshape(attn.rq, attn.J * attn.D))


def _prefill(attn, x, blocks, start, planes):
    """``x [1, T, hidden]`` appended block by block from column ``start``,
    eagerly (so that a spy on ``select_columns`` sees arrays)."""
    outs, pos = [], start
    for n in blocks:
        out, planes = attn.forward_cached(
            x[:, pos - start:pos - start + n], planes, jnp.int32(pos),
            jnp.asarray([start], jnp.int32))
        outs.append(np.asarray(out))
        pos += n
    return np.concatenate(outs, 1), planes


@pytest.mark.parametrize("shift", [8, 5], ids=["equal_alignment",
                                               "another_alignment"])
def test_restored_blocks_select_the_same_columns(served, shift, monkeypatch):
    """A 20-token row prefilled whole from column 0 against the same row
    whose first 16 columns (4 blocks of 4: latent rows AND selector keys)
    are copied into fresh planes at column ``shift`` and whose last chunk
    is then prefilled there: the last chunk's queries select the same
    tokens' columns and give the same outputs, to the bit where the two
    ``start`` agree modulo the 8-column ``attn_block``.  Ten of the first
    sixteen tokens share one input, so their selector scores TIE exactly
    and the six-column selection's threshold falls among them: the lower
    column wins, in both layouts, though the tied columns lie in other
    blocks of the score loop (tokens 1 .. 7 | 9 .. 15 from column 0, 6 ..
    7 | 9 .. 15 | 16 .. 20 from column 5)."""
    _, model, _ = served
    attn, idx_q = _selector_layer(model)
    seen, real = [], latent_attention.select_columns_span

    def spy(scores, *rule):
        sel = real(scores, *rule)
        seen.append((np.asarray(sel), np.asarray(scores)))
        return sel
    monkeypatch.setattr(latent_attention, "select_columns_span", spy)
    monkeypatch.setattr(attn.idx_q, "_value", idx_q)
    x = np.array(jax.random.normal(jax.random.key(11), (1, 20, 32)))
    x[0, list(TIED)] = x[0, TIED[0]]
    x = jnp.asarray(x)
    whole, planes = _prefill(attn, x, (4,) * 5, 0,
                             attn.gen_ring_cache(1, 32))
    sel_whole, scores = seen[-1]
    # the restore: columns [0, 16) of both planes into [shift, shift + 16)
    fresh = attn.gen_ring_cache(1, 32)
    restored = type(fresh)(*(Tensor(unwrap(f).at[:, :, shift:shift + 16]
                                    .set(unwrap(p)[:, :, :16]))
                             for f, p in zip(fresh, planes)))
    last, _ = attn.forward_cached(x[:, 16:], restored, jnp.int32(shift + 16),
                                  jnp.asarray([shift], jnp.int32))
    sel_hit = seen[-1][0]
    np.testing.assert_array_equal(sel_hit[0, :, shift:shift + 20],
                                  sel_whole[0, :, :20])
    assert not sel_hit[0, :, :shift].any() \
        and not sel_hit[0, :, shift + 20:].any()
    assert (sel_whole[0].sum(-1) == 6).all()
    # for most of the four queries the selection's threshold lies inside a
    # group of exactly equal scores that is only PARTLY taken, and of the
    # tied the lowest columns are the ones taken
    partly = 0
    for t in range(4):
        sc, sel = scores[0, t, :17 + t], sel_whole[0, t, :17 + t]
        tied = sc == sc[sel].min()
        took = np.flatnonzero(tied & sel)
        np.testing.assert_array_equal(took, np.flatnonzero(tied)[:took.size])
        partly += took.size < tied.sum()
    assert partly >= 2
    if shift % 8 == 0:
        np.testing.assert_array_equal(np.asarray(last), whole[:, 16:])
    else:
        np.testing.assert_allclose(np.asarray(last), whole[:, 16:], atol=1e-5)


# -- (d) what the prefix cache admits -------------------------------------------

def test_the_prefix_cache_admits_latent_and_selector_key_planes(served):
    """Decided from ``cache_spec``: planes as long as the session, a column
    a token, written once.  That a layer selects is the reader's business."""
    from paddle_tpu.serving import prefix_cache
    _, model, _ = served
    spec = Generator(model, max_len=64, seq_buckets=[64]).cache_spec(64)
    assert {s["kind"] for s in spec} == {"latent+selector_key"}
    prefix_cache.require_kv_planes(spec, 64)
    require_prefix_planes(spec + [dict(spec[0], kind="kv", select_top=None)],
                          64, "x")


@pytest.mark.parametrize("kind,change", [
    ("latent_window", {"columns": 8, "wraps": True, "select_top": None}),
    ("conv_state", {"columns": 0, "select_top": None}),
    ("latent+selector_key", {"columns": 32}),
], ids=["window_plane", "state", "shorter_than_the_session"])
def test_the_prefix_cache_refuses_beside_them(served, kind, change):
    """A window plane, a state without columns, or a plane shorter than
    the session BESIDE admitted planes: refused with the message that was
    there, naming the refused kind alone."""
    _, model, _ = served
    spec = Generator(model, max_len=64, seq_buckets=[64]).cache_spec(64)
    bad = spec + [dict(spec[0], kind=kind, **change)]
    with pytest.raises(InvalidArgumentError) as e:
        require_prefix_planes(bad, 64, "the prefix KV cache")
    assert f"keeps planes of kind {kind!r}, which it cannot cut" \
        in str(e.value)


# -- (e) the names and the counter ----------------------------------------------

def test_the_selector_scopes_are_in_both_programs(served):
    """``selector/score`` (the scores of every valid column) apart from
    ``selector/select`` (the radix search among them), inside the scope
    ``selector`` that was there, in the step and in the chunk program."""
    _, model, _ = served
    ledger.clear()
    try:
        gen = Generator(model, max_len=64, seq_buckets=[64])
        gen.step_exec(3, 64)
        gen.chunk_exec(3, 4, 64)
        tables = ledger.program_scopes()
    finally:
        ledger.clear()
    for program in ("jit_step", "jit_chunk"):
        scopes = {e["scope"] for e in tables[program].values()}
        for part in ("score", "select"):
            assert any(
                f"attention/latent_attention/selector/{part}" in s
                for s in scopes), (program, part)
        # the selector's own projections stay directly under ``selector``
        assert any(s.endswith("latent_attention/selector") for s in scopes)
    # (the counter ``prefix_restored_bytes`` is held to what a block of
    # this model holds by ``test_a_hit_equals_the_plain_prefill[glm5-*]``)


# -- (g) the search covers the dispatch's live span -----------------------------

SPAN_C = 64      # widths 12, 24, 48, 64 at index_topk 6; attn_block 8
# (pos, start, columns searched): a chunk is B = 1, T = attn_block = 8 with
# its widest context pos + 8 - start; a step B = 3, T = 1 with pos + 1 -
# min(start), a row whose start lies above the frontier is dead
SPAN_CHUNKS = [
    (0, 3, 0), (0, 2, 0), (0, 1, 12),               # topk - 1, topk, topk + 1
    (8, 5, 12), (8, 4, 12), (8, 3, 24),             # 12's edge
    (24, 9, 24), (24, 8, 24), (24, 7, 48),          # 24's edge
    (48, 9, 48), (48, 8, 48), (48, 7, 64),          # 48's edge
    (13, 2, 24),                                    # a chunk off the blocks
    (56, 30, 48), (56, 55, 12),                     # slices clamped at the end
]
SPAN_STEPS = [
    (5, (0, 2, 64), 0), (6, (0, 3, 64), 12),
    (11, (0, 5, 9), 12), (12, (0, 5, 9), 24),
    (23, (0, 20, 64), 24), (24, (0, 20, 64), 48),
    (47, (0, 1, 46), 48), (48, (0, 1, 46), 64),
    (63, (20, 40, 64), 48),                         # clamped: 20 + 48 > 64
    (10, (3, 30, 64), 12),                          # two dead rows of three
    (10, (64, 64, 64), 0),                          # every row dead
]


def _span_planes(attn, B, tied):
    """Planes of ``SPAN_C`` columns with something in every column; with
    ``tied``, three selector keys in four are ONE vector, so that their
    columns' scores tie exactly for every query, on both sides of every
    width's edge."""
    rng = np.random.default_rng(17)
    fresh = attn.gen_ring_cache(B, SPAN_C)
    lat, key = (rng.normal(size=unwrap(p).shape).astype(np.float32)
                for p in fresh)
    if tied:
        key[:, :, np.arange(SPAN_C) % 4 != 1] = key[0, 0, 0]
    return type(fresh)(Tensor(jnp.asarray(lat)), Tensor(jnp.asarray(key)))


@pytest.fixture(scope="module")
def span_programs(served):
    """Layer 1's ``forward_cached`` compiled once for a chunk (B = 1, T =
    8) and once for a step (B = 3, T = 1), each as it is and with the rule
    held to its last width, the plane's own, which is the program as it
    was: scores of every visible block, the search over all ``C`` columns.
    Each hands back (output, selector-key plane, membership, scores,
    branch) of one dispatch."""
    from unittest import mock
    attn = served[1].layers[1].attn
    real = latent_attention.select_columns_span

    def program(whole):
        def run(x, lat, key, pos, start):
            seen = []

            def spy(scores, valid, k, widths, branch, first):
                sel = real(scores, valid, k, widths, branch, first)
                seen.append((sel, scores, jnp.asarray(branch, jnp.int32)))
                return sel
            rule = (lambda widths, *_: len(widths)) if whole \
                else latent_attention.span_branch
            with mock.patch.object(latent_attention, "select_columns_span",
                                   spy), \
                    mock.patch.object(latent_attention, "span_branch", rule):
                out, cache = attn.forward_cached(
                    x, latent_attention.LatentCache(Tensor(lat), Tensor(key)),
                    pos, start)
            return (out, unwrap(cache.index_key)) + seen[-1]
        return jax.jit(run)
    return attn, program(False), program(True)


def _span_dispatch(program, x, planes, pos, start):
    out = program(x, *(unwrap(p) for p in planes), jnp.int32(pos),
                  jnp.asarray(start, jnp.int32))
    return tuple(np.asarray(o) for o in out)


def _check_span(span_programs, x, pos, start, searched, tied):
    attn, span, whole = span_programs
    widths = attn.search_widths(SPAN_C)
    assert widths == (12, 24, 48, 64)
    B, T = x.shape[:2]
    planes = _span_planes(attn, B, tied)
    out, keys, sel, scores, branch = _span_dispatch(span, x, planes, pos,
                                                    start)
    want_out, want_keys, want_sel, all_scores, last = _span_dispatch(
        whole, x, planes, pos, start)
    assert ((0,) + widths)[branch] == searched and last == len(widths)
    # the oracle is the whole-plane search over every visible column's score
    np.testing.assert_array_equal(want_sel, np.asarray(
        latent_attention.select_columns(
            jnp.asarray(all_scores), jnp.isfinite(all_scores), attn.topk)))
    np.testing.assert_array_equal(sel, want_sel)
    np.testing.assert_array_equal(out, want_out)
    # the selector's key is written whatever the branch
    np.testing.assert_array_equal(keys, want_keys)
    assert (keys[:, 0, pos:pos + T] != np.asarray(
        unwrap(planes.index_key))[:, 0, pos:pos + T]).all()
    ctx = pos + np.arange(T)[None, :] + 1 - np.asarray(start)[:, None]
    np.testing.assert_array_equal(sel.sum(-1),
                                  np.clip(ctx, 0, attn.topk))
    if not searched:
        # nothing to decide: no search, and no score was computed
        assert np.isneginf(scores).all() and ctx.max() <= attn.topk
    else:
        np.testing.assert_array_equal(scores, all_scores)
    return sel, all_scores


@pytest.mark.parametrize("tied", [False, True], ids=["distinct", "tied"])
@pytest.mark.parametrize("pos,start,searched", SPAN_CHUNKS)
def test_a_chunks_search_over_its_span_selects_what_the_plane_search_does(
        span_programs, pos, start, searched, tied):
    """A chunk of 8 queries at column ``pos`` of a row that starts at
    ``start``: the membership, the output and the written selector keys of
    the span-bounded search equal the whole-plane search's to the bit, at
    every width's edge, with the slice clamped at the plane's end, and
    with exact ties on both sides of the edges (the lower column wins)."""
    x = jax.random.normal(jax.random.key(pos * 64 + start), (1, 8, 32))
    sel, scores = _check_span(span_programs, x, pos, [start], searched,
                              tied)
    if tied and pos - start >= 16:
        # (the chunk writes its own keys over the plane's: the ties lie
        # below ``pos``)  some query's threshold lies inside a group of equal scores that
        # is only partly taken: the tie rule decided, and decided alike
        partly = 0
        for t in range(8):
            sc, chosen = scores[0, t], sel[0, t]
            if chosen.any():
                group = sc == sc[chosen].min()
                took = np.flatnonzero(group & chosen)
                np.testing.assert_array_equal(
                    took, np.flatnonzero(group)[:took.size])
                partly += took.size < group.sum()
        assert partly >= 1


@pytest.mark.parametrize("tied", [False, True], ids=["distinct", "tied"])
@pytest.mark.parametrize("pos,starts,searched", SPAN_STEPS)
def test_a_steps_search_over_its_span_selects_what_the_plane_search_does(
        span_programs, pos, starts, searched, tied):
    """A step of three rows, one query each at column ``pos``: the same,
    dead rows (``start`` above the frontier) selecting nothing."""
    x = jax.random.normal(jax.random.key(pos), (3, 1, 32))
    sel, _ = _check_span(span_programs, x, pos, list(starts), searched,
                         tied)
    assert not sel[np.asarray(starts) > pos].any()


@pytest.mark.parametrize("C,k,widths", [
    (24576, 2048, (4096, 8192, 16384, 24576)),      # glm-5-ep16-serve
    (12288, 2048, (4096, 8192, 12288)),             # dots3-note-prev-ep8
    (64, 6, (12, 24, 48, 64)), (13, 6, (12, 13)), (12, 6, (12,)),
    (7, 6, (7,)), (6, 6, ()), (4, 6, ())])
def test_the_widths_and_the_rule(C, k, widths):
    """``2 k``, ``4 k``, ... below the plane, then the plane; the rule
    takes the narrowest that holds the widest context, none while that is
    no more than ``k``; host integers and traced values alike."""
    from paddle_tpu.nn.functional import attention
    assert attention.search_widths(C, k) == widths
    for first in (0, 3):
        # every context about index_topk and about each width's edge
        lasts = np.array(sorted({
            first + edge + d - 1 for edge in (0, k) + widths
            for d in (-2, -1, 0, 1) if first + edge + d - 1 < C}))
        want = [_searched(widths, k, first, int(last)) if widths else 0
                for last in lasts]
        assert [attention.searched_columns(widths, k, first, int(last))
                for last in lasts] == want
        if widths:
            traced = jax.jit(lambda f, l: attention.span_branch(
                widths, k, f, l))(first, lasts)
            assert [((0,) + widths)[i] for i in np.asarray(traced)] == want


def _searched(widths, top, first, last):
    """The rule, spelled again: the narrowest width that holds the widest
    context, nothing while that is no more than ``top``."""
    widest = last - first + 1
    return 0 if widest <= top else min(w for w in widths if w >= widest)


@pytest.mark.parametrize("requests,searches", [
    (REQUESTS, True), ([(2, 2), (3, 2), (2, 3), (4, 2), (1, 4)], False)],
    ids=["contexts_past_index_topk", "contexts_within_index_topk"])
def test_the_selector_counters_follow_the_rule(served, requests, searches,
                                               monkeypatch):
    """``selector_columns_searched`` / ``_plane`` and their ``chunk_``
    twins: over the three selecting layers of every step and chunk the
    loop dispatched, the width the rule gives for the dispatch's ``pos``
    and ``start`` (the lowest among its rows), of the plane's 64 columns;
    nothing is searched in a run whose contexts never pass index_topk."""
    _, model, _ = served
    seen, real = [], SlotLoop._tally_columns

    def spy(self, cols, start, chunk=False):
        seen.append((np.array(cols), np.array(start), chunk))
        return real(self, cols, start, chunk)
    monkeypatch.setattr(SlotLoop, "_tally_columns", spy)
    _, _, st = _serve(model, requests)
    want = dict.fromkeys(("selector_columns_searched",
                          "selector_columns_plane",
                          "chunk_selector_columns_searched",
                          "chunk_selector_columns_plane"), 0)
    for cols, start, chunk in seen:
        pre = "chunk_" if chunk else ""
        want[pre + "selector_columns_plane"] += 3 * 64
        if cols.size:
            want[pre + "selector_columns_searched"] += 3 * _searched(
                (12, 24, 48, 64), 6, int(start.min()), int(cols.max()))
    assert {k: st[k] for k in want} == want
    assert st["selector_columns_plane"] == 3 * 64 * st["steps"]
    assert st["chunk_selector_columns_plane"] == 3 * 64 * st["chunks"]
    if searches:
        assert 0 < st["selector_columns_searched"] \
            < st["selector_columns_plane"]
        assert 0 < st["chunk_selector_columns_searched"] \
            < st["chunk_selector_columns_plane"]
    else:
        assert st["selector_columns_searched"] \
            == st["chunk_selector_columns_searched"] == 0


def test_the_widths_are_in_the_spec_and_in_the_ledger_events(served):
    """The layer hands the widths out through ``cache_spec`` (the loop
    reads them there) and the two slot programs' ledger events name them
    beside ``latent_form``; a model without a selecting layer has no such
    fact, and its loop no such counter."""
    _, model, _ = served
    gen = Generator(model, max_len=64, seq_buckets=[64])
    assert [s["select_widths"] for s in gen.cache_spec(64)] \
        == [(12, 24, 48, 64)] * 3
    assert gen._step_program(3, 64)[4]["selector_widths"] == [12, 24, 48, 64]
    assert gen._chunk_program(3, 4, 64)[4]["selector_widths"] \
        == [12, 24, 48, 64]
    kimi = Generator(_build(_tiny("kimi"))[0], max_len=64, seq_buckets=[64])
    assert [s["select_widths"] for s in kimi.cache_spec(64)] == [None] * 3
    assert kimi.selector_widths(64) is None
    assert "selector_widths" not in kimi._step_program(3, 64)[4]
    assert "selector_widths" not in kimi._chunk_program(3, 4, 64)[4]
    loop = SlotLoop(kimi, slots=3, cache_len=64, chunk=4)
    try:
        assert not [k for k in loop.counters if "selector_columns" in k]
    finally:
        loop.close()


# -- (f) the counts at the published size ----------------------------------------

def test_counts_at_the_published_size():
    cfg = _load("configs/glm-5-ep16-serve.json")
    attention = (6144 * 2048 + 2048 + 2048 * 64 * 256 + 6144 * 576 + 512
                 + 64 * 512 * (192 + 256) + 64 * 256 * 6144)
    selector = 2048 * 32 * 128 + 6144 * 128 + 2 * 128 + 6144 * 32
    assert counts.attention_parameters(cfg) == attention == 165_022_208
    assert counts.selector_parameters(cfg) == selector == 9_371_904
    expert = 3 * 6144 * 2048
    assert expert == 37_748_736
    dense = attention + selector + 2 * 6144 + 3 * 6144 * 12288
    moe = attention + selector + 2 * 6144 + 17 * expert + 6144 * 256 + 256
    total = dense + 4 * moe + 2 * 19360 * 6144 + 6144
    assert counts.params(cfg) == total == 3_909_632_768      # 3,909.6 M
    assert counts.weight_bytes(cfg) == 2 * total             # 7.82 GB
    assert counts.cache_bytes_per_token(cfg) == 5 * (576 + 128) * 2
    shapes = ref.leaf_shapes(cfg)
    assert sum(int(np.prod(s)) for s, _ in shapes.values()) == total
    # a step of 16 rows, each with 16,000 valid columns of which the
    # selector keeps 2,048, in each of 5 layers; 16 x 8 x 4 assignments,
    # 1/16 of them held
    rows, held = 16.0, 32.0
    valid, selected = 16 * 16000 * 5.0, 16 * 2048 * 5.0
    st = counts.step(cfg, rows, held, selected, valid)
    fixed = total - 64 * expert - 19360 * 6144
    touched = 4 * 16 * (1 - (15 / 16) ** 8)
    assert st["bytes"] == pytest.approx(
        2 * (fixed + touched * expert) + selected * 576 * 2
        + valid * 128 * 2)
    per_token = 5 * 2 * (attention + selector) + 6 * 6144 * 12288 \
        + 4 * (2 * expert + 2 * 6144 * 256)
    assert st["flops"] == pytest.approx(
        per_token * rows + 2 * expert * held + 2 * 6144 * 19360 * rows
        + (2 * 32 * 128 + 64) * valid + 2 * 64 * 1088 * selected)
    # a chunk of 512 tokens whose contexts average 8,192 in each layer
    tokens, held = 512.0, 512 * 8 * 4 / 16
    valid, selected = 512 * 8192 * 5.0, 512 * 2048 * 5.0
    ch = counts.chunk(cfg, tokens, held, selected, valid)
    end = 8192 + 256
    assert ch["bytes"] == pytest.approx(
        2 * (fixed + 4 * 16 * (1 - (15 / 16) ** 256) * expert)
        + 5 * 2048 * 576 * 2 + 5 * end * 128 * 2)
    absorbed = 2 * 64 * 1088 * selected
    per_head = 2 * 64 * 512 * selected + 2 * 64 * 512 * 448 * 5 * (2048 - 512)
    assert per_head < absorbed
    assert ch["flops"] == pytest.approx(
        per_token * tokens + 2 * expert * held + 2 * 6144 * 19360
        + (2 * 32 * 128 + 64) * valid + per_head)
    # what the masked read costs beside the least: a step's valid columns
    # are 7.8 x its selected ones
    assert valid / selected == 4.0 and 16000 / 2048 > 7.8
