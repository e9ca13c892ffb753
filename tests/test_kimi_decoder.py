"""Kimi-K2.5's language model (every layer a full latent layer with NO
selector, no gate, no latent rescale, YaRN positions; text/models/
latent_moe.py) at tiny widths on the CPU, seeded weights: the slot loop's
chunks and steps against the plain reference's full forward
(benchmark/reference/kimi_k2.py, which imports nothing of the program), the
expert shares, YaRN, the prefix cache over latent planes (a row served from
a hit is the row prefilled whole, to the bit), the document traffic, and
the count functions at the published size.  The cases that GLM-5's
configuration shares (the expert shares, a hit against the plain prefill)
run for both families; tests/test_glm5_decoder.py holds what its selector
adds and takes its helpers from here.
"""
import importlib
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

from benchmark import harness                                 # noqa: E402
from benchmark.counts import kimi_k2 as counts               # noqa: E402
from benchmark.generators import closed_loop_docs            # noqa: E402
from benchmark.reference import kimi_k2 as ref               # noqa: E402
from benchmark.reference.common import Arith                 # noqa: E402
from paddle_tpu.framework.enforce import InvalidArgumentError  # noqa: E402
from paddle_tpu.framework.tensor import unwrap               # noqa: E402
from paddle_tpu.nn.functional.attention import (             # noqa: E402
    rotary, rotary_frequencies, yarn_attention_factor)
from paddle_tpu.serving.prefix_cache import PrefixCache      # noqa: E402
from paddle_tpu.serving.slots import SlotLoop                # noqa: E402
from paddle_tpu.text.generation import Generator             # noqa: E402

# float32 on the CPU: the program (absorbed form, cache, chunks) and the
# reference (per-head, one pass) differ by summation order only; a served
# token may be the reference's second choice at a near-tie of that size
GAP_TOL = 1e-4
# bfloat16 weights and operands (float32 accumulation and residual stream)
# against the float32 reference on the SAME bfloat16 weights: operands
# rounded to 8 bits move a logit of spread ~1 by ~0.01, and a served token
# may be any that lies that near the best (on the chip the cell's limit is
# set between such readings and the float8 control's: PERF.md section 2)
GAP_TOL_BF16 = 0.08
REQUESTS = [(9, 6), (13, 8), (5, 4), (17, 8), (7, 8), (11, 5), (14, 7),
            (16, 6)]


def _load(rel):
    with open(os.path.join(ROOT, "benchmark", rel)) as f:
        return json.load(f)


# the configurations of the latent family that the prefix cache serves:
# (the benchmark's file, its tiny size)
FAMILIES = {"kimi": ("kimi-k2.5-ep32-serve", "kimi_tiny"),
            "glm5": ("glm-5-ep16-serve", "glm5_tiny")}


def _tiny(family="kimi", **over):
    config, size = FAMILIES[family]
    cfg = _load(f"configs/{config}.json")
    tiny = _load(f"tests/data/{size}.json")["over"]
    cfg["serve"].update(tiny.pop("serve"))
    cfg.update(tiny)
    cfg.update(over)
    return cfg


def _modules(cfg):
    """(benchmark.models.<family>, benchmark.reference.<family>)."""
    return tuple(importlib.import_module(f"benchmark.{part}.{cfg['family']}")
                 for part in ("models", "reference"))


def _build(cfg, seed=5):
    models, reference = _modules(cfg)
    mapped = models.to_program(reference.init_weights(cfg, seed))
    model = models.build(cfg, mapped)
    return model, harness.canonical_view(mapped, models.leaf_ids(cfg))


@pytest.fixture(scope="module")
def served():
    """ONE tiny float32 model with the reference's seeded weights, and its
    view of them for the reference."""
    cfg = _tiny()
    return (cfg,) + _build(cfg)


def _serve(model, requests, prompts=None, chunk=4, columns=64, **loop_kw):
    gen = Generator(model, max_len=columns, seq_buckets=[columns])
    loop = SlotLoop(gen, slots=3, cache_len=columns, chunk=chunk, **loop_kw)
    rng = np.random.default_rng(1)
    if prompts is None:
        prompts = [rng.integers(0, 96, n).astype(np.int32)
                   for n, _ in requests]
    futs = [loop.submit(p, k) for p, (_, k) in zip(prompts, requests)]
    out = [np.asarray(f.result(timeout=300)) for f in futs]
    stats = loop.stats()
    loop.close()
    return prompts, out, stats


def _widest_gap(cfg, view, prompts, tokens):
    gaps = _modules(cfg)[1].served_gaps
    return max(float(np.max(gaps(cfg, view, p, t)))
               for p, t in zip(prompts, tokens))


# -- (a) chunked prefill, then cached decode, against the reference ------------

def _cpu_bf16_products(monkeypatch):
    """The CPU backend has no bfloat16 product for ONE of the layer's
    einsums inside the whole program (``W_uv`` on the attention-weighted
    latent: "Unsupported element type for DotThunk"; the chip has it).
    Here, and only here, its two bfloat16 operands are widened to float32
    first: the same operands (a product of two bfloat16 numbers is exact in
    float32) and the same float32 sums."""
    real = jnp.einsum

    def einsum(spec, *ops, **kw):
        if spec == "bthr,hrv->bthv" and ops[1].dtype == jnp.bfloat16:
            ops = [o.astype(jnp.float32) for o in ops]
        return real(spec, *ops, **kw)
    monkeypatch.setattr(jnp, "einsum", einsum)


@pytest.mark.parametrize("dtype,tol", [("float32", GAP_TOL),
                                       ("bfloat16", GAP_TOL_BF16)])
def test_slot_loop_equals_the_reference(served, dtype, tol, monkeypatch):
    """Prefill by chunks + decoding through SlotLoop, rows joining and
    retiring (8 requests over 3 slots), equals the reference's full
    forward; the loop's counters say what ran: one latent plane a layer,
    every valid column read."""
    cfg, model, view = served
    if dtype != "float32":
        _cpu_bf16_products(monkeypatch)
        cfg = _tiny(dtype=dtype)
        model, view = _build(cfg)
    prompts, tokens, st = _serve(model, REQUESTS)
    assert _widest_gap(cfg, view, prompts, tokens) < tol
    assert st["plane_kinds"] == ["latent"]
    # this family's chunk still runs on a row cut out of the planes
    assert st["chunk_row"] == "sliced"
    assert st["latent_form"] == {"step": "absorbed", "chunk": "absorbed"}
    moe_layers, k = 2, cfg["num_experts_per_tok"]
    assert st["moe_assignments"] == \
        (sum(n for n, _ in REQUESTS) + st["emitted_tokens"]) * k * moe_layers
    assert 0 < st["moe_assignments_held"] < st["moe_assignments"]
    assert st["chunk_tokens"] == sum(n for n, _ in REQUESTS)
    # no selector: what a token's attention reads is its whole context, in
    # each of the three layers
    assert st["attn_columns_selected"] == st["attn_columns_valid"] > 0
    assert st["chunk_attn_columns_valid"] == 3 * sum(
        n * (n + 1) // 2 for n, _ in REQUESTS)
    assert "window_wraps" not in st and "kv_columns_valid" not in st


def test_the_planes_are_one_latent_plane_a_layer(served):
    cfg, model, _ = served
    gen = Generator(model, max_len=64, seq_buckets=[64])
    spec = gen.cache_spec(64)
    assert [s["kind"] for s in spec] == ["latent"] * 3
    assert all(s["columns"] == 64 and not s["wraps"]
               and s["select_top"] is None and s["window"] is None
               for s in spec)
    planes = gen.slot_cache_avals_all(3, 64)
    # latent 12 + rotary key 8 = 20 numbers, padded to the lane count; no
    # selector-key plane beside it
    assert [[tuple(p.shape) for p in c] for c in planes] \
        == [[(3, 1, 64, 128)]] * 3
    attn = model.layers[0].attn
    assert attn.gate is None and not attn.selects and attn.s_q == 1.0
    assert not hasattr(attn, "idx_q")


def test_absorbed_form_equals_the_per_head_form(served):
    """One attention layer: ``forward`` (no cache, per-head keys and values
    from the latent) against ``forward_cached`` (absorbed; blocks of 3, 2
    and 4 tokens, then single steps), batch of 2: float32 rounding of
    another summation order, under YaRN in both."""
    _, model, _ = served
    attn = model.layers[1].attn
    x = jax.random.normal(jax.random.key(2), (2, 20, 32))
    full = np.asarray(jax.jit(attn.forward)(x))
    planes0 = attn.gen_ring_cache(2, 32)

    @jax.jit
    def cached(xs, planes, pos):
        out, cache = attn.forward_cached(xs, type(planes0)(*planes), pos,
                                         jnp.zeros(2, jnp.int32))
        return out, tuple(unwrap(p) for p in cache)

    planes, pos, got = tuple(unwrap(p) for p in planes0), 0, []
    for n in (3, 2, 4) + (1,) * 11:
        out, planes = cached(x[:, pos:pos + n], planes, jnp.int32(pos))
        got.append(np.asarray(out))
        pos += n
    np.testing.assert_allclose(np.concatenate(got, 1), full, atol=2e-6)
    assert len(planes) == 1

@pytest.mark.parametrize("blocks", [(5, 3, 7, 4, 1, 1, 6, 1), (27, 1)],
                         ids=["narrow_blocks", "one_wide_block"])
def test_the_cached_forms_agree(served, blocks):
    """A full layer WITHOUT selector under YaRN, three rows whose ``start``
    differ and are no multiples of the 8-column ``attn_block``: the
    absorbed form, the per-head form (each forced, whatever the block's
    width) and the cache-less ``forward`` agree at every live position, and
    both cached forms write the same rows (tests/test_latent_decoder.py
    holds the selector and the window layers to the same)."""
    from test_latent_decoder import assert_forms_agree
    _, model, _ = served
    x = jax.random.normal(jax.random.key(7), (3, 28, 32))
    assert_forms_agree(model.layers[1].attn, x, blocks, (0, 3, 5), 32)


@pytest.fixture(scope="module")
def served_wide():
    """The tiny model built for chunks of 32 tokens: over the rule's
    threshold (24 queries at these widths)."""
    cfg = _tiny()
    cfg["serve"]["prefill_chunk"] = 32
    return (cfg,) + _build(cfg)


@pytest.mark.parametrize("dtype,tol", [("float32", GAP_TOL),
                                       ("bfloat16", GAP_TOL_BF16)])
def test_wide_chunks_equal_the_reference(served_wide, dtype, tol,
                                         monkeypatch):
    """Prompts of 5-70 tokens prefilled in chunks of 32 (per head) and
    decoded by absorbed steps over the rows those chunks wrote, against
    the reference's full forward, in float32 and in the served dtype."""
    cfg, model, view = served_wide
    if dtype != "float32":
        _cpu_bf16_products(monkeypatch)
        cfg = dict(cfg, dtype=dtype)
        model, view = _build(cfg)
    requests = [(40, 6), (33, 8), (5, 4), (70, 8), (64, 5), (17, 7)]
    prompts, tokens, st = _serve(model, requests, chunk=32, columns=128)
    assert _widest_gap(cfg, view, prompts, tokens) < tol
    assert st["latent_form"] == {"step": "absorbed", "chunk": "per_head"}
    assert st["chunk_attn_columns_valid"] == 3 * sum(
        n * (n + 1) // 2 for n, _ in requests)


# -- (b) the shares add up ------------------------------------------------------

def share_parts(cfg, reference, whole, u, lo, hi):
    """(routed, shared, margin) that the share holding experts [lo, hi) of
    the uncut layer ``whole`` gives for the normed tokens ``u``."""
    lw = {k: (v[lo:hi] if k.startswith("exp_") else v)
          for k, v in whole.items()}
    return jax.jit(lambda u, lw, lo: reference.moe_parts(
        Arith("float32"), u, lw, cfg, (lo, None)))(u, lw, jnp.int32(lo))


@pytest.mark.parametrize("shares", [4, 2, 1])
@pytest.mark.parametrize("family", list(FAMILIES))
def test_shares_add_up_to_the_uncut_layer(family, shares):
    """The routed parts that all the shares of one layer give, with what
    every chip computes alike (the shared expert) counted once, equal the
    uncut 8-expert reference layer's FFN, ``routed_scaling_factor`` and
    all (float32: to rounding of another summation order).  The
    PROGRAM's share, ``DroplessMoE`` holding experts [0, n) of the 8, is
    the first of those parts."""
    from paddle_tpu.framework.functional import _bound_state
    from paddle_tpu.nn.layer.moe import DroplessMoE
    cfg = _tiny(family)
    reference = _modules(cfg)[1]
    E = cfg["n_routed_experts_published"]
    whole = reference._layer_weights(
        reference.init_weights(dict(cfg, experts_held=[0, E]), 7), 1)
    u = jax.random.normal(jax.random.key(0), (23, cfg["hidden_size"]))
    routed, shared, _ = share_parts(cfg, reference, whole, u, 0, E)
    n = E // shares
    each = [share_parts(cfg, reference, whole, u, i * n, (i + 1) * n)
            for i in range(shares)]
    top = float(jnp.abs(routed + shared).max())
    np.testing.assert_allclose(sum(p[0] for p in each) + each[0][1],
                               routed + shared, atol=1e-5 * top)
    if shares > 1:      # a share alone is NOT the layer: the cut is real
        assert float(jnp.abs(each[0][0] - routed).max()) > 1e-3
    layer = DroplessMoE(cfg["hidden_size"], cfg["moe_intermediate_size"], E,
                        cfg["num_experts_per_tok"], held=(0, n), shared=1,
                        scaling=cfg["routed_scaling_factor"])
    w = {"router": whole["router"], "router_bias": whole["router_b"],
         "w_gate": whole["exp_g"][:n], "w_up": whole["exp_u"][:n],
         "w_down": whole["exp_d"][:n], "shared.w_gate": whole["sh_g"],
         "shared.w_up": whole["sh_u"], "shared.w_down": whole["sh_d"]}
    with _bound_state(layer, w, {}):
        mine = unwrap(layer(u))
    np.testing.assert_allclose(mine, each[0][0] + each[0][1],
                               atol=1e-5 * top)


# -- (c) YaRN ------------------------------------------------------------------

def test_yarn_factor_one_is_the_plain_rotary_to_the_bit():
    x = jax.random.normal(jax.random.key(3), (2, 9, 4, 64))
    pos = jnp.arange(18, dtype=jnp.int32).reshape(2, 9) * 37
    one = {"type": "yarn", "factor": 1, "beta_fast": 32, "beta_slow": 1,
           "mscale": 1, "mscale_all_dim": 1,
           "original_max_position_embeddings": 4096}
    inv = rotary_frequencies(64, 50000.0, one)
    np.testing.assert_array_equal(np.asarray(inv),
                                  np.asarray(rotary_frequencies(64, 50000.0)))
    np.testing.assert_array_equal(
        np.asarray(rotary(x, pos, 50000.0, inv=inv)),
        np.asarray(rotary(x, pos, 50000.0)))
    assert yarn_attention_factor(one) == 1.0 == yarn_attention_factor(None)


def test_yarn_factor_64_matches_the_reference():
    """At the published keys: the program's frequencies are the
    reference's (written out from the config on its own), the correction
    dimensions 8 and 20, the softmax scale 192^-0.5 x 2.0047."""
    cfg = _load("configs/kimi-k2.5-ep32-serve.json")
    sc, d, base = cfg["rope_scaling"], 64, 50000.0
    mine = np.asarray(rotary_frequencies(d, base, sc))
    want = ref.yarn_frequencies(cfg)
    np.testing.assert_allclose(mine, want, rtol=2e-6)
    plain = np.asarray(rotary_frequencies(d, base))
    np.testing.assert_array_equal(mine[:9], plain[:9])     # fast: kept
    np.testing.assert_allclose(mine[20:], plain[20:] / 64, rtol=1e-6)
    assert np.all(np.diff(mine / plain) <= 0)
    np.testing.assert_allclose(mine[14] / plain[14], 1 - 0.5 * 63 / 64,
                               rtol=1e-6)
    m = 0.1 * np.log(64.0) + 1.0
    assert yarn_attention_factor(sc) == pytest.approx(m * m) \
        == pytest.approx(2.00474, abs=1e-5)
    assert ref.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
    assert ref.rotary_multiplier(cfg) == 1.0
    from paddle_tpu.nn.layer.latent_attention import LatentAttention
    attn = LatentAttention(64, 2, 128, 64, 128, 16, 16, base, rope_scaling=sc,
                           gate=False, rescale=False)
    assert attn.scale == pytest.approx(ref.softmax_scale(cfg))
    np.testing.assert_array_equal(np.asarray(attn.inv), mine)


# -- (d) the prefix cache over latent planes -----------------------------------

def _logits_spy(loop):
    """Every activation row the loop writes into its step logits."""
    rows, put = [], loop._put_row

    def spy(logits, row, i):
        rows.append(np.asarray(row))
        return put(logits, row, i)
    loop._put_row = spy
    return rows


def _block_nbytes(gen, S, T, C):
    return sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(gen._block_avals(S, T, C)))


@pytest.mark.parametrize("attn_block", [1, 8])
@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_hit_equals_the_plain_prefill(family, attn_block):
    """Three asks of one document through a loop WITH the prefix cache (the
    later ones restore the first's blocks into OTHER rows at OTHER starts
    and prefill their suffix chunks) against the same through a loop
    without it; and the counters say what the cache did.

    ``attn_block`` 1: the same tokens and the same activation logits, BIT
    FOR BIT: a restored block is a copy of what the publishing row
    computed, and everything after it is the same program.  At a block of
    8 columns the tokens are the same and the logits differ in their last
    bits (float32: under 1e-5 of a spread of ~1), hit or no hit: the
    attention's running softmax takes a row's context in blocks of
    ABSOLUTE columns (``latent_attend_blocked``), so the order of its sums
    follows ``start mod attn_block``, and a hit activates at another
    ``start`` than the whole prefill would.  Two plain prefills of one
    prompt at two starts differ as much.

    With a selector on every layer (``glm5``: a block carries each layer's
    selector keys beside its latent rows) the same holds: the restored keys
    are scored as those a chunk wrote, and the row selects the columns the
    whole prefill selects."""
    cfg = _tiny(family)
    cfg["serve"]["attn_block"] = attn_block
    model, _ = _build(cfg)
    rng = np.random.default_rng(4)
    doc = rng.integers(0, 96, 22).astype(np.int32)
    filler = rng.integers(0, 96, 9).astype(np.int32)
    asks = [np.concatenate([doc, rng.integers(0, 96, n).astype(np.int32)])
            for n in (3, 5, 2)]
    got = {}
    for name in ("plain", "cached"):
        gen = Generator(model, max_len=64, seq_buckets=[64])
        cache = PrefixCache(4, _block_nbytes(gen, 3, 4, 64)) \
            if name == "cached" else None
        loop = SlotLoop(gen, slots=3, cache_len=64, chunk=4,
                        prefix_cache=cache)
        rows = _logits_spy(loop)
        toks = []
        try:
            # the first ask alone (a miss: it publishes), then a filler and
            # the later asks together: they land in other rows, later in
            # the session
            toks.append(np.asarray(loop.submit(asks[0], 6).result(timeout=300)))
            futs = [loop.submit(filler, 7)] + [loop.submit(a, 6)
                                               for a in asks[1:]]
            toks += [np.asarray(f.result(timeout=300)) for f in futs]
            got[name] = (toks, rows, loop.stats())
        finally:
            loop.close()
    (plain_t, plain_r, _), (hit_t, hit_r, st) = got["plain"], got["cached"]
    for a, b in zip(plain_t, hit_t):
        np.testing.assert_array_equal(a, b)
    assert len(plain_r) == len(hit_r) == 4
    for row in hit_r:           # (activation order differs: match by value)
        nearest = min(plain_r, key=lambda p: float(np.abs(p - row).max()))
        if attn_block == 1:
            np.testing.assert_array_equal(row, nearest)
        else:
            np.testing.assert_allclose(row, nearest, atol=1e-5)
    # 22 document tokens = 5 whole blocks of 4: both later asks hit them
    assert st["prefix_lookups"] == 4 and st["prefix_hits"] == 2
    assert st["prefix_hit_tokens"] == 2 * 20
    assert st["restore_pushes"] == 2 * 5
    # by what a block holds: one latent plane a layer, or two planes
    planes = 2 if family == "glm5" else 1
    assert st["plane_kinds"] == [
        "latent+selector_key" if family == "glm5" else "latent"]
    assert st["prefix_restored_bytes"] == 10 * _block_nbytes(gen, 3, 4, 64) \
        == 10 * 3 * 4 * 4 * (128 + (planes - 1) * cfg.get("index_head_dim", 0))
    assert st["prompt_tokens_admitted"] == sum(a.size for a in asks) + 9
    # asks of 25, 27 and 24 tokens: 6 whole blocks each, 5 of them the
    # document's (published once); the filler's 2
    assert st["prefix_blocks_published"] == 6 + 1 + 1 + 2
    assert st["prefix_blocks_evicted"] == 0
    assert st["phase_s"]["restore"] > 0 and st["phase_s"]["publish"] > 0
    # the hits prefilled their suffix only: 2 and 1 chunks, not 7 and 6
    assert st["chunks"] == 7 + 3 + 2 + 1


def test_eviction_under_a_small_budget_frees_only_unpinned_blocks(served):
    """A budget of 6 blocks: publishing a second document evicts the
    first's blocks, oldest leaves first, but never one that a lookup has
    pinned; the loop goes on answering correctly (a document evicted is a
    miss again) and counts what went."""
    _, model, _ = served
    gen = Generator(model, max_len=64, seq_buckets=[64])
    nbytes = _block_nbytes(gen, 3, 4, 64)
    cache = PrefixCache(4, nbytes, hbm_budget_mb=6 * nbytes / 2 ** 20)
    toks = list(range(24))
    assert cache.publish(toks, lambda j: ("a", j)) == 6
    blocks, pin = cache.lookup(toks[:13])            # pins blocks 0..2
    assert [b[1] for b in blocks] == [0, 1, 2]
    other = list(range(50, 70))
    assert cache.publish(other, lambda j: ("b", j)) == 5
    # over budget by 5: the three unpinned blocks of the first chain went
    # (leaves first), the pinned ones stayed, and then the newcomers'
    # own leaves, oldest first
    st = cache.stats()
    assert st["evictions"] == 5 and st["blocks"] == 6
    again, pin2 = cache.lookup(toks)
    assert [b[1] for b in again] == [0, 1, 2]
    cache.release(pin)
    cache.release(pin2)
    # through the loop: three documents, 6 blocks of budget
    loop = SlotLoop(gen, slots=3, cache_len=64, chunk=4,
                    prefix_cache=PrefixCache(
                        4, nbytes, hbm_budget_mb=6 * nbytes / 2 ** 20))
    plain = SlotLoop(gen, slots=3, cache_len=64, chunk=4)
    rng = np.random.default_rng(6)
    docs = [rng.integers(0, 96, 21).astype(np.int32) for _ in range(3)]
    prompts = [np.concatenate([d, rng.integers(0, 96, 3).astype(np.int32)])
               for d in docs for _ in range(2)]
    try:
        for p in prompts:
            np.testing.assert_array_equal(
                np.asarray(loop.submit(p, 4).result(timeout=300)),
                np.asarray(plain.submit(p, 4).result(timeout=300)))
        st = loop.stats()
    finally:
        loop.close()
        plain.close()
    assert st["prefix_hits"] == 3 and st["prefix_blocks_evicted"] > 0
    assert st["prefix_cache_blocks"] <= 6 if "prefix_cache_blocks" in st \
        else True


@pytest.mark.parametrize("family", ["dots3", "lfm2"])
def test_the_prefix_cache_still_refuses_planes_it_cannot_cut(family):
    """A window plane shorter than the session and a state without
    columns: refused, with the message the refusal had, decided from
    ``cache_spec``.  A selector-key plane beside a latent plane is NOT
    among the refused any more (tests/test_glm5_decoder.py): of dots3's
    two kinds only the window plane is named."""
    from paddle_tpu.serving import prefix_cache
    from paddle_tpu.text.generation import require_prefix_planes
    latent = {"kind": "latent", "columns": 64, "wraps": False,
              "select_top": None}
    prefix_cache.require_kv_planes([latent, dict(latent, kind="kv")], 64)
    if family == "dots3":
        bad = [dict(latent, kind="latent+selector_key", select_top=6),
               dict(latent, kind="latent_window", columns=8, wraps=True)]
        names = "'latent_window'"
    else:
        bad = [dict(latent, kind="conv_state", columns=0),
               dict(latent, kind="kv")]
        names = "'conv_state'"
    with pytest.raises(InvalidArgumentError) as e:
        prefix_cache.require_kv_planes(bad, 64)
    assert f"keeps planes of kind {names}, which it cannot cut" \
        in str(e.value)
    assert "the prefix KV cache" in str(e.value)
    # a plane of the right kind but shorter than the session is refused too
    with pytest.raises(InvalidArgumentError, match="'latent'"):
        require_prefix_planes([dict(latent, columns=32)], 64, "x")


def test_sessions_and_handoff_go_on_refusing_a_latent_plane(served):
    from paddle_tpu.serving.cluster import handoff
    _, model, _ = served
    gen = Generator(model, max_len=64, seq_buckets=[64])
    with pytest.raises(InvalidArgumentError, match="'latent'"):
        SlotLoop(gen, slots=2, cache_len=64, chunk=4, session_store=object())
    with pytest.raises(InvalidArgumentError, match="'latent'"):
        handoff.require_kv_planes(gen.plane_kinds())


# -- (e) the document traffic ----------------------------------------------------

def _doc_traffic(**kw):
    tr = _load("traffic/docqa8k-closed-1S.json")
    tr.update(pool_docs=40,
              doc_len={"dist": "lognormal", "median": 22, "sigma": 0.1,
                       "min": 18, "max": 26},
              question_len={"dist": "lognormal", "median": 4, "sigma": 0.5,
                            "min": 2, "max": 8},
              max_new_tokens={"dist": "lognormal", "median": 4, "sigma": 0.5,
                              "min": 2, "max": 6})
    tr.update(kw)
    return tr


def _walk(docs, clients, rounds):
    """Every client's requests over ``rounds`` rounds, round-robin."""
    out = []
    for _ in range(rounds):
        for c in range(clients):
            out.append((c,) + docs.next(c))
    return out


def test_closed_loop_docs_is_one_sequence_for_every_seed():
    tr = _doc_traffic()
    a = _walk(closed_loop_docs.Docs(tr, 96, 1, 8), 8, 12)
    b = _walk(closed_loop_docs.Docs(tr, 96, 2 ** 31 + 5, 8), 8, 12)
    assert [(c, p.size, m, d, k) for c, p, m, d, k in a] \
        == [(c, p.size, m, d, k) for c, p, m, d, k in b]
    assert any(not np.array_equal(x[1], y[1]) for x, y in zip(a, b))
    again = _walk(closed_loop_docs.Docs(tr, 96, 1, 8), 8, 12)
    assert all(np.array_equal(x[1], y[1]) for x, y in zip(a, again))
    assert all(0 <= int(p.min()) and int(p.max()) < 96 for _, p, *_ in a)


def test_closed_loop_docs_asks_share_the_document_and_nothing_else():
    tr = _doc_traffic()
    docs = closed_loop_docs.Docs(tr, 96, 3, 8)
    reqs = _walk(docs, 8, 14)
    by_doc = {}
    for c, p, m, d, k in reqs:
        by_doc.setdefault(d, []).append((c, k, p))
    whole = {d: v for d, v in by_doc.items()
             if len(v) == docs.asks[d]}          # documents asked to the end
    assert len(whole) > 12
    for d, asks in whole.items():
        assert len({c for c, _, _ in asks}) == 1       # one worker a document
        assert [k for _, k, _ in asks] == list(range(len(asks)))
        n = int(docs.doc_len[d])
        for _, k, p in asks:
            assert p.size == n + docs.question_len[d, k]
            np.testing.assert_array_equal(p[:n], asks[0][2][:n])
        if len(asks) > 1:       # the questions differ from their first token
            assert len({int(p[n]) for _, _, p in asks}
                       | {bytes(p[n:]) for _, _, p in asks}) > len(asks)
        # caller c's FIRST document has 1 + c mod 4 asks; every other 3..5
        if d < 8:
            assert len(asks) == 1 + d % 4 and asks[0][0] == d
        else:
            assert 3 <= len(asks) <= 5
    later = docs.asks[8:]
    assert set(later.tolist()) == {3, 4, 5}
    # documents of different ids share nothing
    first = {d: v[0][2] for d, v in by_doc.items()}
    assert not np.array_equal(first[0][:18], first[1][:18])


def test_closed_loop_docs_drives_a_closed_loop():
    """``drive`` with a submit that resolves at once: callers x asks in
    order, every record whole, marked with its document and ask."""
    from concurrent.futures import Future
    tr = _doc_traffic(ramp_s=0.05)
    marks = []

    def submit(prompt, max_new):
        f = Future()
        f.set_result([np.zeros((1, max_new), np.int32)])
        return f

    records, t_open, t_close = closed_loop_docs.drive(
        tr, 9, 0.3, submit, vocab_size=96, slots=4,
        on_open=lambda: marks.append("open"),
        on_close=lambda: marks.append("close"), span=harness.span)
    assert marks == ["open", "close"] and t_close - t_open == pytest.approx(0.3)
    assert len(records) > 16 and all(r.done is not None for r in records)
    assert [r.doc for r in records[:4]] == [0, 1, 2, 3]
    assert all(r.ask == 0 for r in records[:4])
    misses = sum(1 for r in records if r.ask == 0)
    assert 0.15 < misses / len(records) < 0.45


# -- (f) the counts at the published size ----------------------------------------

def test_counts_at_the_published_size():
    cfg = _load("configs/kimi-k2.5-ep32-serve.json")
    attention = (7168 * 1536 + 1536 + 1536 * 64 * 192 + 7168 * 576 + 512
                 + 64 * 512 * 256 + 64 * 128 * 7168)
    assert counts.attention_parameters(cfg) == attention == 101_124_096
    expert = 3 * 7168 * 2048
    layer0 = attention + 2 * 7168 + 3 * 7168 * 18432
    moe = attention + 2 * 7168 + 13 * expert + 7168 * 384 + 384
    total = layer0 + 4 * moe + 2 * 20480 * 7168 + 7168
    assert counts.params(cfg) == total == 3_496_763_904      # 3,496.7 M
    assert counts.weight_bytes(cfg) == 2 * total             # 6.99 GB
    assert counts.cache_bytes_per_token(cfg) == 5 * 576 * 2
    shapes = ref.leaf_shapes(cfg)
    assert sum(int(np.prod(s)) for s, _ in shapes.values()) == total
    # a step of 32 rows, each with 7,600 valid columns in each of 5 layers;
    # 32 x 8 x 4 assignments, 1/32 of them held
    rows, held, valid = 32.0, 32.0, 32 * 7600 * 5.0
    st = counts.step(cfg, rows, held, valid, valid)
    touched = 4 * 12 * (1 - (11 / 12) ** 8)
    fixed = total - 2_113_929_216 - 20480 * 7168
    assert st["bytes"] == pytest.approx(
        2 * (fixed + touched * expert) + valid * 576 * 2)
    per_token = 5 * 2 * attention + 6 * 7168 * 18432 \
        + 4 * (2 * expert + 2 * 7168 * 384)
    assert st["flops"] == pytest.approx(
        per_token * rows + 2 * expert * held + 2 * 7168 * 20480 * rows
        + 2 * 64 * 1088 * valid)
    # a chunk of 512 tokens whose contexts average 4,096 in each layer
    tokens, held, valid = 512.0, 512 * 8 * 4 / 32, 512 * 4096 * 5.0
    ch = counts.chunk(cfg, tokens, held, valid, valid)
    end = 4096 + 256
    assert ch["bytes"] == pytest.approx(
        2 * (fixed + 4 * 12 * (1 - (11 / 12) ** 128) * expert)
        + 5 * end * 576 * 2)
    absorbed = 2 * 64 * 1088 * valid
    per_head = 2 * 64 * 320 * valid + 2 * 64 * 512 * 256 * 5 * (end - 512)
    assert per_head < absorbed
    assert ch["flops"] == pytest.approx(
        per_token * tokens + 2 * expert * held + 2 * 7168 * 20480 + per_head)
    # a chunk with few valid tokens far into a context (under 171 of them):
    # expanding every earlier column's keys and values costs more than the
    # absorbed pairs do, so the absorbed form is the cheaper
    few = counts.chunk(cfg, 64.0, 64.0, 0.0, 64 * 4096 * 5.0)
    assert few["flops"] == pytest.approx(
        per_token * 64 + 2 * expert * 64 + 2 * 7168 * 20480
        + 2 * 64 * 1088 * 64 * 4096 * 5.0)
