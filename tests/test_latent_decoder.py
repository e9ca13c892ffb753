"""The latent-attention MoE decoder (text/models/latent_moe.py) at tiny
widths on the CPU, float32, seeded weights: the slot loop's chunks and steps
against the plain reference's full forward (benchmark/reference/dots3.py,
the one source of truth, which imports nothing of the program), the
absorbed against the per-head form, the expert share, dropless routing,
and the seam through which a model tells the Generator what its planes are.
"""
import contextlib
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

from benchmark.models import dots3 as bench_models          # noqa: E402
from benchmark.reference import dots3 as ref                 # noqa: E402
from paddle_tpu.framework.enforce import InvalidArgumentError  # noqa: E402
from paddle_tpu.framework.tensor import unwrap               # noqa: E402
from paddle_tpu.nn.layer.moe import DroplessMoE              # noqa: E402
from paddle_tpu.serving.slots import SlotLoop                # noqa: E402
from paddle_tpu.text.generation import Generator             # noqa: E402

# float32 on the CPU: the program (absorbed form, cache, chunks) and the
# reference (per-head, one pass) differ by summation order only; a served
# token may be the reference's second choice at a near-tie of that size
GAP_TOL = 1e-4
# prompts of 9-17 tokens in chunks of 4 (3-5 chunks, so a dead row's step
# would hit a column its own chunk wrote a ring length back), answers that
# carry every context past the selector's 6 and the window's 5 columns and
# the session's columns round the 8-column window planes several times
REQUESTS = [(9, 6), (13, 8), (5, 4), (17, 8), (7, 8), (11, 5), (14, 7),
            (16, 6)]


def _tiny():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "dots3-note-prev-ep8-serve.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "tests", "data",
                           "dots3_tiny.json")) as f:
        over = json.load(f)["over"]
    cfg["serve"].update(over.pop("serve"))
    cfg.update(over, num_hidden_layers=4, reference_pad=32)
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


def _serve(model, requests, chunk=4, columns=64, **loop_kw):
    gen = Generator(model, max_len=columns, seq_buckets=[columns])
    loop = SlotLoop(gen, slots=3, cache_len=columns, chunk=chunk, **loop_kw)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n, _ in requests]
    futs = [loop.submit(p, k) for p, (_, k) in zip(prompts, requests)]
    out = [np.asarray(f.result(timeout=300)) for f in futs]
    stats = loop.stats()
    loop.close()
    return prompts, out, stats


def _widest_gap(cfg, view, prompts, tokens):
    return max(float(jnp.max(ref.served_gaps(cfg, view, p, t)))
               for p, t in zip(prompts, tokens))


def test_slot_loop_equals_the_reference(served):
    """Prefill by chunks + decoding through SlotLoop, rows joining and
    retiring (8 requests over 3 slots), equals the reference's full
    forward; and the loop's counters say what ran."""
    cfg, model, view = served
    prompts, tokens, st = _serve(model, REQUESTS)
    assert _widest_gap(cfg, view, prompts, tokens) < GAP_TOL
    assert st["plane_kinds"] == ["latent+selector_key", "latent_window"]
    # this family's chunk still runs on a row cut out of the planes
    assert st["chunk_row"] == "sliced"
    # chunks of 4 queries are under the rule's threshold at these widths
    assert st["latent_form"] == {"step": "absorbed", "chunk": "absorbed"}
    moe_layers, k = 3, cfg["num_experts_per_tok"]
    assert st["moe_assignments"] == \
        (sum(n for n, _ in REQUESTS) + st["emitted_tokens"]) * k * moe_layers
    assert 0 < st["moe_assignments_held"] < st["moe_assignments"]
    assert 1 <= st["moe_expert_tokens_max"] <= 4 * k
    # the selector binds (contexts pass 6 columns) and the planes wrap
    assert st["attn_columns_selected"] < 0.7 * st["attn_columns_valid"]
    assert st["chunk_tokens"] == sum(n for n, _ in REQUESTS)
    assert st["window_wraps"] > len(REQUESTS)


_PROGRAMS = {}


def _prefill_beside_a_live_row(model, steps_between):
    """Row 1 prefills a 14-token prompt in 4 chunks of 4 on the slot
    loop's own schedule (admitted at column 20, so ``act = 24``; chunk
    ``k`` goes out once the frontier has passed ``act - 4 + k``), through
    the Generator's own chunk and step programs.  With ``steps_between``
    row 0 decodes meanwhile: the step at column 22 falls on ring slot 6 of
    the 8-column window planes, where row 1's chunk 1 has just put column
    14, which chunk 2's queries read.  Returns every chunk's logits."""
    gen = Generator(model, max_len=64, seq_buckets=[64])
    # the chunk program is the same whether or not the step takes the live
    # rows; only the step is built per setting (one compile each)
    masked = type(model).cached_forward_takes_rows
    if "chunk" not in _PROGRAMS:
        _PROGRAMS["chunk"] = jax.jit(gen._build_chunk(2, 4, 64))
    if steps_between and masked not in _PROGRAMS:
        _PROGRAMS[masked] = jax.jit(gen._build_step(2, 64, -1))
    chunk, step = _PROGRAMS["chunk"], _PROGRAMS.get(masked)
    state, cache = gen._state_args(), gen.init_slot_cache(2, 64)
    prompt = np.random.default_rng(0).integers(1, 96, 14).astype(np.int32)
    padded = np.concatenate([np.zeros(2, np.int32), prompt])
    logits = jnp.zeros((2, 96), jnp.float32)
    start, done = np.zeros(2, np.int32), np.array([False, True])
    live, outs = np.array([True, False]), []
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
def test_a_dead_row_must_not_write_into_a_plane_that_wraps(served, monkeypatch,
                                                           masked):
    """The slot loop's dead-column rule (slots.py header) is argued for
    planes as long as the session.  For a plane that wraps it does NOT
    hold, so the step passes the live rows to a model that asks for them
    and a dead row's write is masked; with the write left unmasked the
    prefilling row's window plane is garbled by its neighbour's steps."""
    _, model, _ = served
    alone = _prefill_beside_a_live_row(model, steps_between=False)
    monkeypatch.setattr(type(model), "cached_forward_takes_rows", masked)
    beside = _prefill_beside_a_live_row(model, steps_between=True)
    if masked:
        np.testing.assert_array_equal(beside, alone)
    else:
        # (0.01-0.1 of logits of spread ~1: a garbled column of one layer)
        assert np.abs(beside - alone).max() > 1e-3


@pytest.mark.parametrize("layer", [0, 2], ids=["full", "window"])
def test_absorbed_form_equals_the_per_head_form(served, layer):
    """One attention layer: ``forward`` (no cache, per-head keys and values
    from the latent) against ``forward_cached`` (absorbed; blocks of 3, 2
    and 4 tokens, then single steps), batch of 2, contexts past the
    selector's 6 and the window's 5 columns and round the 8-column plane:
    float32 rounding of another summation order."""
    _, model, _ = served
    attn = model.layers[layer].attn
    x = jax.random.normal(jax.random.key(2), (2, 20, 32))
    full = np.asarray(jax.jit(attn.forward)(x))

    @jax.jit
    def cached(xs, planes, pos):
        out, cache = attn.forward_cached(xs, type(planes0)(*planes), pos,
                                         jnp.zeros(2, jnp.int32))
        return out, tuple(unwrap(p) for p in cache)

    planes0 = attn.gen_ring_cache(2, 32)
    planes, pos, got = tuple(unwrap(p) for p in planes0), 0, []
    for n in (3, 2, 4) + (1,) * 11:
        out, planes = cached(x[:, pos:pos + n], planes, jnp.int32(pos))
        got.append(np.asarray(out))
        pos += n
    np.testing.assert_allclose(np.concatenate(got, 1), full, atol=2e-6)
    assert (planes[0].shape[2] == 8) == (layer == 2)

# -- the two cached forms ------------------------------------------------------

@contextlib.contextmanager
def forced_form(attn, form):
    """``attn.forward_cached`` traced in ``form`` whatever the block's
    width (the layer's own rule is ``cached_form``, a function of ``T``)."""
    attn.cached_form = lambda T, columns=None: form
    try:
        yield
    finally:
        del attn.cached_form


def cached_in_form(attn, form, x, blocks, start, columns):
    """``x [B, sum(blocks), hidden]`` appended block by block from column 0
    in ``form``: (the outputs, the planes after every block)."""
    planes0 = attn.gen_ring_cache(x.shape[0], columns)
    start = jnp.asarray(start, jnp.int32)

    def cached(xs, planes, pos):
        out, cache = attn.forward_cached(xs, type(planes0)(*planes), pos,
                                         start)
        return out, tuple(unwrap(p) for p in cache)

    planes, pos, outs, seen = tuple(unwrap(p) for p in planes0), 0, [], []
    with forced_form(attn, form):
        step = jax.jit(cached)
        for n in blocks:
            out, planes = step(x[:, pos:pos + n], planes, jnp.int32(pos))
            outs.append(np.asarray(out))
            seen.append([np.asarray(p) for p in planes])
            pos += n
    return np.concatenate(outs, 1), seen


def assert_forms_agree(attn, x, blocks, start, columns, tol=1e-5):
    """The absorbed and the per-head cached forms and the cache-less
    ``forward`` give one answer at every live position (a row's columns
    from its ``start`` on: position 0 of ``forward``), and the two cached
    forms write the same rows, bit for bit."""
    a, planes_a = cached_in_form(attn, "absorbed", x, blocks, start, columns)
    b, planes_b = cached_in_form(attn, "per_head", x, blocks, start, columns)
    for pa, pb in zip(planes_a, planes_b):
        for u, v in zip(pa, pb):
            np.testing.assert_array_equal(u, v)
    for row, s0 in enumerate(start):
        plain = np.asarray(jax.jit(attn.forward)(x[row:row + 1, s0:]))[0]
        np.testing.assert_allclose(a[row, s0:], plain, atol=tol)
        np.testing.assert_allclose(b[row, s0:], plain, atol=tol)
        np.testing.assert_allclose(a[row, s0:], b[row, s0:], atol=tol)


@pytest.mark.parametrize("layer,blocks,ties", [
    (0, (5, 3, 7, 4, 1, 1, 6, 1), False),
    (0, (5, 3, 7, 4, 1, 1, 6, 1), True),
    (2, (3, 2, 4, 1, 4, 1, 4, 4, 1, 4), False),
], ids=["full_selector", "full_selector_all_tied", "window_wraps"])
def test_the_cached_forms_agree(served, layer, blocks, ties):
    """One layer, three rows whose ``start`` differ and are no multiples of
    the 8-column ``attn_block``, blocks of 1-7 queries over 28 columns: a
    full layer whose selector binds (6 of up to 28 valid columns; with the
    selector's head weights zeroed EVERY score ties and the lowest columns
    are chosen), a window layer round its 8-column plane three times."""
    _, model, _ = served
    attn = model.layers[layer].attn
    x = jax.random.normal(jax.random.key(7), (3, 28, 32))
    held = attn.idx_w._value if ties else None
    if ties:
        attn.idx_w._value = jnp.zeros_like(held)
    try:
        assert_forms_agree(attn, x, blocks, (0, 3, 5), 32)
    finally:
        if ties:
            attn.idx_w._value = held
    assert attn.selects == (layer == 0)


@pytest.mark.parametrize("r_kv,d_n,d_v,T,form", [
    (512, 128, 128, 170, "absorbed"), (512, 128, 128, 171, "per_head"),
    (1024, 192, 128, 189, "absorbed"), (1024, 192, 128, 190, "per_head"),
    (512, 128, 128, 1, "absorbed"), (512, 128, 128, 512, "per_head"),
    (12, 8, 8, 24, "absorbed"), (12, 8, 8, 25, "per_head"),
    (8, 8, 8, 10 ** 6, "absorbed"),
], ids=lambda v: str(v))
def test_the_rule_picks_the_cheaper_form(r_kv, d_n, d_v, T, form):
    """``cached_form`` from the layer's own dimensions: the served full
    layers (512 / 128 / 128: per head from 171 queries on) and dots3's
    window layers (1024 / 192 / 128: from 190), the tiny ones of these
    tests (from 25), and a layer whose latent is no wider than a head's
    key and value together (never)."""
    from paddle_tpu.nn.layer.latent_attention import LatentAttention
    attn = LatentAttention(8, 1, d_n, 4, d_v, 4, r_kv, 1e4, index_topk=0)
    assert attn.cached_form(T) == form
    # the operations either form takes for T queries over one column
    absorbed = T * (2 * r_kv + 4)
    per_head = T * (d_n + 4 + d_v) + r_kv * (d_n + d_v)
    assert (per_head < absorbed) == (form == "per_head")


@pytest.fixture()
def on_one_tpu(monkeypatch):
    """The backend steered (on the CPU every site keeps the XLA loop) and
    the process's mesh held to one device; the kernel is interpreted."""
    from paddle_tpu.nn.functional import attention as A
    from paddle_tpu.parallel.mesh import MeshGuard, make_mesh
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    with MeshGuard(make_mesh({"dp": 1}, jax.devices()[:1])):
        yield


# the published width sets: heads, d_n, d_r, d_v, r_kv, window
DOTS3_FULL = (128, 128, 64, 128, 512, None)
KIMI = (64, 128, 64, 128, 512, None)
GLM5 = (64, 192, 64, 256, 512, None)
DOTS3_WINDOW = (64, 192, 64, 128, 1024, 513)
TINY = (4, 8, 4, 8, 12, None)


@pytest.mark.parametrize("name,widths,T,columns,where,form", [
    ("dots3_full", DOTS3_FULL, 512, 12288, "tpu", "per_head_fused"),
    ("dots3_full_step", DOTS3_FULL, 1, 12288, "tpu", "absorbed"),
    ("kimi", KIMI, 512, 16384, "tpu", "per_head_fused"),
    ("kimi_step", KIMI, 1, 16384, "tpu", "absorbed"),
    ("glm5", GLM5, 512, 24576, "tpu", "per_head_fused"),
    ("glm5_step", GLM5, 1, 24576, "tpu", "absorbed"),
    ("dots3_window", DOTS3_WINDOW, 512, 1024, "tpu", "per_head_fused"),
    ("dots3_window_step", DOTS3_WINDOW, 1, 1024, "tpu", "absorbed"),
    ("dots3_window_plane_unseen", DOTS3_WINDOW, 512, None, "tpu",
     "per_head_fused"),
    ("an_odd_number_of_heads", (63,) + KIMI[1:], 512, 16384, "tpu",
     "per_head"),
    ("tiny_model", TINY, 32, 96, "tpu", "per_head"),
    ("a_chunk_off_the_lanes", DOTS3_FULL, 500, 12288, "tpu", "per_head"),
    ("a_plane_the_block_does_not_divide", DOTS3_FULL, 512, 12000, "tpu",
     "per_head"),
    ("a_plane_unseen", DOTS3_FULL, 512, None, "tpu", "per_head_fused"),
    ("a_mesh_of_several_devices", DOTS3_FULL, 512, 12288, "mesh",
     "per_head"),
    ("the_cpu_backend", DOTS3_FULL, 512, 12288, "cpu", "per_head"),
], ids=lambda v: v if isinstance(v, str) and "_" in v else "")
def test_the_rule_picks_the_kernel_by_what_it_sees(name, widths, T, columns,
                                                   where, form, on_one_tpu,
                                                   monkeypatch):
    """``cached_form`` as a table: the per-head form is ONE kernel where
    the program is traced for a TPU, no mesh of several devices, at
    shapes on the lane grid (ops/pallas/latent_attention.py: all three
    published width sets and dots3's window layers); the XLA loop
    everywhere else; a step is absorbed wherever it is traced.  No flag,
    no name of a model: the layer's own dimensions and what ``jax``
    says."""
    from paddle_tpu import nn
    from paddle_tpu.nn.functional import attention as A
    from paddle_tpu.nn.layer.latent_attention import LatentAttention
    from paddle_tpu.parallel.mesh import MeshGuard, make_mesh
    H, d_n, d_r, d_v, r_kv, window = widths
    with jax.default_device(jax.devices("cpu")[0]):
        attn = LatentAttention(
            8, H, d_n, d_r, d_v, 4, r_kv, 1e4, window=window, index_topk=0,
            cache_block=min(T, 512), attn_block=512 if T > 32 else 32,
            weight_attr=nn.ParamAttr(initializer=nn.initializer.Constant(0.)))
    if where == "cpu":
        monkeypatch.setattr(A, "_on_tpu", lambda: False)
    if where == "mesh":
        with MeshGuard(make_mesh({"dp": 2}, jax.devices()[:2])):
            assert attn.cached_form(T, columns) == form
    else:
        assert attn.cached_form(T, columns) == form


def _aligned_layer(kind):
    """A layer at the least widths the kernel takes (every width on the
    lane grid, column blocks of 128), float32: ``kind`` "selector" (topk
    160 of up to 512 columns), "plain" (no selector) or "window" (129
    columns over a ring of 384)."""
    from paddle_tpu.nn.layer.latent_attention import LatentAttention
    import paddle_tpu as paddle
    paddle.seed(3)
    kw = {"selector": dict(index_heads=2, index_dim=64, index_topk=160),
          "plain": dict(index_topk=0),
          "window": dict(window=129, index_topk=0)}[kind]
    return LatentAttention(32, 2, 64, 64, 128, 16, 256, 1e4, cache_block=256,
                           attn_block=128, **kw)


@pytest.mark.parametrize("kind", ["selector", "plain", "window"])
def test_the_kernel_form_agrees_with_the_loop(kind, on_one_tpu):
    """``forward_cached`` in the XLA loop and in the kernel (interpreted),
    NOT forced: the layer's own rule picks the kernel for a block of 256
    under the steered backend and the loop on the CPU.  Three rows with
    other ``start``s, three blocks of 256 over 768 columns (the selector
    binding from the second block on, the window's ring of 384 wrapped):
    outputs to float32 rounding, the planes to the bit."""
    from paddle_tpu.nn.functional import attention as A
    attn = _aligned_layer(kind)
    assert attn.cached_form(256) == "per_head_fused"
    x = jax.random.normal(jax.random.key(5), (3, 768, 32))
    start = (0, 37, 300)
    fused, planes_f = cached_in_form(attn, "per_head_fused", x,
                                     (256, 256, 256), start, 768)
    loop, planes_l = cached_in_form(attn, "per_head", x, (256, 256, 256),
                                    start, 768)
    for pf, pl in zip(planes_f, planes_l):
        for u, v in zip(pf, pl):
            np.testing.assert_array_equal(u, v)
    for row, s0 in enumerate(start):
        np.testing.assert_allclose(fused[row, s0:], loop[row, s0:],
                                   atol=1e-5 * np.abs(loop).max())
    # the rule itself, asked where the program would be traced
    planes0 = attn.gen_ring_cache(1, 768)

    def cached(xs, planes):
        return attn.forward_cached(xs, type(planes0)(*planes), jnp.int32(0),
                                   jnp.zeros(1, jnp.int32))[0]
    text = jax.jit(cached).lower(
        x[:1, :256], tuple(unwrap(p) for p in planes0)).as_text(
            debug_info=True)
    assert "latent_attention_per_head" in text
    assert "/latent_attention/per_head/" in text
    with pytest.MonkeyPatch.context() as m:
        m.setattr(A, "_on_tpu", lambda: False)
        assert attn.cached_form(256) == "per_head"


@pytest.mark.parametrize("T", [24, 25])
def test_either_side_of_the_threshold(T):
    """A full layer with the selector at the tiny widths (threshold 24),
    NOT forced: a block of 24 queries is traced absorbed, one of 25 per
    head (its loop under a scope of its own), and both are ``forward``."""
    from paddle_tpu.text.models.latent_moe import latent_attention_of
    cfg = _tiny()
    cfg["serve"]["prefill_chunk"] = 32
    attn = latent_attention_of(bench_models.program_config(cfg), 1)
    x = jax.random.normal(jax.random.key(3), (2, T, 32))
    planes0 = attn.gen_ring_cache(2, 32)

    def cached(xs, planes):
        out, _ = attn.forward_cached(xs, type(planes0)(*planes), jnp.int32(0),
                                     jnp.zeros(2, jnp.int32))
        return out
    lowered = jax.jit(cached).lower(x, tuple(unwrap(p) for p in planes0))
    text = lowered.as_text(debug_info=True)
    assert "/latent_attention/" in text
    assert ("/latent_attention/per_head/" in text) == (T == 25)
    np.testing.assert_allclose(np.asarray(lowered.compile()(
        x, tuple(unwrap(p) for p in planes0))),
        np.asarray(jax.jit(attn.forward)(x)), atol=1e-5)


@pytest.fixture(scope="module")
def served_wide():
    """The tiny model built for chunks of 32 tokens, over the rule's
    threshold at its widths (window planes of 5 + 32 - 1 columns)."""
    from benchmark import harness
    cfg = _tiny()
    cfg["serve"]["prefill_chunk"] = 32
    mapped = bench_models.to_program(ref.init_weights(cfg, 5))
    return cfg, bench_models.build(cfg, mapped), harness.canonical_view(
        mapped, bench_models.leaf_ids(cfg))


def test_the_step_is_absorbed_and_a_wide_chunk_per_head(served_wide):
    """The slot loop's two programs as the Generator builds them: the step
    (one query a row) lowers with the ``latent_attention`` scope and no
    ``per_head`` under it, the 32-wide chunk with ``per_head`` in every
    layer, and the ledger events' ``extra`` say so."""
    _, model, _ = served_wide
    gen = Generator(model, max_len=64, seq_buckets=[64])
    texts = {}
    for what, prog in (("step", gen._step_program(3, 64)),
                       ("chunk", gen._chunk_program(3, 32, 64))):
        _key, _kind, fn, avals, extra, donate = prog
        texts[what] = jax.jit(fn, donate_argnums=donate).lower(
            *gen._state_avals(), *avals).as_text(debug_info=True)
        assert extra["latent_form"] == \
            {"step": "absorbed", "chunk": "per_head"}[what]
    assert "/latent_attention/" in texts["step"]
    assert "/per_head/" not in texts["step"]
    assert texts["chunk"].count("/latent_attention/per_head/while") >= 2
    assert model.latent_form(1) == "absorbed"
    assert model.latent_form(64) == model.latent_form(32) == "per_head"
    # (a GPT keeps no latent plane: no such fact in its events)
    from paddle_tpu.text.models.gpt import GPTConfig, GPTModel
    gpt = Generator(GPTModel(GPTConfig.tiny(vocab_size=32, hidden_size=16,
                                            layers=1, heads=2, seq=32)),
                    max_len=32, seq_buckets=[32])
    assert gpt.latent_form(1) is None
    assert "latent_form" not in gpt._step_program(2, 32)[4]


def test_wide_chunks_equal_the_reference(served_wide):
    """Prompts of 5-70 tokens prefilled in chunks of 32 (per head: full
    layers with the selector binding, window layers round their planes)
    and decoded by absorbed steps over the rows those chunks wrote, against
    the reference's full forward."""
    cfg, model, view = served_wide
    requests = [(40, 6), (33, 8), (5, 4), (70, 8), (64, 5), (17, 7)]
    prompts, tokens, st = _serve(model, requests, chunk=32, columns=128)
    assert _widest_gap(cfg, view, prompts, tokens) < GAP_TOL
    assert st["latent_form"] == {"step": "absorbed", "chunk": "per_head"}
    assert st["chunk_tokens"] == sum(n for n, _ in requests)


def test_the_slot_loop_serves_the_kernel_form(on_one_tpu):
    """The tiny model at the least widths the kernel takes (heads of 64 +
    64 / 128 over latents of 256, chunks of 256, column blocks of 128; a
    selector that binds past 160 columns, window planes of 129 + 255
    columns), traced under the steered backend: the chunk program carries
    the kernel's custom call under the per-head scope in every layer, the
    step is the absorbed XLA text, the ledger events, ``stats()`` and the
    executable cache's identity say ``per_head_fused``, and the served
    tokens are the reference's."""
    from benchmark import harness
    cfg = _tiny()
    cfg.update(num_attention_heads=2, qk_nope_head_dim=64,
               qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=256,
               index_head_dim=64, index_topk=160, sliding_window_size=129,
               swa_num_attention_heads=2, swa_qk_nope_head_dim=64,
               swa_qk_rope_head_dim=64, swa_v_head_dim=128,
               swa_kv_lora_rank=256, reference_pad=256)
    cfg["serve"].update(prefill_chunk=256, attn_block=128)
    mapped = bench_models.to_program(ref.init_weights(cfg, 5))
    model = bench_models.build(cfg, mapped)
    view = harness.canonical_view(mapped, bench_models.leaf_ids(cfg))
    assert model.latent_form(1) == "absorbed"
    assert model.latent_form(256) == "per_head_fused"
    gen = Generator(model, max_len=768, seq_buckets=[768])
    assert ("latent_form", "absorbed", "per_head_fused") \
        in gen._program_identity()
    texts = {}
    for what, prog in (("step", gen._step_program(3, 768)),
                       ("chunk", gen._chunk_program(3, 256, 768))):
        _key, _kind, fn, avals, extra, donate = prog
        texts[what] = jax.jit(fn, donate_argnums=donate).lower(
            *gen._state_avals(), *avals).as_text(debug_info=True)
        assert extra["latent_form"] == \
            {"step": "absorbed", "chunk": "per_head_fused"}[what]
    assert "latent_attention_per_head" not in texts["step"]
    assert "/per_head/" not in texts["step"]
    assert "/latent_attention/per_head/while" not in texts["chunk"]
    assert texts["chunk"].count("latent_attention_per_head") >= 4
    requests = [(300, 4), (470, 5), (130, 3)]
    prompts, tokens, st = _serve(model, requests, chunk=256, columns=768)
    assert _widest_gap(cfg, view, prompts, tokens) < GAP_TOL
    assert st["latent_form"] == {"step": "absorbed",
                                 "chunk": "per_head_fused"}
    assert st["attn_columns_selected"] < st["attn_columns_valid"]


def _moe_fn(layer):
    """``f(params, u)`` of a DroplessMoE, compiled once per shape: the
    parameters are arguments, so shares that differ in numbers only share
    one program."""
    from paddle_tpu.framework.functional import _bound_state

    @jax.jit
    def f(params, u):
        with _bound_state(layer, params, {}):
            return layer(u), layer.last_counts
    return f


@pytest.mark.parametrize("shares", [8, 2])
def test_expert_shares_add_up_to_the_uncut_layer(shares):
    """The share test: the routed parts of all the shares, with the shared
    expert counted once, equal the uncut 16-expert layer.  Share ``i`` is
    the layer that holds experts ``[0, n)`` of a router whose columns are
    rolled by ``i n``: the same program for every share."""
    whole = DroplessMoE(32, 16, 16, 3, shared=1)
    whole.router_bias.set_value(jnp.linspace(-0.1, 0.1, 16))
    w = {k: unwrap(v) for k, v in whole.named_parameters()}
    u = jax.random.normal(jax.random.key(3), (2, 11, 32))
    n = 16 // shares
    part = _moe_fn(DroplessMoE(32, 16, 16, 3, held=(0, n)))
    parts = [part({"router": jnp.roll(w["router"], -i * n, 1),
                   "router_bias": jnp.roll(w["router_bias"], -i * n),
                   **{k: w[k][i * n:(i + 1) * n]
                      for k in ("w_gate", "w_up", "w_down")}}, u)[0]
             for i in range(shares)]
    (uncut, made), shared = _moe_fn(whole)(w, u), whole.shared(u)
    np.testing.assert_allclose(sum(parts) + shared, uncut, atol=1e-6)
    assert float(jnp.abs(parts[0] - (uncut - shared)).max()) > 1e-4
    assert int(made[0]) == int(made[1]) == 2 * 11 * 3    # all held: none absent


@pytest.mark.parametrize("tokens,skewed", [(1, True), (40, True),
                                           (256, True), (256, False)])
def test_no_assignment_is_dropped(tokens, skewed):
    """8 of 64 experts held, 2 a token.  A skewed router sends every token
    to the same 2 held experts: whatever the number of rows, each is
    computed (no capacity, no drop).  At 256 tokens the 512 assignments
    pass the size from which each expert's rows are padded to 4 x the even
    share (32): evenly routed they take the one batched product, skewed
    they take the grouped products over the sorted rows: the same
    numbers either way, against every expert computed densely."""
    layer = DroplessMoE(32, 16, 64, 2, held=(0, 8))
    w = {k: unwrap(v) for k, v in layer.named_parameters()}
    if skewed:
        w["router"] = jnp.zeros((32, 64))
        w["router_bias"] = jnp.zeros(64).at[jnp.array([2, 5])].set(5.0)
    u = jax.random.normal(jax.random.key(4), (tokens, 32))
    from paddle_tpu.framework.functional import _bound_state
    with _bound_state(layer, w, {}):
        ids, wt = layer.route(u)
    want = 0.0
    for e in range(8):
        g, v, d = (w[n][e] for n in ("w_gate", "w_up", "w_down"))
        we = jnp.where(ids == e, wt, 0.0).sum(-1, keepdims=True)
        want = want + we * ((jax.nn.silu(u @ g) * (u @ v)) @ d)
    got, counts = _moe_fn(layer)(w, u)
    np.testing.assert_allclose(got, want, atol=2e-6)
    made, held, most = (int(c) for c in counts)
    assert made == 2 * tokens and held == int((ids < 8).sum())
    if skewed:
        assert (held, most) == (2 * tokens, tokens)
    else:
        assert 0 < most <= 32 and held > 32     # inside the padded rows


@pytest.mark.parametrize("feature", ["prefix_cache", "session_store"])
def test_kv_movers_refuse_planes_they_cannot_cut(served, feature):
    _, model, _ = served
    gen = Generator(model, max_len=64, seq_buckets=[64])
    with pytest.raises(InvalidArgumentError, match="latent"):
        SlotLoop(gen, slots=2, cache_len=64, chunk=4, **{feature: object()})


def test_handoff_refuses_planes_it_cannot_cut():
    from paddle_tpu.serving.cluster import handoff
    handoff.require_kv_planes(["kv", "kv_int8"])
    with pytest.raises(InvalidArgumentError, match="latent_window"):
        handoff.require_kv_planes(["kv", "latent_window"])


@pytest.mark.parametrize("flag,kind,g", [("bf16", "kv", 2), ("int8", "kv_int8", 1)])
def test_a_gpt_says_what_its_planes_are(flag, kind, g):
    """The cache class and the head packing come from the model's layers."""
    from paddle_tpu.framework import flags
    from paddle_tpu.text.models import GPTConfig, GPTModel
    old = flags.flag("kv_cache_dtype")
    flags.set_flags({"FLAGS_kv_cache_dtype": flag})
    try:
        gen = Generator(GPTModel(GPTConfig.tiny(hidden_size=128, heads=2)),
                        max_len=32, seq_buckets=[32])
        spec = gen.cache_spec(32)
        assert [s["kind"] for s in spec] == [kind, kind]
        assert gen.kv_heads_per_lane_row() == g and gen.plane_kinds() == [kind]
        assert gen.decode_count_names() == ()
    finally:
        flags.set_flags({"FLAGS_kv_cache_dtype": old})
