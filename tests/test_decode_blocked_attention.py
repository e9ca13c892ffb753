"""The decode step's attention over the live span of the ring (ISSUE 28).

``cached_attention`` with a decode ``window`` reads bf16/f32 ring planes
in blocks of ``DECODE_BLOCK`` columns, from the block of the lowest
``start`` to the block of the highest ``end``, and nothing outside.
Held here, for the head geometries of ``tests/test_kv_packed_layout.py``
(packed ``g`` = 8 and 2, unpacked ``g`` = 1): the blocked form against the
one-expression attention under the dense mask; the frontier on and
around a block edge; unlike ``start``s, on and off an edge, and every row
with ONE valid column; bf16 planes within one bfloat16 step of the
float32 attention; a row that is
not generating (``start = C``, where the slot loop keeps it) leaves the
span alone; no live row gives finite values; stale values outside a row's
window change nothing, and infinities in the blocks outside the span are
never read; a cache that is no multiple of the block; the slot loop's
tokens equal to ``generate()`` when the two sides cut their columns into
different blocks; which calls of ``cached_attention`` take the blocked
form; the int8 cache's read under a decode window (dequantize, then
the one expression under the mask); and the benchmark's reader of the
loop's ``attn_blocks_*``.

Since ISSUE 47 the blocked read has two forms, picked when the program is
traced (``decode_read_form``): the XLA loops over the union span, and on
the TPU ONE Pallas kernel that reads each row's own blocks
(ops/pallas/span_decode.py).  Held here with the backend steered and the
kernel interpreted: the kernel against the loops over the served head
geometries (two heads a lane row; grouped queries, 4 and 16 a cached
head), planes the block divides and does not, dead rows, one-column
spans, a row that starts inside the last block; what the rule admits; and
a slot-loop session with joins, leaves and a ring restart under both
forms, tokens and ``attn_blocks_*`` alike.
"""
import importlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.framework.tensor import Tensor, unwrap
from paddle_tpu.nn.functional import attention as A
from paddle_tpu.nn.layer.transformer import (dequantize_kv_rows,
                                             kv_heads_per_lane_row,
                                             pack_heads, quantize_kv_rows)
from paddle_tpu.serving.slots import SlotLoop
from paddle_tpu.text.generation import Generator
from paddle_tpu.text.models.gpt import GPTConfig, GPTModel

# (head_dim, heads) as in tests/test_kv_packed_layout.py
CASES = [(16, 5), (64, 5), (128, 3)]
IDS = [f"h{hd}x{n}" for hd, n in CASES]
BLOCK = 16          # the tests' block; C spans four of them
C = 4 * BLOCK
B = 5
TOL = dict(rtol=2e-5, atol=2e-6)
# bf16 operands against float32: one bfloat16 step at the outputs' size
BF16_TOL = dict(rtol=2e-2, atol=4e-3)
# each row's start below its frontier; ``one-column``: start == pos
UNLIKE = [0, 3, BLOCK, BLOCK + 5, 2 * BLOCK]
STARTS = {"unlike": UNLIKE, "one-column": [C] * B}


@pytest.fixture
def block16(monkeypatch):
    monkeypatch.setattr(A, "DECODE_BLOCK", BLOCK)


def _planes(hd, heads, seed=0, cols=C):
    """Queries, the planes as ``gen_ring_cache`` packs them, and the same
    K/V head by head."""
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.standard_normal((B, heads, 1, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, heads, cols, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, heads, cols, hd)), jnp.float32)
    g = kv_heads_per_lane_row(hd)
    return q, pack_heads(k, g), pack_heads(v, g), k, v


def _mask(start, end, cols=C):
    """The dense additive mask a ``[start, end)`` window spells."""
    col = jnp.arange(cols)
    valid = (col[None, :] >= start[:, None]) & (col[None, :] < end[:, None])
    return jnp.where(valid, 0.0, -1e30).astype(jnp.float32)[:, None, None, :]


def _dense(q, kp, vp, k, v, start, end):
    """Today's one-expression attention under the dense additive mask."""
    mask = _mask(start, end, kp.shape[2])
    if kp.shape[-1] != q.shape[-1]:
        return A._sdpa_packed_fn(q, kp, vp, mask)
    return A._sdpa_mask_fn(q, k, v, mask)


def _window(start, pos):
    start = jnp.asarray(start, jnp.int32)
    return start, jnp.full(start.shape, pos + 1, jnp.int32)


def blocked(q, k, v, start, end):
    return A._decode_span_fn(q, k, v, start, end,
                             block=A.decode_block(k.shape[2]))


@pytest.mark.parametrize("starts", sorted(STARTS))
@pytest.mark.parametrize("pos", [BLOCK - 1, BLOCK, C - 1],
                         ids=["edge-1", "edge", "last"])
@pytest.mark.parametrize("hd,heads", CASES, ids=IDS)
def test_blocked_equals_the_dense_mask(hd, heads, pos, starts, block16):
    """Rows with unlike starts, on a block edge (0, BLOCK) and off one;
    rows that hold exactly one valid column, the frontier's."""
    q, kp, vp, k, v = _planes(hd, heads)
    start, end = _window([min(s, pos) for s in STARTS[starts]], pos)
    got = blocked(q, kp, vp, start, end)
    want = _dense(q, kp, vp, k, v, start, end)
    assert got.shape == want.shape == q.shape
    np.testing.assert_allclose(got, want, **TOL)


@pytest.mark.parametrize("whole", [False, True],
                         ids=["windowed", "whole-ring"])
@pytest.mark.parametrize("hd,heads", CASES, ids=IDS)
def test_bf16_planes_within_one_step_of_float32(hd, heads, whole, block16):
    """What the cells serve: bf16 queries and planes (products in bf16,
    float32 scores and sums) against the dense mask in float32 over the
    same values."""
    q, kp, vp, k, v = (a.astype(jnp.bfloat16) for a in _planes(hd, heads, 8))
    start, end = _window([0] * B, C - 1) if whole \
        else _window(UNLIKE, 2 * BLOCK + 9)
    got = blocked(q, kp, vp, start, end)
    assert got.dtype == jnp.bfloat16 and got.shape == q.shape
    want = _dense(*(a.astype(jnp.float32) for a in (q, kp, vp, k, v)),
                  start, end)
    np.testing.assert_allclose(got.astype(jnp.float32), want, **BF16_TOL)


@pytest.mark.parametrize("hd,heads", CASES, ids=IDS)
def test_cache_that_is_no_multiple_of_the_block(hd, heads, block16):
    cols = 2 * BLOCK + 8
    q, kp, vp, k, v = _planes(hd, heads, seed=3, cols=cols)
    for pos in (BLOCK + 2, 2 * BLOCK, cols - 1):
        start, end = _window([0, 1, BLOCK, BLOCK + 1, 2], pos)
        np.testing.assert_allclose(blocked(q, kp, vp, start, end),
                                   _dense(q, kp, vp, k, v, start, end), **TOL)


@pytest.mark.parametrize("hd,heads", CASES, ids=IDS)
def test_the_modules_own_block_width(hd, heads):
    """``DECODE_BLOCK`` as it is spelled, on a cache of three blocks."""
    cols = 3 * A.DECODE_BLOCK
    q, kp, vp, k, v = _planes(hd, heads, seed=5, cols=cols)
    pos = 2 * A.DECODE_BLOCK + 7
    start, end = _window([A.DECODE_BLOCK + 9, cols, pos, cols,
                          2 * A.DECODE_BLOCK], pos)
    live = np.asarray(start) <= pos
    got = blocked(q, kp, vp, start, end)
    np.testing.assert_allclose(
        got[live], _dense(q, kp, vp, k, v, start, end)[live], **TOL)
    assert np.isfinite(np.asarray(got)).all()


def _poison(plane, value, dead):
    """``value`` at the columns ``dead [B, C]`` (or ``[C]``) of a plane."""
    dead = jnp.broadcast_to(jnp.asarray(dead), (plane.shape[0],
                                                plane.shape[2]))
    return jnp.where(dead[:, None, :, None], value, plane)


@pytest.mark.parametrize("hd,heads", CASES, ids=IDS)
def test_stale_values_outside_a_rows_window_change_nothing(hd, heads, block16):
    """1e4 above ``pos`` and below each row's ``start`` (what an earlier
    occupant and the steps' unmasked writes leave there): bit-equal."""
    q, kp, vp, _, _ = _planes(hd, heads, seed=1)
    pos = 2 * BLOCK + 3
    start, end = _window([BLOCK + 2, 2 * BLOCK, BLOCK, pos, 2 * BLOCK + 1], pos)
    col = jnp.arange(C)
    dead = (col[None, :] < start[:, None]) | (col[None, :] > pos)
    clean = blocked(q, kp, vp, start, end)
    stale = blocked(q, _poison(kp, 1e4, dead), _poison(vp, 1e4, dead),
                    start, end)
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(stale))


@pytest.mark.parametrize("hd,heads", CASES, ids=IDS)
def test_blocks_outside_the_span_are_not_read(hd, heads, block16):
    """A masked column that is READ multiplies its V by an exact zero,
    so an infinity there would come out as NaN: infinities in every
    block below the oldest live row's and above the frontier's leave the
    live rows bit-equal.  Rows 1 and 3 are not generating: their
    ``start`` is C, and the span is the live rows' alone."""
    q, kp, vp, _, _ = _planes(hd, heads, seed=2)
    pos = 2 * BLOCK + 3                             # frontier in block 2
    start, end = _window([BLOCK + 2, C, 2 * BLOCK, C, BLOCK + 9], pos)
    col = jnp.arange(C)
    outside = (col < BLOCK) | (col >= 3 * BLOCK)    # blocks 0 and 3
    live = np.asarray(start) <= pos
    clean = np.asarray(blocked(q, kp, vp, start, end))
    inf = np.asarray(blocked(q, _poison(kp, jnp.inf, outside),
                             _poison(vp, jnp.inf, outside), start, end))
    assert np.isfinite(inf).all()
    np.testing.assert_array_equal(clean[live], inf[live])
    # the span IS made from the starts it is handed: the same rows with
    # one start in block 0 read that block
    wide = blocked(q, _poison(kp, jnp.inf, outside),
                   _poison(vp, jnp.inf, outside), start.at[1].set(0), end)
    assert not np.isfinite(np.asarray(wide)).all()


@pytest.mark.parametrize("starts", [[C] * B, [C - 1] * B],
                         ids=["all-empty", "all-above-the-frontier"])
@pytest.mark.parametrize("hd,heads", CASES, ids=IDS)
def test_no_live_row_gives_finite_values(hd, heads, starts, block16):
    q, kp, vp, _, _ = _planes(hd, heads, seed=4)
    out = blocked(q, kp, vp, *_window(starts, BLOCK + 1))
    assert out.shape == q.shape and np.isfinite(np.asarray(out)).all()


def test_frontier_past_the_ring_reads_every_block(block16):
    """A scanned decode that wraps (``pos >= C``) sees every column at or
    above its start, as under the dense mask."""
    hd, heads = CASES[1]
    q, kp, vp, k, v = _planes(hd, heads, seed=6)
    start, end = _window([0, 3, BLOCK, 1, 2], C + 5)
    np.testing.assert_allclose(blocked(q, kp, vp, start, end),
                               _dense(q, kp, vp, k, v, start, end), **TOL)


def _gpt(hd, heads, seed=7):
    paddle.seed(seed)
    m = GPTModel(GPTConfig.tiny(vocab_size=64, hidden_size=hd * heads,
                                layers=2, heads=heads, seq=64))
    m.eval()
    return m


@pytest.mark.parametrize("hd,heads", CASES, ids=IDS)
def test_slot_tokens_equal_generate_across_unlike_block_cuts(hd, heads,
                                                             block16):
    """A slot row's columns lie elsewhere in the ring than the same
    prompt's in ``generate()``, so the two sides cut them into other
    blocks of 16; the tokens are the same, through slot reuse."""
    m = _gpt(hd, heads)
    gen = Generator(m, site=f"blocked:slots{hd}", seq_buckets=(8, 16, 32),
                    max_len=64)
    oracle = Generator(m, seq_buckets=(8, 16, 32), max_len=64)
    loop = SlotLoop(gen, slots=2, cache_len=64, chunk=8)
    try:
        rng = np.random.RandomState(23)
        reqs = [(rng.randint(1, 64, lp).tolist(), mn)
                for lp, mn in ((19, 7), (3, 9), (11, 6), (5, 8), (9, 5))]
        futs = [loop.submit(p, mn) for p, mn in reqs]
        for (p, mn), f in zip(reqs, futs):
            got = np.asarray(f.result(timeout=120)).reshape(-1)
            want = np.asarray(oracle.generate(
                np.asarray([p], np.int32),
                lengths=np.asarray([len(p)], np.int32),
                max_new_tokens=mn).numpy())[0]
            np.testing.assert_array_equal(got[:mn], want[:mn])
        c = loop.stats()
        assert 0 < c["attn_blocks_read"] < c["attn_blocks_total"] \
            == c["steps"] * (64 // BLOCK)
    finally:
        loop.close()


def test_which_calls_take_the_blocked_form(monkeypatch):
    """A decode window over bf16/f32 planes, packed or not; not a block
    of queries (no window), not the int8 cache."""
    calls = []
    real = A._decode_span_fn
    monkeypatch.setattr(
        A, "_decode_span_fn",
        lambda *a, **kw: calls.append(a[1].shape) or real(*a, **kw))
    for hd, heads in CASES:
        q, kp, vp, k, v = _planes(hd, heads)
        start, end = _window([0, 1, 2, 3, 4], 9)
        mask = Tensor(jnp.zeros((B, 1, 1, C), jnp.float32))
        win = (Tensor(start), Tensor(end))
        out = A.cached_attention(Tensor(q), Tensor(kp), Tensor(vp),
                                 attn_mask=mask, window=win)
        assert calls.pop() == kp.shape and not calls
        np.testing.assert_allclose(unwrap(out),
                                   _dense(q, kp, vp, k, v, start, end), **TOL)
        A.cached_attention(Tensor(q), Tensor(kp), Tensor(vp), attn_mask=mask)
        kq, ks = quantize_kv_rows(k)
        vq, vs = quantize_kv_rows(v)
        A.cached_attention(Tensor(q), Tensor(kq), Tensor(vq), attn_mask=mask,
                           window=win, k_scale=Tensor(ks), v_scale=Tensor(vs))
        assert not calls


@pytest.mark.parametrize("case", ["whole-ring", "windowed", "one-column",
                                  "bf16-query"])
def test_int8_read_under_a_window(case):
    """The int8 cache's planes are unpacked rows and scales: the step
    dequantizes them to the query's dtype and attends in one expression
    under the mask its window spells, over every column: held to the
    float32 attention over the same dequantized values."""
    hd, heads = CASES[1]
    q, _, _, k, v = _planes(hd, heads, seed=9)
    pos = C - 1 if case == "whole-ring" else 2 * BLOCK + 3
    start, end = _window({"whole-ring": [0] * B,
                          "one-column": [pos] * B}.get(case, UNLIKE), pos)
    tol = TOL
    if case == "bf16-query":
        q, tol = q.astype(jnp.bfloat16), BF16_TOL
    k8, ks = quantize_kv_rows(k)
    v8, vs = quantize_kv_rows(v)
    mask = _mask(start, end)
    got = unwrap(A.cached_attention(
        Tensor(q), Tensor(k8), Tensor(v8), attn_mask=Tensor(mask),
        window=(Tensor(start), Tensor(end)), k_scale=Tensor(ks),
        v_scale=Tensor(vs)))
    assert got.dtype == q.dtype and got.shape == q.shape
    want = A._sdpa_mask_fn(*(a.astype(jnp.float32) for a in (
        q, dequantize_kv_rows(k8, ks, dtype=q.dtype),
        dequantize_kv_rows(v8, vs, dtype=q.dtype))), mask)
    np.testing.assert_allclose(got.astype(jnp.float32), want, **tol)


@pytest.mark.parametrize("stats,value", [
    ({"steps": 10, "attn_blocks_read": 30, "attn_blocks_total": 80}, 37.5),
    ({"steps": 10, "attn_blocks_read": 0, "attn_blocks_total": 80}, 0.0),
    ({"steps": 0, "attn_blocks_read": 0, "attn_blocks_total": 0}, None),
    ({"steps": 10, "chunks": 3}, None),       # the parent: no such counter
    (None, None),
])
def test_attn_span_read_pct_reader(stats, value):
    reader = importlib.import_module(
        "benchmark.layer_metrics.attn_span_read_pct")
    got = reader.compute({"counters": {"slot_loop": stats} if stats else {}})
    assert got == (pytest.approx(value) if value is not None else None)


# -- the per-row kernel (ISSUE 47) -------------------------------------------------

@pytest.fixture
def on_one_tpu(monkeypatch):
    """The backend steered (on the CPU every read keeps the XLA loops) and
    the process's mesh held to one device; the kernel is interpreted."""
    from paddle_tpu.parallel.mesh import MeshGuard, make_mesh
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    with MeshGuard(make_mesh({"dp": 1}, jax.devices()[:1])):
        yield


# (heads a lane row, queries a cached head, query heads): GPT-2 XL's 25
# heads of 64 on 13 lane rows; lfm2's 32 over 8 of 64; nemotron3's 32
# over 2 of 128
GEOMETRIES = [(2, 1, 25), (2, 4, 32), (1, 16, 32)]
# name: (columns, starts, frontier) in blocks of ``K`` columns; a start at
# the plane's length is a row that is not generating
def _rows(K):
    cols = 4 * K
    return {
        "unlike-starts": (cols, [0, cols, K + 1, 2 * K + 8, 2 * K + 1],
                          2 * K + 8),
        # (half a block over: the last block starts at ``cols - K``)
        "plane-the-block-does-not-divide": (
            cols + K // 2, [0, cols + K // 2, K + 1, cols + 2, cols + 6],
            cols + 6),
        "every-row-dead": (cols, [cols] * B, K + 1),
        "one-column": (cols, [2 * K + 3] * B, 2 * K + 3),
        "starts-inside-the-last-block": (cols, [3 * K + 2, cols, cols - 1, 0,
                                                3 * K], cols - 1),
        "frontier-past-the-ring": (cols, [0, 3, K, cols, 2], cols + 5),
    }


def _grouped(g, rep, n, cols, dtype, seed=11):
    """Queries ``[B, n, 1, 128 / g]`` and planes of ``n / rep`` cached
    heads, ``g`` a lane row."""
    hd = 128 // g
    groups = -(-(-(-n // rep)) // g)
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.standard_normal((B, n, 1, hd)), dtype)
    k, v = (jnp.asarray(rng.standard_normal((B, groups, cols, g * hd)),
                        dtype) for _ in range(2))
    return q, k, v


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("rows", sorted(_rows(BLOCK)))
@pytest.mark.parametrize("g,rep,n", GEOMETRIES,
                         ids=[f"g{g}rep{r}n{n}" for g, r, n in GEOMETRIES])
def test_the_per_row_kernel_equals_the_loops(g, rep, n, rows, dtype,
                                             on_one_tpu, monkeypatch):
    # a block is a whole number of the dtype's tiles, half a block too
    block = BLOCK if dtype == jnp.float32 else 2 * BLOCK
    cols, starts, pos = _rows(block)[rows]
    q, k, v = _grouped(g, rep, n, cols, dtype)
    start, end = _window(starts, pos)
    took = []
    real = A._decode_rows_fn
    monkeypatch.setattr(A, "_decode_rows_fn",
                        lambda *a, **kw: took.append(kw) or real(*a, **kw))
    got = A.decode_attention(q, k, v, start, end, block=block, rep=rep)
    assert took == [{"block": block, "rep": rep}]
    want = A._decode_span_fn(q, k, v, start, end, block=block, rep=rep)
    assert got.shape == want.shape == q.shape and got.dtype == q.dtype
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.isfinite(got).all()
    live = np.asarray(start) < min(pos + 1, cols)
    assert live.sum() == sum(s < cols for s in starts)
    np.testing.assert_allclose(
        got[live], want[live], **(TOL if dtype == jnp.float32 else BF16_TOL))


def test_the_kernel_reads_nothing_outside_a_rows_own_blocks(block16,
                                                            on_one_tpu):
    """Infinities in every block that holds no valid column OF THAT ROW
    (inside the union span too, where the loops read them for every row):
    the rows come out bit-equal, and a row that generates nothing reads
    zeros."""
    g, rep, n = GEOMETRIES[0]
    q, k, v = _grouped(g, rep, n, C, jnp.float32, seed=12)
    pos = 3 * BLOCK + 2
    start, end = _window([0, C, 2 * BLOCK + 1, 3 * BLOCK, BLOCK], pos)
    col = jnp.arange(C)
    other = col[None, :] // BLOCK < start[:, None] // BLOCK
    clean = np.asarray(A.decode_attention(q, k, v, start, end, block=BLOCK))
    inf = np.asarray(A.decode_attention(
        q, _poison(k, jnp.inf, other), _poison(v, jnp.inf, other), start,
        end, block=BLOCK))
    np.testing.assert_array_equal(clean, inf)
    assert not clean[1].any() and clean[0].any()
    loops = np.asarray(A._decode_span_fn(
        q, _poison(k, jnp.inf, other), _poison(v, jnp.inf, other), start,
        end, block=BLOCK))
    assert not np.isfinite(loops).all()


def test_what_takes_the_per_row_kernel(monkeypatch, block16):
    """The TPU, one device, no mask of chosen blocks, bf16 / f32 planes of
    whole lane rows whose blocks start on a tile's edge: from what the
    code can see, no flag."""
    from paddle_tpu.parallel.mesh import MeshGuard, make_mesh
    plane, f32, bf16 = (B, 13, C, 128), jnp.float32, jnp.bfloat16
    with MeshGuard(make_mesh({"dp": 1}, jax.devices()[:1])):
        assert A.decode_read_form(plane, bf16, BLOCK) == "span"     # the CPU
        monkeypatch.setattr(A, "_on_tpu", lambda: True)
        assert A.decode_read_form(plane, bf16, BLOCK) == "per_row"
        assert A.decode_read_form(plane, f32, BLOCK) == "per_row"
        assert A.decode_read_form(plane, f32, 8) == "per_row"
        for shape, dtype, block, keep in [
                (plane, bf16, BLOCK, jnp.ones((B, 13, 1, C), bool)),
                (plane, jnp.int8, BLOCK, None),
                ((B, 25, C, 80), bf16, BLOCK, None),     # half a lane row
                (plane, bf16, 8, None),                  # half a bf16 tile
                ((B, 13, C + 8, 128), bf16, BLOCK, None),
                ((B, 13, 8, 128), f32, BLOCK, None),     # wider than it
                # a row's float32 scores would not wait in VMEM
                ((2, 13, 65536, 128), bf16, 128, None)]:
            assert A.decode_read_form(shape, dtype, block, keep) == "span"
    if len(jax.devices()) > 1:
        with MeshGuard(make_mesh({"dp": 2}, jax.devices()[:2])):
            assert A.decode_read_form(plane, bf16, BLOCK) == "span"


@pytest.mark.parametrize("form", ["span", "per_row"])
def test_a_session_serves_the_same_tokens_under_both_read_forms(
        form, block16, monkeypatch, request):
    """Joins, leaves and a ring restart in a loop of 2 slots over 32
    columns: the tokens are ``generate()``'s under either form, and the
    counters are the host's arithmetic on what each step was handed: the
    generating rows' own blocks of ``slots x blocks`` where the step
    reads per row, the span from the oldest generating row's ``start`` of
    the plane's blocks where it reads the span."""
    if form == "per_row":
        request.getfixturevalue("on_one_tpu")
    hd, heads = CASES[0]
    m = _gpt(hd, heads, seed=41)
    gen = Generator(m, site=f"read_form:{form}", seq_buckets=(8, 16, 32),
                    max_len=64)
    monkeypatch.setattr(A, "_on_tpu", lambda: False)
    oracle = Generator(m, seq_buckets=(8, 16, 32), max_len=64)
    want = [np.asarray(oracle.generate(
        np.asarray([p], np.int32), lengths=np.asarray([len(p)], np.int32),
        max_new_tokens=mn).numpy())[0][:mn]
        for p, mn in SESSION]
    monkeypatch.setattr(A, "_on_tpu", lambda: form == "per_row")
    assert gen.step_read(32) == form
    loop = SlotLoop(gen, slots=2, cache_len=32, chunk=8)
    seen, real = [], loop._step

    def step(*args):
        start, _finished, active, _joined, pos = args[-5:]
        seen.append((int(pos), np.array(start), np.array(active)))
        return real(*args)

    loop._step = step
    try:
        futs = [loop.submit(p, mn) for p, mn in SESSION]
        for f, w in zip(futs, want):
            np.testing.assert_array_equal(
                np.asarray(f.result(timeout=120)).reshape(-1)[:len(w)], w)
    finally:
        loop.close()
    c = loop.stats()
    # the form is a fact of the step program: in ``stats()``, and in the
    # ``generate_step`` event's ``extra`` (not in the chunk's)
    from paddle_tpu.profiler import ledger
    evs = {e["kind"]: e for e in ledger.compile_events(gen.site)}
    assert c["step_read"] == evs["generate_step"]["step_read"] == form
    assert "step_read" not in evs["generate_chunk"]
    assert c["session_resets"] >= 1 and c["steps"] == len(seen)
    own = sum(int((pos // BLOCK + 1 - start[active] // BLOCK).sum())
              for pos, start, active in seen)
    span = sum(pos // BLOCK + 1 - int(start[active].min()) // BLOCK
               for pos, start, active in seen)
    blocks = 32 // BLOCK
    if form == "per_row":
        assert (c["attn_blocks_read"], c["attn_blocks_total"]) \
            == (own, c["steps"] * 2 * blocks)
    else:
        assert (c["attn_blocks_read"], c["attn_blocks_total"]) \
            == (span, c["steps"] * blocks)
    # one generating row of two reads half of what the span charges
    assert own < 2 * span
    reader = importlib.import_module(
        "benchmark.layer_metrics.attn_span_read_pct")
    assert reader.compute({"counters": {"slot_loop": c}}) == pytest.approx(
        100.0 * c["attn_blocks_read"] / c["attn_blocks_total"])


SESSION = [([7, 3, 9, 1, 5, 2], 4), ([4, 8, 6, 2, 9, 1], 20),
           ([1, 2, 3, 4, 5, 6], 20), ([9, 9, 1], 6)]
