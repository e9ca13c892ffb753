"""The ring cache's packed plane layout (ISSUE 25).

A bf16/f32 ring plane is ``(B, ceil(N/g), C, g*H)``: ``g = 128 // H``
adjacent heads lie side by side on the minor dimension so that a token's
K/V for a row is contiguous on the device (on the chip the one-column
write then lands on the sublanes; ``tools/kv_layout_check.py`` reads
that back from the compiled program, these tests hold the mathematics).
Packed against unpacked planes, for head_dim 16 and 64 with an odd head
count and for head_dim 128 (``g = 1``: today's planes): the same logits
through a multi-chunk prefill, single steps and a ring wrap; the slot
loop's tokens equal to ``generate()``; the padded head's lanes zero and
never read; the KV data movers, the prefix cache and the session store
on packed planes.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.framework.tensor import Tensor, unwrap
from paddle_tpu.nn.layer import transformer as tfm
from paddle_tpu.nn.layer.transformer import (MultiHeadAttention,
                                             kv_heads_per_lane_row,
                                             pack_heads)
from paddle_tpu.profiler import ledger
from paddle_tpu.serving.prefix_cache import PrefixCache
from paddle_tpu.serving.sessions import SessionStore
from paddle_tpu.serving.slots import SlotLoop
from paddle_tpu.text.generation import Generator
from paddle_tpu.text.models.gpt import GPTConfig, GPTModel

V = 64
# (head_dim, heads): g = 8 with 5 heads padded to 8, g = 2 with 5 padded
# to 6 (GPT-2 XL's case: 25 -> 26), g = 1
CASES = [(16, 5), (64, 5), (128, 3)]
PACKED = CASES[:2]
IDS = [f"h{hd}x{n}" for hd, n in CASES]


def _gpt(hd, heads, seed=7):
    paddle.seed(seed)
    m = GPTModel(GPTConfig.tiny(vocab_size=V, hidden_size=hd * heads,
                                layers=2, heads=heads, seq=64))
    m.eval()
    return m


def _unpacked(monkeypatch):
    """Build today's (B, N, C, H) planes: the tests' way to the
    reference, not an option of the program."""
    monkeypatch.setattr(tfm, "kv_heads_per_lane_row", lambda head_dim: 1)


def _raw(cache):
    return [tuple(np.asarray(unwrap(p)) for p in c) for c in cache]


def _drive(m, ids, C=32):
    """A two-chunk prefill (8 + 8 columns) then three single steps;
    returns every stage's logits and the final planes."""
    cache = m.init_cache(ids.shape[0], C)
    start = Tensor(jnp.zeros((ids.shape[0],), jnp.int32))
    logits = []
    with paddle.no_grad():
        for lo, hi in ((0, 8), (8, 16), (16, 17), (17, 18), (18, 19)):
            out, cache = m.forward_cached(Tensor(ids[:, lo:hi]), cache,
                                          lo, start)
            logits.append(np.asarray(unwrap(out)))
    return logits, cache


@pytest.mark.parametrize("hd,heads", CASES, ids=IDS)
def test_plane_shape_packs_heads_along_the_minor_dim(hd, heads):
    g = kv_heads_per_lane_row(hd)
    assert g == {16: 8, 64: 2, 128: 1}[hd]
    groups = -(-heads // g)
    m = _gpt(hd, heads)
    cache = m.init_cache(2, 32)
    assert len(cache) == 2
    for c in cache:
        assert isinstance(c, MultiHeadAttention.RingCache)
        assert tuple(c.k.shape) == tuple(c.v.shape) == \
            (2, groups, 32, g * hd)
    if g == 1:                  # head_dim >= 128: today's planes
        assert tuple(cache[0].k.shape) == (2, heads, 32, hd)
    gen = Generator(m, site=f"pack:shape{hd}", seq_buckets=(8, 16, 32),
                    max_len=64)
    assert gen.kv_heads_per_lane_row() == g
    block = gen._block_avals(4, 8, 64)
    assert all(tuple(p.shape) == (1, groups, 8, g * hd)
               for c in block for p in c)
    # a width that does not divide a lane row is left alone
    assert kv_heads_per_lane_row(96) == kv_heads_per_lane_row(256) == 1


@pytest.mark.parametrize("hd,heads", CASES, ids=IDS)
def test_forward_cached_packed_equals_unpacked(hd, heads, monkeypatch):
    rng = np.random.RandomState(3)
    ids = rng.randint(1, V, (2, 19)).astype(np.int32)
    m = _gpt(hd, heads)
    g = kv_heads_per_lane_row(hd)
    got, cache = _drive(m, ids)
    _unpacked(monkeypatch)
    want, ref = _drive(m, ids)
    assert tuple(ref[0].k.shape) == (2, heads, 32, hd)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
        np.testing.assert_array_equal(a.argmax(-1), b.argmax(-1))
    # the first layer's K/V never passed through an attention: the
    # packed plane is the unpacked one rearranged, bit for bit
    for p, r in zip(_raw(cache)[0], _raw(ref)[0]):
        np.testing.assert_array_equal(p, np.asarray(pack_heads(r, g)))
    for c, rc in zip(_raw(cache)[1:], _raw(ref)[1:]):
        for p, r in zip(c, rc):
            np.testing.assert_allclose(p, np.asarray(pack_heads(r, g)),
                                       rtol=2e-5, atol=2e-5)
    # and against the plain, cache-free forward of the same tokens
    with paddle.no_grad():
        full = np.asarray(unwrap(m(Tensor(ids.astype(np.int64)))))
    np.testing.assert_allclose(got[1][:, -1], full[:, 15], rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(got[-1][:, 0], full[:, 18], rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("hd,heads", CASES, ids=IDS)
def test_ring_wrap_two_leg_write_on_packed_planes(hd, heads, monkeypatch):
    """A 4-wide block at traced position C - 2 wraps: ring_block_write's
    two legs land columns 6, 7, 0, 1 in a packed plane exactly where the
    unpacked plane has them, and the attention reads them alike."""
    C, T = 8, 4
    g = kv_heads_per_lane_row(hd)
    paddle.seed(11)
    mha = MultiHeadAttention(hd * heads, heads)
    mha.eval()
    rng = np.random.RandomState(5)
    fill = rng.randn(1, 6, hd * heads).astype(np.float32)
    x = rng.randn(1, T, hd * heads).astype(np.float32)

    def run():
        cache = mha.gen_ring_cache(1, C)
        with paddle.no_grad():
            _, cache = mha(Tensor(fill), cache=cache,
                           cache_position=Tensor(jnp.int32(0)))
            step = jax.jit(lambda xv, k, v, pos: tuple(
                unwrap(t) for t in _flat(mha(
                    Tensor(xv), cache=MultiHeadAttention.RingCache(
                        Tensor(k), Tensor(v)),
                    cache_position=Tensor(pos)))))
            before = np.asarray(unwrap(cache.k))
            return [before] + [np.asarray(a) for a in step(
                x, unwrap(cache.k), unwrap(cache.v), jnp.int32(C - 2))]

    before, out, k, v = run()
    _unpacked(monkeypatch)
    _, out_r, k_r, v_r = run()
    assert k_r.shape == (1, heads, C, hd)
    np.testing.assert_array_equal(k, np.asarray(pack_heads(k_r, g)))
    np.testing.assert_array_equal(v, np.asarray(pack_heads(v_r, g)))
    np.testing.assert_allclose(out, out_r, rtol=2e-5, atol=2e-5)
    # the wrap really happened: the block's tail replaced columns 0, 1,
    # its head filled 6, 7, and columns 2..5 kept what they held
    assert not np.array_equal(k[:, :, :2], before[:, :, :2])
    assert np.all(before[:, :, 6:] == 0) and np.any(k[:, :, 6:] != 0)
    np.testing.assert_array_equal(k[:, :, 2:6], before[:, :, 2:6])


def _flat(res):
    out, cache = res
    return out, cache.k, cache.v


@pytest.mark.parametrize("hd,heads", CASES, ids=IDS)
def test_slot_loop_tokens_equal_generate_position_by_position(hd, heads):
    """Multi-chunk prefills, steps, and more requests than slots (a
    slot is reused after its row retires) on packed planes."""
    m = _gpt(hd, heads)
    site = f"pack:slots{hd}"
    gen = Generator(m, site=site, seq_buckets=(8, 16, 32), max_len=64)
    oracle = Generator(m, seq_buckets=(8, 16, 32), max_len=64)
    loop = SlotLoop(gen, slots=2, cache_len=64, chunk=8)
    g = kv_heads_per_lane_row(hd)
    try:
        rng = np.random.RandomState(17)
        reqs = [(rng.randint(1, V, lp).tolist(), mn)
                for lp, mn in ((19, 5), (3, 6), (11, 4), (5, 6), (9, 3))]
        futs = [loop.submit(p, mn) for p, mn in reqs]
        for (p, mn), f in zip(reqs, futs):
            got = np.asarray(f.result(timeout=120)).reshape(-1)
            want = np.asarray(oracle.generate(
                np.asarray([p], np.int32),
                lengths=np.asarray([len(p)], np.int32),
                max_new_tokens=mn).numpy())[0]
            np.testing.assert_array_equal(got[:mn], want[:mn])
        assert loop.counters["retired"] == 5
        assert loop.stats()["kv_heads_per_lane_row"] == g
        evs = {e["kind"]: e for e in ledger.compile_events(site)}
        assert evs["generate_step"]["kv_heads_per_lane_row"] == g
        assert evs["generate_chunk"]["kv_heads_per_lane_row"] == g
    finally:
        loop.close()


@pytest.mark.parametrize("hd,heads", PACKED, ids=IDS[:2])
def test_padded_head_lanes_stay_zero_and_are_never_read(hd, heads):
    rng = np.random.RandomState(9)
    ids = rng.randint(1, V, (2, 20)).astype(np.int32)
    m = _gpt(hd, heads)
    g = kv_heads_per_lane_row(hd)
    _, cache = _drive(m, ids)
    used = (heads - (-(-heads // g) - 1) * g) * hd   # last group's lanes
    assert 0 < used < g * hd
    for c in _raw(cache):
        for p in c:
            assert np.any(p[:, -1, :19, :used] != 0)
            np.testing.assert_array_equal(p[:, -1, :, used:], 0)
    # poison the padded lanes: no logit may move by a bit
    poisoned = [MultiHeadAttention.RingCache(*(
        Tensor(unwrap(p).at[:, -1, :, used:].set(7.0)) for p in c))
        for c in cache]
    start = Tensor(jnp.zeros((2,), jnp.int32))
    with paddle.no_grad():
        clean, _ = m.forward_cached(Tensor(ids[:, 19:20]), cache, 19, start)
        dirty, after = m.forward_cached(Tensor(ids[:, 19:20]), poisoned,
                                        19, start)
    np.testing.assert_array_equal(np.asarray(unwrap(clean)),
                                  np.asarray(unwrap(dirty)))
    # the step's own write puts zeros, not garbage, into its column
    for c in _raw(after):
        for p in c:
            np.testing.assert_array_equal(p[:, -1, 19, used:], 0)


@pytest.mark.parametrize("hd,heads", PACKED, ids=IDS[:2])
def test_prefix_publish_and_restore_on_packed_planes(hd, heads):
    m = _gpt(hd, heads)
    gen = Generator(m, site=f"pack:pfx{hd}", seq_buckets=(8, 16, 32),
                    max_len=64)
    oracle = Generator(m, seq_buckets=(8, 16, 32), max_len=64)
    pc = PrefixCache(block_tokens=8, block_nbytes=4096, hbm_budget_mb=0.0)
    loop = SlotLoop(gen, slots=2, cache_len=64, chunk=8, prefix_cache=pc)
    try:
        rng = np.random.RandomState(23)
        prefix = rng.randint(1, V, 16).tolist()
        reqs = [(prefix + rng.randint(1, V, n).tolist(), 4)
                for n in (3, 5, 2, 6)]
        for p, mn in reqs:
            got = np.asarray(loop.submit(p, mn).result(timeout=120)) \
                .reshape(-1)
            want = np.asarray(oracle.generate(
                np.asarray([p], np.int32),
                lengths=np.asarray([len(p)], np.int32),
                max_new_tokens=mn).numpy())[0]
            np.testing.assert_array_equal(got[:mn], want[:mn])
        assert loop.counters["prefix_hit_tokens"] >= 16 * (len(reqs) - 1)
        assert pc.stats()["blocks"] >= 2
    finally:
        loop.close()


@pytest.mark.parametrize("hd,heads", PACKED, ids=IDS[:2])
def test_session_park_and_resume_on_packed_planes(hd, heads):
    m = _gpt(hd, heads)
    gen = Generator(m, site=f"pack:sess{hd}", seq_buckets=(8, 16, 32),
                    max_len=64)
    oracle = Generator(m, seq_buckets=(8, 16, 32), max_len=64)
    store = SessionStore()
    loop = SlotLoop(gen, slots=2, cache_len=64, chunk=8,
                    session_store=store)
    try:
        rng = np.random.RandomState(29)
        transcript = rng.randint(1, V, 10).tolist()
        for turn in range(3):
            snap = store.take("conv")
            assert (snap is not None) == (turn > 0)
            got = np.asarray(loop.submit(
                transcript, 4, session_id="conv",
                snapshot=snap).result(timeout=120)).reshape(-1)
            want = np.asarray(oracle.generate(
                np.asarray([transcript], np.int32),
                lengths=np.asarray([len(transcript)], np.int32),
                max_new_tokens=4).numpy())[0]
            np.testing.assert_array_equal(got[:4], want[:4])
            transcript = transcript + [int(t) for t in got[:4]] \
                + rng.randint(1, V, 2).tolist()
        assert loop.counters["parked"] >= 3
        assert loop.counters["restored"] >= 2
    finally:
        loop.close()
