"""Static-shape KV-cache generate() tests.

Correctness (incremental ring-cache forward == naive full-forward
recompute; beam == a hand-rolled NumPy beam search), the two-executable
compile contract proven through the recompile ledger (zero per-token /
repeat-call compiles), bucket/ladder behavior, eos freezing, the hapi
Model.generate surface, and the decode flags' registration hygiene."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework.enforce import (InvalidArgumentError,
                                          OutOfRangeError)
from paddle_tpu.framework.flags import (define_flag, flag, flags_restore,
                                        flags_snapshot, set_flags)
from paddle_tpu.profiler import ledger
from paddle_tpu.text.generation import Generator, generate
from paddle_tpu.text.models.gpt import GPTConfig, GPTModel

V, HID, HEADS, LAYERS = 64, 32, 2, 2


def _model(seed=7, vocab=V, seq=64):
    paddle.seed(seed)
    m = GPTModel(GPTConfig.tiny(vocab_size=vocab, hidden_size=HID,
                                layers=LAYERS, heads=HEADS, seq=seq))
    m.eval()
    return m


def _prompts(rng, b, l):
    return rng.randint(2, V, (b, l)).astype(np.int64)


def _padded_forward(m, pad=16):
    """``ids [1, pad] -> logits`` of ``m``, compiled once per model."""
    import jax
    from paddle_tpu.framework.functional import _bound_state, layer_state
    from paddle_tpu.framework.tensor import Tensor, unwrap
    fn = getattr(m, "_test_padded_forward", None)
    if fn is None:
        params, buffers = layer_state(m)

        @jax.jit
        def forward(ids):
            with _bound_state(m, params, buffers):
                return unwrap(m(Tensor(ids)))
        fn = m._test_padded_forward = forward
    return fn


def _naive_greedy(m, ids_row, steps, pad=16):
    """Reference: recompute the FULL forward per token and take argmax —
    the O(T^2) path the KV cache replaces.  The sequence is right-padded
    to ``pad`` positions (causal, so what follows a position cannot reach
    it): one compiled program per model, where the eager forward compiled
    every op anew for every length."""
    forward = _padded_forward(m, pad)
    seq = list(ids_row)
    for _ in range(steps):
        ids = np.zeros((1, pad), np.int64)
        ids[0, :len(seq)] = seq
        logits = np.asarray(forward(ids))
        seq.append(int(np.argmax(logits[0, len(seq) - 1])))
    return np.asarray(seq[len(ids_row):])


# -- correctness -------------------------------------------------------------

def test_greedy_matches_full_forward_recompute():
    m = _model()
    rng = np.random.RandomState(0)
    ids = _prompts(rng, 3, 5)
    lens = np.array([5, 3, 4])
    gen = Generator(m, seq_buckets=(8, 16), max_len=32)
    out = np.asarray(gen.generate(ids, lengths=lens,
                                  max_new_tokens=6).numpy())
    assert out.shape == (3, 6) and out.dtype == np.int32
    for b in range(3):
        np.testing.assert_array_equal(
            out[b], _naive_greedy(m, ids[b, :lens[b]], 6))


def test_results_are_bucket_and_batch_invariant():
    """Left-padding + the validity mask make each row independent of its
    batch mates AND of the prompt bucket it padded to — the property
    that lets serving pack mixed requests without changing results."""
    m = _model(seed=11)
    rng = np.random.RandomState(1)
    p = rng.randint(2, V, (1, 4)).astype(np.int64)
    small = Generator(m, seq_buckets=(4, 16), max_len=32)
    big = Generator(m, seq_buckets=(16,), max_len=32)
    a = np.asarray(small.generate(p, max_new_tokens=5).numpy())
    b = np.asarray(big.generate(p, max_new_tokens=5).numpy())
    np.testing.assert_array_equal(a, b)       # bucket-invariant
    batch = np.concatenate([p, rng.randint(2, V, (2, 4))], axis=0)
    c = np.asarray(small.generate(batch, max_new_tokens=5).numpy())
    np.testing.assert_array_equal(c[0], a[0])  # batch-invariant


def test_beam_matches_numpy_beam_search():
    """generate(beam_size=K) against a hand-rolled NumPy beam search over
    the same full-forward log-probs (beam_search_step + parent-gather
    semantics, incubate BeamSearchDecoder discipline)."""
    m = _model(seed=3)
    rng = np.random.RandomState(2)
    B, L, steps, K, EOS = 2, 4, 5, 3, 1
    ids = _prompts(rng, B, L)
    gen = Generator(m, seq_buckets=(4, 16), max_len=16)
    paths, scores = gen.generate(ids, max_new_tokens=steps, beam_size=K,
                                 eos_token_id=EOS)
    paths = np.asarray(paths.numpy())
    scores = np.asarray(scores.numpy())
    assert paths.shape == (B, K, steps) and scores.shape == (B, K)

    def logp_of(seq):
        lg = m(paddle.to_tensor(np.asarray([seq], np.int64))) \
            .numpy()[0, -1].astype(np.float64)
        lg = lg - lg.max()
        return lg - np.log(np.exp(lg).sum())

    for b in range(B):
        prompt = list(ids[b])
        seqs = [list(prompt) for _ in range(K)]
        sc = np.array([0.0] + [-1e9] * (K - 1))
        pre = np.full((K,), -2)
        for _ in range(steps):
            total = np.empty((K, V))
            for k in range(K):
                if pre[k] == EOS:        # finished beams propose only EOS
                    total[k] = -np.inf
                    total[k, EOS] = sc[k]
                else:
                    total[k] = sc[k] + logp_of(seqs[k])
            top = np.argsort(-total.reshape(-1), kind="stable")[:K]
            parents, toks = top // V, top % V
            sc = total.reshape(-1)[top]
            seqs = [seqs[p] + [int(t)] for p, t in zip(parents, toks)]
            pre = toks
        ref = np.array([s[len(prompt):] for s in seqs])
        np.testing.assert_array_equal(paths[b], ref)
        np.testing.assert_allclose(scores[b], sc, atol=1e-4)


def test_eos_freezes_greedy_rows():
    """Once a row emits eos, every later step emits eos at no state
    change (the finished mask in the scanned step)."""
    m = _model(seed=5)
    rng = np.random.RandomState(3)
    ids = _prompts(rng, 4, 4)
    gen = Generator(m, seq_buckets=(4, 16), max_len=32)
    free = np.asarray(gen.generate(ids, max_new_tokens=8).numpy())
    eos = int(free[0, 2])                 # force an early hit on row 0
    out = np.asarray(gen.generate(ids, max_new_tokens=8,
                                  eos_token_id=eos).numpy())
    for b in range(4):
        hits = np.where(out[b] == eos)[0]
        if len(hits):
            assert (out[b, hits[0]:] == eos).all()


# -- the two-executable compile contract -------------------------------------

def test_ledger_shows_exactly_prefill_plus_decode():
    m = _model(seed=9)
    gen = Generator(m, seq_buckets=(8, 16), max_len=32,
                    site="generate:ledger-test")
    ledger.clear()
    ids = _prompts(np.random.RandomState(4), 2, 5)
    gen.generate(ids, max_new_tokens=4)
    evs = ledger.compile_events("generate:ledger-test")
    # a FULL generate() call = exactly the warm-up set: one prefill
    # executable + one scanned-decode executable — zero per-token compiles
    assert [e["kind"] for e in evs] == ["generate_prefill",
                                       "generate_decode"]
    assert evs[0]["prompt"] == 8 and evs[0]["cache"] == 16
    assert evs[1]["steps"] == 4 and evs[1]["beam"] == 1
    # steady state: same buckets -> zero new compiles, 10 more calls
    for _ in range(3):
        gen.generate(ids, max_new_tokens=4)
    assert len(ledger.compile_events("generate:ledger-test")) == 2
    # a new bucket (longer prompt) is a NEW warm-up pair, not a per-token
    # compile: exactly two more events
    long_ids = _prompts(np.random.RandomState(5), 2, 12)
    gen.generate(long_ids, max_new_tokens=4)
    evs = ledger.compile_events("generate:ledger-test")
    assert len(evs) == 4 and evs[2]["prompt"] == 16


def test_is_compiled_and_refresh_state_keep_executables():
    m = _model(seed=13)
    gen = Generator(m, seq_buckets=(8,), max_len=16)
    ids = _prompts(np.random.RandomState(6), 1, 3)
    gen.generate(ids, max_new_tokens=4)
    assert gen.is_compiled("prefill", 1, P=8, C=16)
    assert gen.is_compiled("decode", 1, C=16, steps=4, beam=1)
    assert not gen.is_compiled("decode", 1, C=16, steps=4, beam=2)
    n = len(ledger.compile_events(gen.site))
    # weight update flows through WITHOUT recompiling
    packed, start = gen.pack_prompts([ids[0]], 8)
    _, logits_before = gen.prefill(packed, start, 16)
    w = m.wte.weight
    w.set_value(paddle.to_tensor(
        w.numpy() + np.random.RandomState(0).randn(*w.shape)
        .astype("float32")))
    gen.refresh_state()
    _, logits_after = gen.prefill(packed, start, 16)
    gen.generate(ids, max_new_tokens=4)
    assert len(ledger.compile_events(gen.site)) == n
    assert not np.allclose(np.asarray(logits_before),
                           np.asarray(logits_after))


# -- validation / bucketing --------------------------------------------------

def test_bucket_and_length_validation():
    m = _model(seed=15)
    gen = Generator(m, seq_buckets=(8, 16), max_len=16)
    assert gen.prefill_bucket(3) == 8 and gen.prefill_bucket(9) == 16
    assert gen.cache_bucket(8, 4) == 16
    with pytest.raises(OutOfRangeError):
        gen.prefill_bucket(40)
    with pytest.raises(OutOfRangeError):
        gen.cache_bucket(16, 4)           # 20 > max_len
    rng = np.random.RandomState(7)
    with pytest.raises(InvalidArgumentError):
        gen.generate(_prompts(rng, 1, 4)[0])          # 1-D input
    with pytest.raises(InvalidArgumentError):
        gen.generate(_prompts(rng, 2, 4), lengths=[5, 1])  # len > L
    with pytest.raises(InvalidArgumentError):
        gen.generate(_prompts(rng, 1, 4), max_new_tokens=0)
    with pytest.raises(OutOfRangeError):
        # prompt + steps exceeds max_position_embeddings (=64 for tiny)
        Generator(m, seq_buckets=(64,), max_len=128).generate(
            _prompts(rng, 1, 60), max_new_tokens=10)
    with pytest.raises(InvalidArgumentError):
        Generator(paddle.nn.Linear(4, 4))   # no decoding contract


def test_module_level_generate_and_model_surface():
    m = _model(seed=17)
    rng = np.random.RandomState(8)
    ids = _prompts(rng, 2, 4)
    snap = flags_snapshot()
    try:
        set_flags({"FLAGS_decode_buckets": "8,16",
                   "FLAGS_decode_max_len": 32})
        out = generate(m, ids, max_new_tokens=3)       # memoized Generator
        again = m.generate(ids, max_new_tokens=3)      # GPTModel method
        hapi = paddle.Model(m).generate(ids, max_new_tokens=3)
        np.testing.assert_array_equal(np.asarray(out.numpy()),
                                      np.asarray(again.numpy()))
        np.testing.assert_array_equal(np.asarray(out.numpy()),
                                      np.asarray(hapi.numpy()))
        assert m._paddle_tpu_generator is not None
    finally:
        flags_restore(snap)


# -- flags hygiene (satellite) -----------------------------------------------

def test_decode_flags_registered_with_defaults():
    assert flag("decode_max_len") == 1024
    assert "16" in str(flag("decode_buckets"))


def test_decode_flags_idempotent_reregistration():
    # same default: no-op; different default: loud error
    define_flag("decode_max_len", 1024, "dup")
    define_flag("decode_buckets", "16,32,64,128,256,512,1024", "dup")
    with pytest.raises(ValueError):
        define_flag("decode_buckets", "16,32", "conflicting")
    with pytest.raises(ValueError):
        define_flag("decode_max_len", 2048, "conflicting")


def test_decode_flags_snapshot_restore_roundtrip():
    snap = flags_snapshot()
    set_flags({"FLAGS_decode_buckets": "4,8",
               "FLAGS_decode_max_len": 8})
    assert flag("decode_max_len") == 8
    # the generator reads the mutated flags...
    m = _model(seed=19)
    gen = Generator(m)
    assert gen.seq_buckets == [4, 8]
    flags_restore(snap)
    assert "16" in str(flag("decode_buckets"))
    assert flag("decode_max_len") == 1024
    with pytest.raises(ValueError):
        set_flags({"FLAGS_decode_buckets": "0,4"})     # validator


# -- the prefill chunk writes its block into the planes in place -------------
#
# A model whose cached forward takes the row (``cached_forward_takes_row``)
# gets the full donated planes and the row index; every other model's row is
# cut out of the planes and spliced back.  The two programs must agree to
# the bit: the planes they hand back (the chunk's row and every other row)
# and the chunk's last-column logits.

_S, _T, _C = 5, 8, 32
# (dtype, head_dim, heads, FLAGS_kv_cache_dtype; "bf16" = the model's own
# dtype): packed planes with two heads a
# lane row (3 heads pad to 4) and plain ones, float32 and bfloat16, and the
# int8 cache's four unpacked planes a layer
_CHUNK_MODELS = {
    "f32-g2": ("float32", 64, 3, "bf16"),
    "bf16-g2": ("bfloat16", 64, 3, "bf16"),
    "f32-g1": ("float32", 128, 2, "bf16"),
    "bf16-g1": ("bfloat16", 128, 2, "bf16"),
    "int8": ("float32", 32, 2, "int8"),
}
_CHUNK_ROWS = {"row0": 0, "middle-row": 2, "last-row": _S - 1}
# a block at column 0, in the middle of the ring, ending at C, and one that
# wraps (ring_block_write's two legs)
_CHUNK_COLUMNS = {"at-0": 0, "mid-ring": 11, "ends-at-C": _C - _T,
                  "wraps": _C - 3}
_CHUNK_PROGRAMS = {}


def _chunk_programs(case):
    """``(in place, sliced, state, planes, ids)`` for one model case, each
    program compiled once.  The sliced form is the same Generator's program
    traced for a model that does not declare the capability (the tests' way
    to the reference, not an option of the program)."""
    import jax
    import jax.numpy as jnp
    if case in _CHUNK_PROGRAMS:
        return _CHUNK_PROGRAMS[case]
    dtype, hd, heads, kv = _CHUNK_MODELS[case]
    paddle.seed(41)
    m = GPTModel(GPTConfig.tiny(vocab_size=V, hidden_size=hd * heads,
                                layers=2, heads=heads, seq=64))
    if dtype != "float32":
        m.to(dtype=dtype)
    m.eval()
    snap = flags_snapshot()
    try:
        set_flags({"FLAGS_kv_cache_dtype": kv})
        gen = Generator(m, max_len=_C, seq_buckets=[_C])
        assert gen.chunk_row() == "in_place"
        rng = np.random.RandomState(3)
        planes = [tuple(
            jnp.asarray(rng.randint(-127, 128, p.shape), p.dtype)
            if p.dtype == jnp.int8
            else jnp.asarray(rng.uniform(0.01, 1.0, p.shape)
                             * (1 if p.shape[-1] == 1
                                else rng.choice([-1, 1], p.shape)), p.dtype)
            for p in c) for c in gen.init_slot_cache(_S, _C)]
        assert len(planes[0]) == (4 if kv == "int8" else 2)
        ids = jnp.asarray(rng.randint(1, V, (1, _T)), jnp.int32)
        args = (*gen._state, planes, ids, jnp.zeros((1,), jnp.int32),
                jnp.int32(0), jnp.int32(0))
        progs = []
        for takes in (True, False):
            type(m).cached_forward_takes_row = takes
            try:
                progs.append(jax.jit(gen._build_chunk(_S, _T, _C))
                             .lower(*args).compile())
            finally:
                type(m).cached_forward_takes_row = True
    finally:
        flags_restore(snap)
    out = _CHUNK_PROGRAMS[case] = (*progs, gen._state, planes, ids)
    return out


@pytest.mark.parametrize("column", _CHUNK_COLUMNS)
@pytest.mark.parametrize("row", _CHUNK_ROWS)
@pytest.mark.parametrize("case", _CHUNK_MODELS)
def test_chunk_in_place_equals_the_sliced_row_to_the_bit(case, row, column):
    import jax
    import jax.numpy as jnp
    in_place, sliced, state, planes, ids = _chunk_programs(case)
    r, pos = _CHUNK_ROWS[row], _CHUNK_COLUMNS[column]
    start = jnp.asarray([min(pos, 2)], jnp.int32)
    got = in_place(*state, planes, ids, start, jnp.int32(r), jnp.int32(pos))
    want = sliced(*state, planes, ids, start, jnp.int32(r), jnp.int32(pos))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    assert np.isfinite(np.asarray(got[1])).all()
    others = np.arange(_S) != r
    cols = (pos + np.arange(_T)) % _C
    for new, ref, old in zip(*map(jax.tree_util.tree_leaves,
                                  (got[0], want[0], planes))):
        new, ref, old = (np.asarray(a.astype(jnp.float32))
                         for a in (new, ref, old))
        np.testing.assert_array_equal(new, ref)
        # every OTHER row of every plane as it was, and of the chunk's row
        # every column outside the block; the block itself is new
        np.testing.assert_array_equal(new[others], old[others])
        outside = np.setdiff1d(np.arange(_C), cols)
        np.testing.assert_array_equal(new[r][:, outside], old[r][:, outside])
        assert not np.array_equal(new[r][:, cols], old[r][:, cols])


@pytest.mark.parametrize("takes", [True, False],
                         ids=["model-takes-the-row", "model-does-not"])
def test_the_chunk_cuts_a_row_only_for_a_model_that_takes_none(
        takes, monkeypatch):
    """Decided when the program is traced, from the model's declaration:
    ``_slice_row`` / ``_splice_row`` are entered for a model without
    ``cached_forward_takes_row`` and never for one with it, and the
    program's ``extra`` says which."""
    import jax
    from paddle_tpu.text import generation as G
    m = _model(seed=23)
    if not takes:
        monkeypatch.delattr(GPTModel, "cached_forward_takes_row")
    calls = []
    for name in ("_slice_row", "_splice_row"):
        real = getattr(G, name)
        monkeypatch.setattr(
            G, name, lambda *a, _r=real, _n=name: (calls.append(_n),
                                                   _r(*a))[1])
    gen = Generator(m, max_len=32, seq_buckets=[32])
    _key, kind, fn, avals, extra, donate = gen._chunk_program(3, 8, 32)
    jax.jit(fn, donate_argnums=donate).lower(*gen._state_avals(), *avals)
    assert kind == "generate_chunk"
    assert extra["chunk_row"] == ("in_place" if takes else "sliced")
    assert calls == ([] if takes else ["_slice_row", "_splice_row"])
    # part of the executable cache's identity only where it is in place:
    # every other model's identity is what it was
    assert (("chunk_row", "in_place") in gen._program_identity()) is takes


@pytest.mark.parametrize("width", [1, 3, 8])
def test_ring_block_write_with_a_row_at_every_boundary_offset(width):
    """Both legs of the wrap-aware write, addressed by row: at every start
    column the block lands in THAT row, wrapped, the columns the clamped
    window re-writes keep that row's contents, and no other row moves."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.nn.layer.transformer import ring_block_write
    S, G, C, L = 4, 2, 8, 4
    rng = np.random.RandomState(width)
    plane = rng.randn(S, G, C, L).astype(np.float32)
    new = rng.randn(1, G, width, L).astype(np.float32)
    write = jax.jit(lambda p, n, pos, row: ring_block_write(
        p, n, pos, row=row))
    for row in range(S):
        for pos in range(C):
            want = plane.copy()
            want[row][:, (pos + np.arange(width)) % C] = new[0]
            got = write(plane, new, jnp.int32(pos), jnp.int32(row))
            np.testing.assert_array_equal(np.asarray(got), want)
    # a static in-range position takes the single store
    got = ring_block_write(plane, new, 0, row=jnp.int32(2))
    want = plane.copy()
    want[2][:, :width] = new[0]
    np.testing.assert_array_equal(np.asarray(got), want)
    with pytest.raises(ValueError):
        ring_block_write(plane, np.concatenate([new, new]), 0,
                         row=jnp.int32(0))
