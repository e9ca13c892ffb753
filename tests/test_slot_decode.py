"""Iteration-level continuous batching: the slot decode loop.

Adversarial join/leave churn — randomized arrival order, prompt
lengths, and generation lengths — must emit tokens BIT-IDENTICAL to a
per-request ``generate()`` of the same prompt, for the plain, the
speculative, and the int8-KV variants, with ZERO steady-state
recompiles across arbitrary slot occupancy.  Plus: bounded-ring
session resets, the FLAGS_decode_slots / FLAGS_prefill_chunk surface
(validation, snapshot/restore, off-path), token-level occupancy
signals, and the slot-mode Server integration."""
import functools
import random

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.framework.enforce import (InvalidArgumentError,
                                          OutOfRangeError)
from paddle_tpu.framework.flags import flags_restore, flags_snapshot, \
    set_flags
from paddle_tpu.profiler import ledger
from paddle_tpu.serving.slots import SlotLoop
from paddle_tpu.text.generation import Generator
from paddle_tpu.text.models.gpt import GPTConfig, GPTModel
from paddle_tpu.text.speculative import SpeculativeGenerator

V = 64


@functools.lru_cache(maxsize=None)
def _gpt(seed=21):
    paddle.seed(seed)
    m = GPTModel(GPTConfig.tiny(vocab_size=V, hidden_size=32, layers=2,
                                heads=2, seq=64))
    m.eval()
    return m


@functools.lru_cache(maxsize=None)
def _draft(seed=101):
    paddle.seed(seed)
    d = GPTModel(GPTConfig.tiny(vocab_size=V, hidden_size=16, layers=1,
                                heads=2, seq=64))
    d.eval()
    return d


@functools.lru_cache(maxsize=None)
def _oracle(seed=21, speculative=False):
    """ONE stateless oracle per model for the whole module: the tests ask
    it for many (prompt bucket, cache bucket, steps) programs, and a fresh
    Generator per test compiled each of them again.  The models are
    built once per seed too (nothing here mutates their weights); the
    kv-cache dtype is part of every executable's key, so the int8 tests
    share the oracle safely."""
    if speculative:
        return SpeculativeGenerator(_gpt(seed), _draft(), seq_buckets=(8, 16, 32),
                                    max_len=64, gamma=3)
    return Generator(_gpt(seed), seq_buckets=(8, 16, 32), max_len=64)


def _trace(rng, n, max_lp=20, max_mn=10):
    """A randomized churn schedule: mixed short/long prompts and
    generation lengths, so rows join and retire at staggered token
    boundaries across the whole run."""
    reqs = []
    for k in range(n):
        lp = rng.randint(max_lp // 2, max_lp) if k % 4 == 0 \
            else rng.randint(1, max(2, max_lp // 3))
        mn = max_mn if k % 3 == 1 else rng.randint(1, max(2, max_mn // 2))
        reqs.append(([rng.randrange(V) for _ in range(lp)], mn))
    return reqs


def _run_churn(loop, reqs, waves=3):
    """Submit in waves — later waves join while earlier rows are still
    decoding — and drain every future before returning."""
    futs = []
    per = -(-len(reqs) // waves)
    for w in range(waves):
        futs += [loop.submit(p, mn)
                 for p, mn in reqs[w * per:(w + 1) * per]]
        # wait on one future per wave so the next wave's submissions
        # arrive mid-flight (join churn), deterministically
        futs[w * per].result(timeout=120)
    return [np.asarray(f.result(timeout=120)).reshape(-1) for f in futs]


def _oracle_steps(p, mn):
    """The oracle decodes greedily, so the first ``mn`` tokens of a longer
    continuation ARE the ``mn``-token continuation (EOS freezing
    included): ask for ``mn`` rounded up to a multiple of 8 where the
    model's 64 positions allow, so that the module compiles three decode
    lengths and not one per request."""
    steps = -(-mn // 8) * 8
    return steps if len(p) + steps <= 64 else mn


def _assert_bit_identical(oracle, reqs, outs):
    for (p, mn), got in zip(reqs, outs):
        ids = np.asarray([p], np.int32)
        want = np.asarray(oracle.generate(
            ids, lengths=np.asarray([len(p)], np.int32),
            max_new_tokens=_oracle_steps(p, mn)).numpy())[0]
        np.testing.assert_array_equal(got[:mn], want[:mn])


def test_churn_bit_identical_plain_zero_steady_recompiles():
    m = _gpt()
    gen = Generator(m, site="slot:plain", seq_buckets=(8, 16, 32),
                    max_len=64)
    oracle = _oracle()
    loop = SlotLoop(gen, slots=4, cache_len=64, chunk=8)
    mark = len(ledger.compile_events("slot:plain"))
    try:
        for trial in range(4):
            rng = random.Random(500 + trial)
            reqs = _trace(rng, 12)
            outs = _run_churn(loop, reqs)
            _assert_bit_identical(oracle, reqs, outs)
        assert len(ledger.compile_events("slot:plain")) == mark
        assert loop.counters["joined"] == loop.counters["retired"] == 48
    finally:
        loop.close()


def test_churn_bit_identical_speculative():
    m, d = _gpt(), _draft()
    gen = SpeculativeGenerator(m, d, site="slot:spec",
                               seq_buckets=(8, 16, 32), max_len=64,
                               gamma=3)
    oracle = _oracle(speculative=True)
    loop = SlotLoop(gen, slots=4, cache_len=64, chunk=8)
    mark = len(ledger.compile_events("slot:spec"))
    try:
        for trial in range(3):
            rng = random.Random(700 + trial)
            reqs = _trace(rng, 10)
            outs = _run_churn(loop, reqs)
            _assert_bit_identical(oracle, reqs, outs)
        assert len(ledger.compile_events("slot:spec")) == mark
        st = loop.stats()
        assert st["spec_proposed"] > 0 and "spec_acceptance_rate" in st
    finally:
        loop.close()


def test_churn_bit_identical_int8_kv():
    snap = flags_snapshot()
    try:
        set_flags({"FLAGS_kv_cache_dtype": "int8"})
        m = _gpt()
        gen = Generator(m, site="slot:int8", seq_buckets=(8, 16, 32),
                        max_len=64)
        oracle = _oracle()
        loop = SlotLoop(gen, slots=4, cache_len=64, chunk=8)
        mark = len(ledger.compile_events("slot:int8"))
        try:
            rng = random.Random(900)
            reqs = _trace(rng, 10)
            outs = _run_churn(loop, reqs)
            _assert_bit_identical(oracle, reqs, outs)
            assert len(ledger.compile_events("slot:int8")) == mark
        finally:
            loop.close()
    finally:
        flags_restore(snap)


def test_eos_early_retirement_matches_oracle_padding():
    """A row that hits EOS mid-stream retires early; its tail pads with
    the eos token exactly like the scanned decode's freeze."""
    m = _gpt(seed=37)
    gen = Generator(m, seq_buckets=(8, 16, 32), max_len=64)
    oracle = _oracle(37)
    # pick an eos that actually occurs: take the 3rd greedy token
    probe = np.asarray(oracle.generate(
        np.asarray([[5, 9, 2]], np.int32),
        lengths=np.asarray([3], np.int32),
        max_new_tokens=8).numpy())[0]
    eos = int(probe[2])
    loop = SlotLoop(gen, slots=2, cache_len=64, chunk=8,
                    eos_token_id=eos)
    try:
        got = np.asarray(loop.submit([5, 9, 2], 8).result(
            timeout=120)).reshape(-1)
        want = np.asarray(oracle.generate(
            np.asarray([[5, 9, 2]], np.int32),
            lengths=np.asarray([3], np.int32),
            max_new_tokens=8, eos_token_id=eos).numpy())[0]
        np.testing.assert_array_equal(got, want)
    finally:
        loop.close()


def test_bounded_ring_session_reset_and_rejection():
    m = _gpt(seed=39)
    gen = Generator(m, seq_buckets=(8, 16, 32), max_len=64)
    oracle = _oracle(39)
    loop = SlotLoop(gen, slots=2, cache_len=32, chunk=8)
    try:
        # a prompt+continuation that can NEVER fit C=32 fails at submit
        with pytest.raises(OutOfRangeError):
            loop.submit(list(range(1, 25)), 12)
        # enough sequential traffic to exhaust the ring at least once:
        # the loop drains, restarts the session at pos=0, and stays
        # bit-exact across the reset
        rng = random.Random(11)
        reqs = [([rng.randrange(V) for _ in range(6)], 6)
                for _ in range(8)]
        outs = [np.asarray(loop.submit(p, mn).result(timeout=120))
                .reshape(-1) for p, mn in reqs]
        _assert_bit_identical(oracle, reqs, outs)
        assert loop.counters["session_resets"] >= 1
    finally:
        loop.close()


def test_occupancy_signals_and_counters():
    m = _gpt(seed=41)
    gen = Generator(m, seq_buckets=(8, 16, 32), max_len=64)
    loop = SlotLoop(gen, slots=4, cache_len=64, chunk=8,
                    model="sigtest")
    try:
        futs = [loop.submit([3, 1, 4, 1, 5], 6) for _ in range(6)]
        for f in futs:
            f.result(timeout=120)
        sig = loop.signals()
        assert sig["slots_joined_total"] == 6
        assert sig["slots_retired_total"] == 6
        assert 0.0 <= sig["decode_slot_occupancy_ratio"] <= 1.0
        assert sig["slot_steps_total"] > 0
        assert sig["slot_pending"] == 0
        st = loop.stats()
        assert st["ttft_p50_ms"] > 0 and st["ttft_p99_ms"] > 0
        # the registry gauge carries the per-step ratio for the
        # ClusterSignals leg (scheduler.py instruments)
        from paddle_tpu.serving.scheduler import (SLOT_OCCUPANCY,
                                                  SLOTS_JOINED,
                                                  SLOTS_RETIRED)
        assert SLOTS_JOINED.labels(model="sigtest").value >= 6
        assert SLOTS_RETIRED.labels(model="sigtest").value >= 6
        assert 0.0 <= SLOT_OCCUPANCY.labels(
            model="sigtest").value <= 1.0
    finally:
        loop.close()


def test_flags_validation_and_snapshot_restore():
    snap = flags_snapshot()
    try:
        set_flags({"FLAGS_decode_slots": 8, "FLAGS_prefill_chunk": 32})
        from paddle_tpu.framework import flags as _flags
        assert _flags.flag("decode_slots") == 8
        assert _flags.flag("prefill_chunk") == 32
        with pytest.raises(Exception):
            set_flags({"FLAGS_decode_slots": -1})
        with pytest.raises(Exception):
            set_flags({"FLAGS_decode_slots": 257})
        with pytest.raises(Exception):
            set_flags({"FLAGS_prefill_chunk": 0})
        # failed sets never clobber the last valid values
        assert _flags.flag("decode_slots") == 8
        assert _flags.flag("prefill_chunk") == 32
    finally:
        flags_restore(snap)
    from paddle_tpu.framework import flags as _flags
    assert _flags.flag("decode_slots") == snap["decode_slots"]
    assert _flags.flag("prefill_chunk") == snap["prefill_chunk"]


def test_slot_loop_constructor_guards():
    m = _gpt(seed=43)
    gen = Generator(m, seq_buckets=(8, 16, 32), max_len=64)
    with pytest.raises(InvalidArgumentError):
        SlotLoop(gen, slots=0, cache_len=64, chunk=8)
    loop = SlotLoop(gen, slots=2, cache_len=64, chunk=8)
    try:
        with pytest.raises(InvalidArgumentError):
            loop.submit([], 4)              # empty prompt
        with pytest.raises(InvalidArgumentError):
            loop.submit([1, 2], 0)          # max_new < 1
    finally:
        loop.close()


# -- slot-mode Server integration --------------------------------------------

def test_server_slot_mode_end_to_end():
    """FLAGS_decode_slots swaps the run-to-completion scan for the slot
    loop behind the SAME submit surface: served tokens bit-match the
    oracle, the steady-state recompile invariant holds, and the slot
    accounting reaches Server.stats()/signals()."""
    snap = flags_snapshot()
    try:
        set_flags({"FLAGS_decode_slots": 4, "FLAGS_prefill_chunk": 8})
        m = _gpt(seed=45)
        srv = serving.Server(serving.ServingConfig(workers=2))
        srv.register_decode("gpt", m, batch_buckets=(1, 2),
                            seq_buckets=(8, 16), max_new_tokens=4,
                            max_len=32)
        srv.start()
        try:
            rng = np.random.RandomState(3)
            prompts = [rng.randint(1, V, int(n))
                       for n in (3, 7, 12, 1, 9, 5)]
            futs = [srv.submit_decode("gpt", [p], max_new_tokens=4)
                    for p in prompts]
            served = [f.result(timeout=120)[0][0] for f in futs]
            oracle = Generator(m, seq_buckets=(8, 16), max_len=32)
            for p, got in zip(prompts, served):
                want = np.asarray(oracle.generate(
                    p[None, :].astype(np.int64),
                    max_new_tokens=4).numpy())[0]
                np.testing.assert_array_equal(got, want)
            srv.assert_zero_steady_state_recompiles()
            st = srv.stats("gpt")
            assert st["slot_loop"]["joined"] >= 6
            sig = srv.signals()
            assert "decode_slot_occupancy_ratio" in sig
        finally:
            srv.stop()
    finally:
        flags_restore(snap)


def test_slot_mode_off_path_single_branch():
    """FLAGS_decode_slots=0 (default) keeps the scanned
    run-to-completion path: no SlotLoop is constructed and the decode
    runtime reports no slot accounting."""
    m = _gpt(seed=47)
    srv = serving.Server(serving.ServingConfig(workers=2))
    srv.register_decode("gpt", m, batch_buckets=(1,), seq_buckets=(8,),
                        max_new_tokens=3, max_len=32)
    srv.start()
    try:
        rt = srv._models["gpt"]
        assert rt.slots == 0 and rt._loop is None
        out = srv.run_decode("gpt", [np.arange(1, 5)])[0]
        assert out.shape == (1, 3)
        assert "slot_loop" not in srv.stats("gpt")
    finally:
        srv.stop()
