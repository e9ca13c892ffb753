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

import jax
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


# -- activation: a row's logits stay on the device ----------------------------

def _spy_put_row(loop):
    """Every activation write of the loop, in order: the frontier it was
    dispatched at, the row, and the types of the plane and of the row's
    logits it was handed."""
    put, seen = loop._put_row, []

    def recording(logits, row, i):
        seen.append((loop.pos, int(i), type(logits), type(row)))
        return put(logits, row, i)

    loop._put_row = recording
    return seen


def test_rows_activate_on_the_device_and_are_counted():
    """After a churn of joins the step's logits are the device array the
    last program handed back, every row that joined was activated by the
    row write, and no byte of logits crossed the host for it."""
    gen = Generator(_gpt(), site="slot:activate", seq_buckets=(8, 16, 32),
                    max_len=64)
    loop = SlotLoop(gen, slots=4, cache_len=64, chunk=8)
    seen = _spy_put_row(loop)
    try:
        reqs = _trace(random.Random(1300), 12)
        outs = _run_churn(loop, reqs)
        _assert_bit_identical(_oracle(), reqs, outs)
        assert isinstance(loop._logits, jax.Array)
        assert loop.counters["rows_activated"] == loop.counters["joined"] \
            == len(seen) == 12
        assert loop.counters["logits_bytes_via_host"] == 0
        # a final chunk's logits were never fetched either: the write took
        # the chunk program's own output
        assert all(issubclass(row, jax.Array) for _, _, _, row in seen)
        assert all(s._act_logits is None for s in loop._slots)
    finally:
        loop.close()


@pytest.mark.parametrize("together", [True, False],
                         ids=["two-rows-in-one-iteration",
                              "first-step-of-the-session"])
def test_rows_joining_mid_session_stay_bit_identical(together):
    """The oracle for rows that join a running session, where the write
    of the activation logits is at its edges: two rows that activate in
    the SAME iteration (two writes into one plane before the step reads
    it), and the row that activates on the first step of the loop's life,
    when the plane is still the host's zeros and not yet a step's
    output."""
    gen = Generator(_gpt(), site="slot:join", seq_buckets=(8, 16, 32),
                    max_len=64)
    loop = SlotLoop(gen, slots=4, cache_len=64, chunk=8)
    seen = _spy_put_row(loop)
    rng = random.Random(1400)
    reqs = [([rng.randrange(V) for _ in range(n)], mn)
            for n, mn in ((5, 24), (11, 6), (13, 7))]
    try:
        first = loop.submit(*reqs[0])
        if together:
            # both enter the FIFO before the driver admits again: one
            # admission, one chunk count, so one planned activation
            with loop._cond:
                futs = [loop.submit(p, mn) for p, mn in reqs[1:]]
        else:
            futs = [loop.submit(p, mn) for p, mn in reqs[1:]]
        outs = [np.asarray(f.result(timeout=120)).reshape(-1)
                for f in [first] + futs]
        _assert_bit_identical(_oracle(), reqs, outs)
        # the first write of a loop's life goes into the host's zeros,
        # every later one into what a program handed back
        assert seen[0][2] is np.ndarray
        assert all(issubclass(t, jax.Array) for _, _, t, _ in seen[1:])
        assert len(seen) == 3
        if together:
            (_, r0, _, _), (pos1, r1, _, _), (pos2, r2, _, _) = seen
            assert pos1 == pos2 and len({r0, r1, r2}) == 3
            # ... while the first row was still generating
            assert pos1 < seen[0][0] + 24
    finally:
        loop.close()


def test_warm_up_compiles_the_row_write_and_steady_state_adds_none():
    """The Server's slot-mode warm-up compiles the row write through the
    ledger with the step and the chunk, and runs it once (the dummy
    request's activation); serving then compiles nothing."""
    snap = flags_snapshot()
    try:
        set_flags({"FLAGS_decode_slots": 4, "FLAGS_prefill_chunk": 8})
        srv = serving.Server(serving.ServingConfig(workers=2))
        srv.register_decode("gpt", _gpt(seed=45), batch_buckets=(1, 2),
                            seq_buckets=(8, 16), max_new_tokens=4,
                            max_len=32)
        srv.start()
        try:
            rt = srv._models["gpt"]
            kinds = [e["kind"] for e in ledger.compile_events(rt.site)]
            assert kinds.count("logits_put_row") == 1
            assert {"generate_step", "generate_chunk"} <= set(kinds)
            # warmed: the dummy request's row went through it, and the
            # accounting was zeroed after it
            assert isinstance(rt._loop._logits, jax.Array)
            assert rt._loop.counters["rows_activated"] == 0
            mark = len(ledger.compile_events(rt.site))
            rng = np.random.RandomState(5)
            futs = [srv.submit_decode("gpt", [rng.randint(1, V, int(n))],
                                      max_new_tokens=4)
                    for n in (3, 12, 7, 1, 9)]
            for f in futs:
                f.result(timeout=120)
            assert len(ledger.compile_events(rt.site)) == mark
            srv.assert_zero_steady_state_recompiles()
            assert rt._loop.counters["rows_activated"] == 5
            assert rt._loop.counters["logits_bytes_via_host"] == 0
        finally:
            srv.stop()
    finally:
        flags_restore(snap)


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
        # a speculative step carries tokens: the host takes each final
        # chunk's logits down for their argmax, when the row activates
        assert st["rows_activated"] == 30
        assert st["logits_bytes_via_host"] == 30 * V * 4
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


# -- one step in flight --------------------------------------------------------

def _parent_order(loop):
    """The loop as it ran before it kept a step in flight: every step is
    read before anything else is dispatched."""
    step = loop._plain_step

    def read_at_once(gen_slots, split):
        step(gen_slots, split)
        loop._settle()

    loop._plain_step = read_at_once


def _spy_flights(loop):
    """The columns of the steps as they were dispatched and as they were
    read, and how many steps were in flight at each read (the one being
    read not counted)."""
    dispatched, read, behind = [], [], []
    step, read_step = loop._step, loop._read_step

    def stepping(*args):
        dispatched.append(int(args[-1]))
        return step(*args)

    def reading(flight):
        read.append(flight.pos)
        behind.append(int(loop._inflight is not None))
        return read_step(flight)

    loop._step, loop._read_step = stepping, reading
    return dispatched, read, behind


def _want_tokens(seed, p, mn, eos=None):
    """The scanned decode's tokens: ``mn`` of them, frozen at the end
    token where one is given."""
    kw = {} if eos is None else {"eos_token_id": eos}
    return np.asarray(_oracle(seed).generate(
        np.asarray([p], np.int32), lengths=np.asarray([len(p)], np.int32),
        max_new_tokens=_oracle_steps(p, mn), **kw).numpy())[0][:mn]


def _serve_at_once(seed, reqs, order, slots=2, cache_len=64, chunk=8,
                   eos=None, spy=None, **kw):
    """``reqs`` [(prompt, max_new)] through a fresh loop, all in the FIFO
    before the driver's first admission, so that the schedule follows from
    the lengths alone.  ``order`` "parent" reads every step at once.
    Returns (tokens or the exception of each, stats, what ``spy(loop)``
    returned, the flights)."""
    gen = Generator(_gpt(seed), seq_buckets=(8, 16, 32), max_len=64)
    loop = SlotLoop(gen, slots=slots, cache_len=cache_len, chunk=chunk,
                    eos_token_id=eos, **kw)
    if order == "parent":
        _parent_order(loop)
    flights = _spy_flights(loop)
    seen = spy(loop) if spy is not None else None
    try:
        with loop._cond:
            futs = [loop.submit(p, mn) for p, mn in reqs]
        outs = []
        for f in futs:
            try:
                outs.append(np.asarray(f.result(timeout=120)).reshape(-1))
            except Exception as e:   # noqa: BLE001 — the test looks at it
                outs.append(e)
    finally:
        loop.close()
    assert loop._inflight is None and loop._leaving == []
    return outs, loop.stats(), seen, flights


def _assert_every_step_read_once_in_order(flights, order):
    dispatched, read, behind = flights
    assert read == dispatched
    if order == "parent":
        assert not any(behind)
    else:
        # the next step was on the device whenever there was one to send
        assert any(behind)


ORDERS = ("in_flight", "parent")


def _end_token_scenario():
    """Row A (5 tokens: two chunks of 4, activates at column 8) takes, as
    its eighth token, one it has not produced before: the end token, at
    the step that writes column 15.  Row B (13 tokens: four chunks)
    activates at column 16, the very next step, which the host dispatches
    before it has read A's end token."""
    a = [8, 30, 11, 54, 7]
    free = _want_tokens(37, a, 16)
    assert free[7] not in free[:7]
    eos = int(free[7])
    rng = random.Random(5)
    while True:
        b = [rng.randrange(V) for _ in range(13)]
        if eos not in _want_tokens(37, b, 6):
            return eos, [(a, 14), (b, 6)]


@pytest.mark.parametrize("order", ORDERS)
def test_end_token_in_a_batch_and_a_row_joining_on_the_next_step(order):
    eos, reqs = _end_token_scenario()
    outs, st, _, flights = _serve_at_once(37, reqs, order, chunk=4, eos=eos)
    for (p, mn), got in zip(reqs, outs):
        np.testing.assert_array_equal(got, _want_tokens(37, p, mn, eos))
    assert list(outs[0][7:]) == [eos] * 7          # frozen from its 8th on
    _assert_every_step_read_once_in_order(flights, order)
    dispatched = flights[0]
    assert dispatched[:9] == list(range(8, 17))
    # the step at column 16 listed A although it was done: one slot-step
    assert st["slot_steps_retire_lag"] == (1 if order == "in_flight" else 0)
    assert st["slot_steps_emitting"] == st["emitted_tokens"] == 8 + 6
    assert sum(st[f"slot_steps_{k}"] for k in (
        "emitting", "prefilling", "drain_blocked", "no_demand")) \
        == st["steps"] * 2
    assert st["steps"] == len(dispatched) == 8 + 6
    assert 0 <= st["steps_read_ready"] <= st["steps"]


def _spy_install(loop):
    """At each admission: the frontier, the rows whose last token was in
    flight, and whether a step was."""
    seen, install = [], loop._install

    def installing(slot, head):
        seen.append((loop.pos, len(loop._leaving),
                     loop._inflight is not None))
        return install(slot, head)

    loop._install = installing
    return seen


@pytest.mark.parametrize("order", ORDERS)
def test_a_slot_freed_by_count_is_admitted_into_in_the_next_iteration(order):
    """Two slots, three requests: A ends by count with the step at column
    10 while C goes on; B takes A's slot in the next iteration, as it did
    when the loop read before it dispatched, although A's last token is
    still in flight then."""
    rng = random.Random(17)
    reqs = [([rng.randrange(V) for _ in range(n)], mn)
            for n, mn in ((6, 3), (7, 12), (5, 4))]
    outs, st, seen, flights = _serve_at_once(21, reqs, order,
                                             spy=_spy_install)
    for (p, mn), got in zip(reqs, outs):
        np.testing.assert_array_equal(got, _want_tokens(21, p, mn))
    _assert_every_step_read_once_in_order(flights, order)
    assert [pos for pos, _, _ in seen] == [0, 0, 11]
    assert seen[2][1:] == ((1, True) if order == "in_flight" else (0, False))
    assert st["slot_steps_retire_lag"] == 0
    assert st["joined"] == st["retired"] == 3


@pytest.mark.parametrize("order", ORDERS)
def test_ring_restarts_with_a_step_in_flight(order):
    """A ring of 32 columns and more traffic than it holds: the last row
    of a session leaves by count, the restart finds its last step still in
    flight and reads it before the frontier goes back to 0."""
    rng = random.Random(11)
    reqs = [([rng.randrange(V) for _ in range(6)], 6) for _ in range(8)]
    outs, st, _, flights = _serve_at_once(39, reqs, order, cache_len=32)
    for (p, mn), got in zip(reqs, outs):
        np.testing.assert_array_equal(got, _want_tokens(39, p, mn))
    _assert_every_step_read_once_in_order(flights, order)
    assert st["session_resets"] >= 1
    dispatched = flights[0]
    assert any(b < a for a, b in zip(dispatched, dispatched[1:]))


@pytest.mark.parametrize("order", ORDERS)
def test_close_with_a_step_in_flight_drains_it(order):
    """``close()`` right behind the submissions: the rows admitted by then
    run to their end, the step in flight included; what was not admitted
    fails."""
    rng = random.Random(23)
    reqs = [([rng.randrange(V) for _ in range(5)], 9) for _ in range(3)]
    gen = Generator(_gpt(21), seq_buckets=(8, 16, 32), max_len=64)
    loop = SlotLoop(gen, slots=2, cache_len=64, chunk=8)
    if order == "parent":
        _parent_order(loop)
    flights = _spy_flights(loop)
    with loop._cond:
        futs = [loop.submit(p, mn) for p, mn in reqs[:2]]
    while not loop.counters["steps"]:      # both rows are generating
        pass
    loop.close()
    for (p, mn), f in zip(reqs, futs):
        np.testing.assert_array_equal(
            np.asarray(f.result(timeout=1)).reshape(-1),
            _want_tokens(21, p, mn))
    _assert_every_step_read_once_in_order(flights, order)
    assert loop._inflight is None and loop._leaving == []
    st = loop.stats()
    assert st["steps"] == 9 and st["emitted_tokens"] == 18
    with pytest.raises(Exception, match="closed"):
        loop.submit(*reqs[2])


class _Unreadable:
    """A step's tokens that cannot be fetched."""

    def copy_to_host_async(self):
        pass

    def __array__(self, *a, **kw):
        raise RuntimeError("boom: read")


@pytest.mark.parametrize("order", ORDERS)
@pytest.mark.parametrize("where", ["dispatch", "read"])
def test_a_step_that_raises_fails_its_rows_after_the_tokens_before_it(
        where, order):
    """A (3 tokens) and B (10) step together at columns 8, 9, 10; the
    step at column 11 fails.  At its DISPATCH: A's last token, in flight
    then, is delivered first, and B fails.  At its READ (a device fault
    shows there): A was read a step earlier all the same; B fails; and
    had A's own last step been the unreadable one, A would fail too."""
    rng = random.Random(29)
    reqs = [([rng.randrange(V) for _ in range(n)], mn)
            for n, mn in ((6, 3), (7, 10))]

    def spy(loop):
        step = loop._step

        def failing(*args):
            if int(args[-1]) == 11:
                if where == "dispatch":
                    raise RuntimeError("boom: dispatch")
                return step(*args)[:3] + (_Unreadable(),)
            return step(*args)

        loop._step = failing
        return loop

    outs, st, loop, flights = _serve_at_once(21, reqs, order, spy=spy)
    np.testing.assert_array_equal(outs[0], _want_tokens(21, *reqs[0]))
    assert isinstance(outs[1], RuntimeError) and "boom" in str(outs[1])
    assert st["steps"] == 3 and st["emitted_tokens"] == 6
    assert st["retired"] == 1
    assert isinstance(loop._dead, RuntimeError)


@pytest.mark.parametrize("order", ORDERS)
def test_an_unreadable_last_step_fails_the_row_that_left_with_it(order):
    rng = random.Random(29)
    reqs = [([rng.randrange(V) for _ in range(n)], mn)
            for n, mn in ((6, 3), (7, 10))]

    def spy(loop):
        step = loop._step
        loop._step = lambda *a: step(*a)[:3] + (_Unreadable(),) \
            if int(a[-1]) == 10 else step(*a)

    outs, st, _, _ = _serve_at_once(21, reqs, order, spy=spy)
    assert all(isinstance(o, RuntimeError) for o in outs)
    assert st["steps"] == 2 and st["retired"] == 0


def test_sessions_leave_and_park_with_a_step_in_flight():
    """Two slots.  C decodes throughout.  A, a session's turn, ends by
    count and leaves its slot with its last token in flight; B, the next
    turn of ANOTHER session, is admitted into that slot in the next
    iteration and its restored block is pushed over A's columns before
    A's token is read.  A's snapshot was pulled when A left: it equals,
    bit for bit, the one a loop that reads before it dispatches parks.
    Then ``park_sessions`` with a step in flight: the row is parked with
    every token the device has produced for it."""
    from paddle_tpu.framework.enforce import UnavailableError
    from paddle_tpu.serving.sessions import SessionStore
    rng = random.Random(41)
    first = [rng.randrange(1, V) for _ in range(17)]
    a = [rng.randrange(1, V) for _ in range(9)]
    c = [rng.randrange(1, V) for _ in range(5)]
    snaps = {}
    for order in ORDERS:
        gen = Generator(_gpt(21), seq_buckets=(8, 16, 32), max_len=64)
        store = SessionStore()
        loop = SlotLoop(gen, slots=2, cache_len=64, chunk=8,
                        session_store=store)
        if order == "parent":
            _parent_order(loop)
        seen = _spy_install(loop)
        try:
            got = np.asarray(loop.submit(first, 3, session_id="s2")
                             .result(timeout=120)).reshape(-1)
            turn2 = first + [int(t) for t in got] + [7, 9]
            with loop._cond:
                fc = loop.submit(c, 30)
                fa = loop.submit(a, 3, session_id="s1")
                fb = loop.submit(turn2, 4, session_id="s2",
                                 snapshot=store.take("s2"))
            np.testing.assert_array_equal(
                np.asarray(fa.result(timeout=120)).reshape(-1),
                _want_tokens(21, a, 3))
            np.testing.assert_array_equal(
                np.asarray(fb.result(timeout=120)).reshape(-1),
                _want_tokens(21, turn2, 4))
            if order == "in_flight":
                assert seen[-1][1:] == (1, True)     # B came while A left
            assert loop.counters["restore_pushes"] >= 1
            snaps[order] = store.take("s1")
            # a session row parked mid-stream, a step in flight behind it
            fd = loop.submit(a, 20, session_id="s3")
            while fd.running() or not any(
                    s.req is not None and s.req.session_id == "s3"
                    and s.emitted for s in loop._slots):
                assert not fd.done()
            assert loop.park_sessions(timeout=30.0) == 1
            with pytest.raises(UnavailableError):
                fd.result(timeout=30)
            parked = store.take("s3")
            want = _want_tokens(21, a, 20)
            n = len(parked.emitted)
            assert 0 < n == 20 - parked.remaining
            np.testing.assert_array_equal(parked.emitted, want[:n])
            np.testing.assert_array_equal(
                np.asarray(loop.submit(a, 20, session_id="s3",
                                       snapshot=parked)
                           .result(timeout=120)).reshape(-1), want)
            np.testing.assert_array_equal(
                np.asarray(fc.result(timeout=120)).reshape(-1),
                _want_tokens(21, c, 30))
        finally:
            loop.close()
    one, two = snaps["in_flight"], snaps["parent"]
    assert one.tokens == two.tokens and one.remaining == two.remaining == 0
    for p, q in zip(jax.tree_util.tree_leaves(one.planes),
                    jax.tree_util.tree_leaves(two.planes)):
        np.testing.assert_array_equal(p, q)
