"""Prefix/session KV cache: radix prefix reuse + parked-session restore.

The two planes of ISSUE 19 under adversarial churn: (1) the radix-trie
prefix cache — requests sharing a system prompt restore its ring-cache
plane blocks instead of chunk-prefilling them, BIT-IDENTICALLY, with
ref-counted pins making eviction safe against in-flight restores; and
(2) the session store — a completed turn parks its validity window to
host RAM (optionally sha256-manifested disk spill), and the follow-up
turn restores the planes and chunk-prefills only the new tokens, again
bit-identical to a full re-prefill, for the plain, speculative, and
int8-KV loop variants.  Plus the drain-parks path (mid-generation
snapshot + retryable resume), the migration transport (export/import,
keep-newer), Router session affinity, corrupt-spill fallback, and the
FLAGS_prefix_cache / FLAGS_session_store surface."""
import os
import functools
import random
import time

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.framework.enforce import UnavailableError
from paddle_tpu.framework.flags import flags_restore, flags_snapshot, \
    set_flags
from paddle_tpu.profiler import ledger
from paddle_tpu.serving.prefix_cache import PrefixCache
from paddle_tpu.serving.sessions import SessionSnapshot, SessionStore
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


def _want(oracle, p, mn):
    """The oracle's ``mn`` greedy tokens after ``p``: the head of a
    continuation of ``mn`` rounded up to a multiple of 8 where the model's
    64 positions allow (greedy, so a longer continuation starts with the
    shorter), so that the module compiles a few decode lengths and not one
    per request."""
    ids = np.asarray([p], np.int32)
    steps = -(-mn // 8) * 8
    steps = steps if len(p) + steps <= 64 else mn
    return np.asarray(oracle.generate(
        ids, lengths=np.asarray([len(p)], np.int32),
        max_new_tokens=steps).numpy())[0][:mn]


# -- host-side unit layer -----------------------------------------------------

def test_prefix_trie_dedup_pin_and_lru_eviction():
    """Pure bookkeeping: publish dedups against cached chains, lookup
    pins every node it returns, eviction is LRU / leaves-first /
    refs==0 only, and a fully-pinned cache stays over budget rather
    than freeing a block a restore is about to push."""
    pc = PrefixCache(block_tokens=4, block_nbytes=1 << 20,
                     hbm_budget_mb=3.0)          # budget: 3 blocks
    a = list(range(1, 9))                        # blocks A0, A1
    fetched = []

    def fetch_tag(tag):
        def _f(j):
            fetched.append((tag, j))
            return (tag, j)
        return _f

    assert pc.publish(a, fetch_tag("a")) == 2
    # same first block, different second: only ONE fetch runs
    b = a[:4] + [9, 9, 9, 9]
    assert pc.publish(b, fetch_tag("b")) == 1
    assert fetched == [("a", 0), ("a", 1), ("b", 1)]
    assert len(pc) == 3

    blocks, pin = pc.lookup(a + [5], max_blocks=2)
    assert blocks == [("a", 0), ("a", 1)]
    st = pc.stats()
    assert st["hits"] == 1 and st["hit_tokens"] == 8

    # over-budget publish with the chain pinned: only the UNPINNED
    # leaf ("b", 1) may evict; the pinned chain survives
    c = [7] * 8
    pc.publish(c, fetch_tag("c"))
    assert pc.lookup(a, max_blocks=2)[0] == [("a", 0), ("a", 1)]
    pc.release(pc.lookup(a, max_blocks=2)[1])    # rebalance the extra pin
    assert pc.lookup(b)[0] == [("a", 0)]         # ("b", 1) was the victim
    for _ in range(3):
        pc.release(pin)                          # idempotent-ish unpin
    assert pc.stats()["evictions"] >= 1
    # max_blocks clamp: a full-prompt lookup must leave a suffix token
    blocks, pin2 = pc.lookup(a, max_blocks=(len(a) - 1) // 4)
    assert len(blocks) == 1
    pc.release(pin2)
    pc.clear()
    assert len(pc) == 0 and pc.stats()["blocks"] == 0


def test_session_snapshot_serialization_roundtrip():
    planes = [(np.arange(12, dtype=np.float32).reshape(1, 2, 3, 2),
               np.ones((1, 2, 3, 1), np.int8)),
              [np.zeros((2, 2), np.float32)]]
    snap = SessionSnapshot(
        session_id="conv-1", model="gpt", tokens=[3, 1, 4, 1, 5],
        remaining=2, emitted=[9, 2], planes=planes,
        logits=np.linspace(0, 1, 8).astype(np.float32), cur=7,
        kv_dtype="int8", spec=True, t_park=123.5, meta={"k": "v"})
    back = SessionSnapshot.from_bytes(snap.to_bytes())
    assert back.session_id == "conv-1" and back.model == "gpt"
    assert back.tokens == [3, 1, 4, 1, 5] and back.emitted == [9, 2]
    assert back.remaining == 2 and back.cur == 7 and back.spec
    assert back.kv_dtype == "int8" and back.t_park == 123.5
    assert back.meta == {"k": "v"}
    np.testing.assert_array_equal(back.logits, snap.logits)
    # container kinds survive (the tree_map in the restore path relies
    # on tuple-vs-list structure matching the avals tree exactly)
    assert isinstance(back.planes, list)
    assert isinstance(back.planes[0], tuple)
    assert isinstance(back.planes[1], list)
    np.testing.assert_array_equal(back.planes[0][0], planes[0][0])
    assert back.planes[0][1].dtype == np.int8
    assert back.nbytes() == snap.nbytes()


def test_session_store_spill_corrupt_and_migration(tmp_path):
    d = str(tmp_path / "spill")

    def mk(sid, t_park, tok=5):
        return SessionSnapshot(session_id=sid, model="gpt",
                               tokens=[tok] * 4, t_park=t_park,
                               planes=[np.ones((2, 2), np.float32)])

    store = SessionStore(spill_dir=d, park_after_ms=0)   # write-through
    store.put(mk("s1", 10.0))
    blob_path, man_path = store._paths("s1")
    assert os.path.exists(blob_path) and os.path.exists(man_path)
    assert "s1" in store and len(store) == 1 and store.nbytes() > 0

    # a fresh store over the same dir (the SIGKILL-restart path) finds it
    store2 = SessionStore(spill_dir=d, park_after_ms=0)
    assert store2.peek_ids() == ["s1"]
    got = store2.take("s1")
    assert got is not None and got.tokens == [5] * 4
    assert not os.path.exists(blob_path)          # take removes every copy
    assert store2.take("s1") is None

    # a torn spill is a miss, never a crash — and the wreck is swept
    store2.put(mk("s2", 11.0))
    bp, _ = store2._paths("s2")
    fresh = SessionStore(spill_dir=d, park_after_ms=0)   # disk-only view
    with open(bp, "r+b") as f:
        f.seek(-3, os.SEEK_END)
        f.write(b"\xff\xff\xff")
    assert fresh.take("s2") is None
    assert not os.path.exists(bp)

    # migration transport: export moves, import keeps the newer t_park
    ram = SessionStore()
    ram.put(mk("s3", 20.0, tok=1))
    blob = ram.export_bytes("s3")
    assert blob is not None and "s3" not in ram
    dst = SessionStore()
    dst.put(mk("s3", 30.0, tok=2))                # fresher local turn
    assert dst.import_bytes(blob) is None         # stale replay loses
    assert dst.take("s3").tokens == [2] * 4
    dst.put(mk("s3", 10.0, tok=3))                # now the import is newer
    assert dst.import_bytes(blob) == "s3"
    assert dst.take("s3").tokens == [1] * 4


def test_flags_surface_validation_and_snapshot_restore():
    from paddle_tpu.framework import flags as _flags
    snap = flags_snapshot()
    assert _flags.flag("prefix_cache") is False            # off by default
    assert _flags.flag("session_store") is False
    try:
        set_flags({"FLAGS_prefix_cache": True,
                   "FLAGS_prefix_cache_hbm_mb": 64.0,
                   "FLAGS_session_store": True,
                   "FLAGS_session_store_dir": "/tmp/x",
                   "FLAGS_session_park_after_ms": 250})
        assert _flags.flag("prefix_cache_hbm_mb") == 64.0
        assert _flags.flag("session_park_after_ms") == 250
        with pytest.raises(Exception):
            set_flags({"FLAGS_prefix_cache_hbm_mb": -1.0})
        with pytest.raises(Exception):
            set_flags({"FLAGS_session_park_after_ms": -5})
        assert _flags.flag("prefix_cache_hbm_mb") == 64.0  # no clobber
    finally:
        flags_restore(snap)
    assert _flags.flag("prefix_cache") is False
    assert _flags.flag("session_store") is False


# -- slot-loop integration ----------------------------------------------------

def test_prefix_hit_bit_identical_and_counters():
    """Requests sharing a system prompt: the first publishes, the rest
    restore its blocks and chunk only their suffixes — outputs stay
    bit-identical to the stateless oracle and the hit accounting shows
    the reuse.  Zero steady recompiles across the cached admissions."""
    m = _gpt()
    gen = Generator(m, site="pfx:hit", seq_buckets=(8, 16, 32),
                    max_len=64)
    oracle = _oracle()
    pc = PrefixCache(block_tokens=8, block_nbytes=4096,
                     hbm_budget_mb=0.0)
    loop = SlotLoop(gen, slots=4, cache_len=64, chunk=8,
                    prefix_cache=pc)
    try:
        rng = random.Random(131)
        prefix = [rng.randrange(1, V) for _ in range(24)]
        reqs = [(prefix + [rng.randrange(1, V)
                           for _ in range(rng.randint(1, 6))],
                 rng.randint(1, 5)) for _ in range(8)]
        outs = [np.asarray(loop.submit(p, mn).result(timeout=120))
                .reshape(-1) for p, mn in reqs]
        mark = len(ledger.compile_events("pfx:hit"))
        outs += [np.asarray(loop.submit(p, mn).result(timeout=120))
                 .reshape(-1) for p, mn in reqs]
        assert len(ledger.compile_events("pfx:hit")) == mark
        for (p, mn), got in zip(reqs + reqs, outs):
            np.testing.assert_array_equal(got[:mn], _want(oracle, p, mn))
        assert loop.counters["prefix_hit_tokens"] >= 24 * (len(reqs) - 1)
        st = pc.stats()
        assert st["hits"] >= len(reqs) - 1 and st["blocks"] >= 3
        assert loop.signals()["prefix_cache_blocks"] == st["blocks"]
    finally:
        loop.close()


def test_prefix_eviction_pressure_stays_bit_identical():
    """An HBM budget of ~2 blocks forces constant eviction while
    lookups pin chains mid-restore: the ref-count discipline must keep
    every served token bit-identical under the churn."""
    m = _gpt()
    gen = Generator(m, site="pfx:evict", seq_buckets=(8, 16, 32),
                    max_len=64)
    oracle = _oracle()
    import jax.tree_util as tu
    from paddle_tpu.serving.cluster.handoff import _np_dtype
    block_nbytes = sum(
        int(np.prod(tuple(a.shape))) * _np_dtype(str(a.dtype)).itemsize
        for a in tu.tree_leaves(gen._block_avals(4, 8, 64)))
    pc = PrefixCache(block_tokens=8, block_nbytes=block_nbytes,
                     hbm_budget_mb=2.0 * block_nbytes / (1 << 20))
    loop = SlotLoop(gen, slots=4, cache_len=64, chunk=8,
                    prefix_cache=pc)
    try:
        rng = random.Random(151)
        prefixes = [[rng.randrange(1, V) for _ in range(16)]
                    for _ in range(3)]
        reqs = [(prefixes[k % 3] + [rng.randrange(1, V)], 3)
                for k in range(12)]
        futs = [loop.submit(p, mn) for p, mn in reqs]
        outs = [np.asarray(f.result(timeout=120)).reshape(-1)
                for f in futs]
        for (p, mn), got in zip(reqs, outs):
            np.testing.assert_array_equal(got[:mn], _want(oracle, p, mn))
        assert pc.stats()["evictions"] >= 1
        assert pc.nbytes() <= pc.budget_bytes
    finally:
        loop.close()


def _turn_roundtrip(gen_factory, oracle_factory, site, trials=2):
    """Shared multi-turn scaffold: turn 1 parks, turn 2 takes the
    snapshot, restores the planes and must answer exactly like a
    stateless prefill of the grown transcript — interleaved with
    one-shot churn so restores land in occupied, shifted slots."""
    gen = gen_factory(site)
    oracle = oracle_factory()
    store = SessionStore()
    loop = SlotLoop(gen, slots=4, cache_len=64, chunk=8,
                    session_store=store)
    try:
        for trial in range(trials):
            rng = random.Random(333 + trial)
            sid = f"conv-{trial}"
            transcript = [rng.randrange(1, V) for _ in range(10)]
            noise = [loop.submit([rng.randrange(1, V)
                                  for _ in range(rng.randint(1, 9))],
                                 rng.randint(1, 4))
                     for _ in range(3)]
            for turn in range(3):
                mn = rng.randint(2, 5)
                snap = store.take(sid)
                if turn > 0:
                    assert snap is not None       # parked between turns
                got = np.asarray(loop.submit(
                    transcript, mn, session_id=sid,
                    snapshot=snap).result(timeout=120)).reshape(-1)
                np.testing.assert_array_equal(
                    got[:mn], _want(oracle, transcript, mn))
                transcript = transcript + [int(t) for t in got[:mn]] \
                    + [rng.randrange(1, V) for _ in range(2)]
                if len(transcript) > 40:
                    break
            for f in noise:
                f.result(timeout=120)
        c = loop.counters
        assert c["parked"] >= 2 * trials and c["restored"] >= 2 * trials
        assert c["restore_pushes"] >= 1
    finally:
        loop.close()


def test_turn_park_restore_bit_identical_plain():
    m = _gpt()
    _turn_roundtrip(
        lambda site: Generator(m, site=site, seq_buckets=(8, 16, 32),
                               max_len=64),
        _oracle,
        "sess:plain")


def test_turn_park_restore_bit_identical_speculative():
    m, d = _gpt(), _draft()
    _turn_roundtrip(
        lambda site: SpeculativeGenerator(m, d, site=site,
                                          seq_buckets=(8, 16, 32),
                                          max_len=64, gamma=3),
        lambda: _oracle(speculative=True),
        "sess:spec")


def test_turn_park_restore_bit_identical_int8_kv():
    snap = flags_snapshot()
    try:
        set_flags({"FLAGS_kv_cache_dtype": "int8"})
        m = _gpt()
        _turn_roundtrip(
            lambda site: Generator(m, site=site, seq_buckets=(8, 16, 32),
                                   max_len=64),
            _oracle,
            "sess:int8")
    finally:
        flags_restore(snap)


def test_drain_parks_mid_generation_and_resumes_bit_identical():
    """park_sessions() mid-stream: the generating row snapshots with
    remaining budget, its future fails RETRYABLY, and resubmitting the
    same turn against the snapshot finishes with tokens bit-identical
    to an uninterrupted run."""
    m = _gpt()
    gen = Generator(m, site="sess:drain", seq_buckets=(8, 16, 32),
                    max_len=64)
    oracle = _oracle()
    store = SessionStore()
    loop = SlotLoop(gen, slots=2, cache_len=64, chunk=8,
                    session_store=store)
    prompt = [5, 9, 2, 33, 17, 8]
    try:
        fut = loop.submit(prompt, 24, session_id="drainee")
        # wait for the first committed token: a park during prefill
        # (nothing committed) deliberately snapshots nothing, and this
        # test is about the mid-GENERATION path
        deadline = time.monotonic() + 30.0
        while not loop.stats().get("ttft_p50_ms"):
            assert time.monotonic() < deadline, "row never activated"
            time.sleep(0.002)
        parked = loop.park_sessions(timeout=30.0)
        assert parked >= 1
        with pytest.raises(UnavailableError) as ei:
            fut.result(timeout=30)
        assert getattr(ei.value, "retry_after_s", None) is not None
        snap = store.take("drainee")
        assert snap is not None and snap.remaining > 0
        got = np.asarray(loop.submit(
            prompt, 24, session_id="drainee",
            snapshot=snap).result(timeout=120)).reshape(-1)
        np.testing.assert_array_equal(got[:24], _want(oracle, prompt, 24))
        assert store.take("drainee") is not None  # re-parked on finish
    finally:
        loop.close()


def test_a_resumed_row_takes_its_logits_up_once_and_continues_bit_identical():
    """A row parked mid-generation with its planes (a transcript of a
    chunk or more) resumes with no suffix chunk: nothing on the device
    made its activation logits, so the snapshot's row goes up, once,
    through the same row write, and is the only logits traffic of the
    window.  The park read them off the device plane the steps keep."""
    gen = Generator(_gpt(), site="sess:resume", seq_buckets=(8, 16, 32),
                    max_len=64)
    store = SessionStore()
    loop = SlotLoop(gen, slots=2, cache_len=64, chunk=8,
                    session_store=store)
    prompt = [5, 9, 2, 33, 17, 8, 41, 3, 27, 12]
    try:
        fut = loop.submit(prompt, 24, session_id="resumee")
        deadline = time.monotonic() + 30.0
        while not loop.stats().get("ttft_p50_ms"):
            assert time.monotonic() < deadline, "row never activated"
            time.sleep(0.002)
        assert isinstance(loop._logits, jax.Array)
        assert loop.park_sessions(timeout=30.0) == 1
        with pytest.raises(UnavailableError):
            fut.result(timeout=30)
        snap = store.take("resumee")
        assert snap.remaining > 0 and snap.planes is not None
        assert np.asarray(snap.logits).shape == (V,)
        before = dict(loop.counters)
        assert before["rows_activated"] == 1
        assert before["logits_bytes_via_host"] == 0
        got = np.asarray(loop.submit(
            prompt, 24, session_id="resumee",
            snapshot=snap).result(timeout=120)).reshape(-1)
        np.testing.assert_array_equal(got[:24], _want(_oracle(), prompt, 24))
        c = loop.counters
        assert c["restored"] == 1 and c["chunks"] == before["chunks"]
        assert c["rows_activated"] == 2
        assert c["logits_bytes_via_host"] == V * 4
    finally:
        loop.close()


# -- server + cluster integration ---------------------------------------------

def test_server_sessions_end_to_end_with_drain_and_spill(tmp_path):
    """The full server path: FLAGS_session_store + FLAGS_prefix_cache
    on, two conversation turns bit-match the oracle, drain() parks
    instead of finishing, and a SECOND server over the same spill dir
    (the SIGKILL-restart shape) restores the parked conversation and
    continues bit-identically."""
    flags = flags_snapshot()
    spill = str(tmp_path / "sessions")
    try:
        set_flags({"FLAGS_decode_slots": 4, "FLAGS_prefill_chunk": 8,
                   "FLAGS_session_store": True,
                   "FLAGS_session_store_dir": spill,
                   "FLAGS_prefix_cache": True})
        m = _gpt(seed=45)
        oracle = _oracle(45)
        rng = np.random.RandomState(9)
        p1 = rng.randint(1, V, 6).astype(np.int32)

        srv = serving.Server(serving.ServingConfig(workers=2))
        srv.register_decode("gpt", m, batch_buckets=(1, 2),
                            seq_buckets=(8, 16, 32), max_new_tokens=4,
                            max_len=64)
        srv.start()
        try:
            got1 = srv.submit_decode("gpt", [p1], max_new_tokens=4,
                                     session_id="conv").result(
                                         timeout=120)[0][0]
            np.testing.assert_array_equal(got1, _want(oracle, p1, 4))
            p2 = np.concatenate([p1, got1,
                                 rng.randint(1, V, 3)]).astype(np.int32)
            got2 = srv.submit_decode("gpt", [p2], max_new_tokens=4,
                                     session_id="conv").result(
                                         timeout=120)[0][0]
            np.testing.assert_array_equal(got2, _want(oracle, p2, 4))
            st = srv.stats("gpt")["slot_loop"]
            assert st["restored"] >= 1 and st["parked"] >= 2
            # multi-prompt session requests are rejected up front
            with pytest.raises(Exception):
                srv.submit_decode("gpt", [p1, p2], session_id="conv")
            report = srv.drain(timeout_s=30.0)
            assert report["drained"]
            assert "conv" in srv.session_store
            sig = srv.signals()
            assert sig["sessions_parked"] >= 1
            assert sig["session_store_bytes"] > 0
        finally:
            srv.stop()

        # restart over the same spill dir: the conversation survives
        srv2 = serving.Server(serving.ServingConfig(workers=2))
        srv2.register_decode("gpt", m, batch_buckets=(1, 2),
                             seq_buckets=(8, 16, 32), max_new_tokens=4,
                             max_len=64)
        srv2.start()
        try:
            p3 = np.concatenate([p2, got2,
                                 rng.randint(1, V, 2)]).astype(np.int32)
            got3 = srv2.submit_decode("gpt", [p3], max_new_tokens=4,
                                      session_id="conv").result(
                                          timeout=120)[0][0]
            np.testing.assert_array_equal(got3, _want(oracle, p3, 4))
            assert srv2.stats("gpt")["slot_loop"]["restored"] >= 1
            srv2.assert_zero_steady_state_recompiles()
        finally:
            srv2.stop()
    finally:
        flags_restore(flags)


def test_router_affinity_and_migration_on_retire():
    """Cluster plane: turn 2 follows session affinity back to the
    owner; retiring the owner drains (parking), migrates the parked
    session to the survivor, rewrites affinity, and turn 3 restores
    there — all three turns bit-identical to the oracle."""
    from paddle_tpu.serving.cluster.lifecycle import AutoscaleController
    from paddle_tpu.serving.cluster.router import LocalReplica, Router
    flags = flags_snapshot()
    try:
        set_flags({"FLAGS_decode_slots": 4, "FLAGS_prefill_chunk": 8,
                   "FLAGS_session_store": True,
                   "FLAGS_prefix_cache": True})
        m = _gpt(seed=45)
        oracle = _oracle(45)

        def _server():
            srv = serving.Server(serving.ServingConfig(workers=2))
            srv.register_decode("gpt", m, batch_buckets=(1, 2),
                                seq_buckets=(8, 16, 32), max_new_tokens=4,
                                max_len=64)
            return srv.start()

        s1, s2 = _server(), _server()
        router = Router(replicas=(LocalReplica(s1, "rA", role="both"),
                                  LocalReplica(s2, "rB", role="both")))
        try:
            rng = np.random.RandomState(7)
            p = rng.randint(1, V, 6).astype(np.int32)
            for _turn in range(2):
                got = router.run_decode("gpt", [p], max_new_tokens=4,
                                        session_id="conv")[0][0]
                np.testing.assert_array_equal(got, _want(oracle, p, 4))
                p = np.concatenate([p, got, rng.randint(1, V, 2)]) \
                    .astype(np.int32)
            owner = router.session_affinity("conv")
            assert owner in ("rA", "rB")

            ctrl = AutoscaleController(router, spawn=lambda rid, v: None,
                                       min_replicas=1,
                                       drain_timeout_s=20)
            rep = ctrl.retire(owner)
            assert rep["drained"] and rep["migrated_sessions"] >= 1
            other = "rB" if owner == "rA" else "rA"
            assert router.session_affinity("conv") == other
            survivor = s2 if owner == "rA" else s1
            assert "conv" in survivor.session_store

            got = router.run_decode("gpt", [p], max_new_tokens=4,
                                    session_id="conv")[0][0]
            np.testing.assert_array_equal(got, _want(oracle, p, 4))
            assert survivor.stats("gpt")["slot_loop"]["restored"] >= 1
        finally:
            router.close()
            s1.stop()
            s2.stop()
    finally:
        flags_restore(flags)


def test_session_off_path_is_inert():
    """Defaults (both flags off): no store is built, submit_decode
    ignores session identity beyond validation, and the slot loop
    reports no prefix/session accounting."""
    snap = flags_snapshot()
    try:
        set_flags({"FLAGS_decode_slots": 2, "FLAGS_prefill_chunk": 8})
        m = _gpt(seed=47)
        srv = serving.Server(serving.ServingConfig(workers=2))
        srv.register_decode("gpt", m, batch_buckets=(1,),
                            seq_buckets=(8,), max_new_tokens=3,
                            max_len=32)
        srv.start()
        try:
            assert srv.session_store is None
            rt = srv._models["gpt"]
            assert rt.prefix_cache is None
            out = srv.submit_decode("gpt", [np.arange(1, 5)],
                                    max_new_tokens=3,
                                    session_id="ignored").result(
                                        timeout=120)[0]
            assert out.shape == (1, 3)
            sig = srv.signals()
            assert "sessions_parked" not in sig
            assert "prefix_cache_blocks" not in sig
        finally:
            srv.stop()
    finally:
        flags_restore(snap)
