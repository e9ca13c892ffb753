"""What the slot loop measures about itself: the driver thread's phases
(``slots.PHASES`` / ``SPAN_NAMES``: flat ``profiler.span``s and
``stats()["phase_s"]``), a request's life from one set of stamps
(``phases_ms``, ``decode_slot_phase_seconds``, the FLAGS_trace tree) and
the split of every slot-step (``counters["slot_steps_*"]``)."""
import glob
import importlib.util
import os
import random
import sys
import threading
import time

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler, serving
from paddle_tpu.framework.flags import (flags_restore, flags_snapshot,
                                        set_flags)
from paddle_tpu.profiler import tracing
from paddle_tpu.profiler.metrics import default_registry
from paddle_tpu.serving import slots
from paddle_tpu.serving.slots import SlotLoop
from paddle_tpu.text.generation import Generator
from paddle_tpu.text.models.gpt import GPTConfig, GPTModel

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V = 64
FIVE = ("handoff", "admit_wait", "prefill", "decode", "reply_hold")
STATES = ("emitting", "prefilling", "drain_blocked", "no_demand")


def _gpt(seed=21):
    paddle.seed(seed)
    m = GPTModel(GPTConfig.tiny(vocab_size=V, hidden_size=32, layers=2,
                                heads=2, seq=64))
    m.eval()
    return m


def _loop(seed=21, slots_=4, cache_len=64, chunk=8, **kw):
    gen = Generator(_gpt(seed), seq_buckets=(8, 16, 32), max_len=64)
    return SlotLoop(gen, slots=slots_, cache_len=cache_len, chunk=chunk, **kw)


def _mixed(rng, n):
    """Short and long prompts, short and long answers."""
    return [([rng.randrange(V) for _ in range(rng.choice((2, 5, 11, 19)))],
             rng.choice((1, 3, 6, 10))) for _ in range(n)]


def _spy_replied(loop):
    """Every request whose life the loop closed, in order."""
    seen, real = [], loop.replied

    def replied(req, t_reply=None):
        real(req, t_reply)
        seen.append(req)

    loop.replied = replied
    return seen


@pytest.fixture
def flags_guard():
    snap = flags_snapshot()
    try:
        yield
    finally:
        flags_restore(snap)
        tracing.set_trace_dir(None)
        tracing.clear()


# -- D: every slot-step accounted for ----------------------------------------

def test_slot_steps_sum_to_steps_times_slots_and_emitting_is_emitted():
    loop = _loop()
    try:
        reqs = _mixed(random.Random(3), 14)
        futs = [loop.submit(p, mn) for p, mn in reqs[:9]]
        futs[0].result(timeout=120)
        futs += [loop.submit(p, mn) for p, mn in reqs[9:]]
        for f in futs:
            f.result(timeout=120)
    finally:
        loop.close()
    c = loop.stats()
    split = [c[f"slot_steps_{k}"] for k in STATES]
    assert c["steps"] > 0 and sum(split) == c["steps"] * loop.S
    assert c["slot_steps_emitting"] == c["emitted_tokens"] \
        == sum(mn for _, mn in reqs)
    assert c["slot_steps_prefilling"] > 0     # 14 requests over 4 slots
    assert c["slot_steps_drain_blocked"] == 0   # 64 columns hold them all
    # the typed counters carry the same split
    reg = default_registry().get("decode_slot_steps_total")
    assert reg.labels(model="decode", state="emitting").value \
        >= c["slot_steps_emitting"]


@pytest.mark.parametrize("cache_len,blocked", [(32, True), (64, False)])
def test_ring_too_small_for_the_head_counts_drain_blocked(cache_len, blocked):
    """Two slots; answers of 4 and 20 tokens start at column 8, a third
    request of 8 + 20 columns waits behind them.  When the short row
    retires at column 12 the third does not fit a ring of 32, so its slot
    stands empty until the long row has drained and the session restarts;
    a ring of 64 takes it at once."""
    resets = default_registry().get("decode_slot_session_resets_total") \
        .labels(model=f"ring{cache_len}")
    before = resets.value
    loop = _loop(seed=39, slots_=2, cache_len=cache_len,
                 model=f"ring{cache_len}")
    try:
        futs = [loop.submit([1, 2, 3, 4, 5, 6], n) for n in (4, 20, 20)]
        for f in futs:
            f.result(timeout=120)
    finally:
        loop.close()
    c = loop.stats()
    assert sum(c[f"slot_steps_{k}"] for k in STATES) == c["steps"] * 2
    if blocked:
        assert c["slot_steps_drain_blocked"] > 0
        assert c["session_resets"] == 1 and resets.value == before + 1
    else:
        assert c["slot_steps_drain_blocked"] == 0
        assert c["session_resets"] == 0 and resets.value == before


@pytest.mark.parametrize("eos", [None, 25], ids=["by-count", "end-token"])
def test_snapshots_stay_whole_under_concurrent_resets(eos):
    """A step's counters are committed in one piece under the loop's lock:
    whatever moment other threads pick to read or zero them, the four
    states sum to steps x S and emitting equals the tokens emitted.  With
    an end token (one this model takes often) rows are passed by for a
    step before the host retires them: they count with the empty slots."""
    loop = _loop(seed=29, eos_token_id=eos)
    stop, torn, lag, add = threading.Event(), [], [], loop._add

    def adding(key, n, chunk=False):
        if key == "slot_steps_retire_lag":
            lag.append(n)
        add(key, n, chunk)

    loop._add = adding

    def poke():
        while not stop.is_set():
            st = loop.stats()
            if sum(st[f"slot_steps_{k}"] for k in STATES) != st["steps"] * loop.S \
                    or st["slot_steps_emitting"] != st["emitted_tokens"]:
                torn.append(st)
            loop.reset_stats()

    pokers = [threading.Thread(target=poke, daemon=True) for _ in range(8)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in pokers:
            t.start()
        for wave in range(3):
            futs = [loop.submit(p, mn)
                    for p, mn in _mixed(random.Random(wave), 10)]
            for f in futs:
                f.result(timeout=120)
    finally:
        stop.set()
        for t in pokers:
            t.join(timeout=30)
        sys.setswitchinterval(interval)
        loop.close()
    assert not any(t.is_alive() for t in pokers)
    assert not torn, torn[:2]
    assert (sum(lag) > 0) == (eos is not None)


def test_the_in_flight_counters_are_committed_with_steps_and_reset(
        monkeypatch):
    """``steps_read_ready`` counts the read-backs that waited less than
    ``READ_READY_S`` (none under a threshold of 0, all under a huge one),
    ``slot_steps_retire_lag`` the slot-steps of rows the step passed by;
    both are zeroed with the rest, and a speculative loop, which reads
    every step before the next, has neither."""
    for threshold, ready in ((0.0, lambda st: 0), (1e9, lambda st: st["steps"])):
        monkeypatch.setattr(slots, "READ_READY_S", threshold)
        loop = _loop(seed=29, eos_token_id=25)
        try:
            for f in [loop.submit(p, mn)
                      for p, mn in _mixed(random.Random(1), 10)]:
                f.result(timeout=120)
        finally:
            loop.close()
        st = loop.stats()
        assert st["steps"] > 0 and st["steps_read_ready"] == ready(st)
        assert 0 < st["slot_steps_retire_lag"] \
            <= st["slot_steps_no_demand"] + st["slot_steps_drain_blocked"]
        loop.reset_stats()
        st = loop.stats()
        assert st["steps_read_ready"] == st["slot_steps_retire_lag"] == 0
    from paddle_tpu.text.speculative import SpeculativeGenerator
    paddle.seed(101)
    draft = GPTModel(GPTConfig.tiny(vocab_size=V, hidden_size=16, layers=1,
                                    heads=2, seq=64))
    draft.eval()
    spec = SlotLoop(SpeculativeGenerator(
        _gpt(), draft, seq_buckets=(8, 16, 32), max_len=64, gamma=3),
        slots=2, cache_len=64, chunk=8)
    try:
        assert "steps_read_ready" not in spec.stats()
        assert "slot_steps_retire_lag" not in spec.stats()
    finally:
        spec.close()


# -- C: a request's life -------------------------------------------------------

def test_five_phases_sum_to_reply_minus_arrival():
    loop = _loop(seed=23)
    seen = _spy_replied(loop)
    try:
        reqs = _mixed(random.Random(5), 10)
        t_arrival = time.monotonic() - 0.25    # as a Server would pass it
        rows = [loop.enqueue(p, mn, t_arrival=t_arrival) for p, mn in reqs[:5]]
        futs = [loop.submit(p, mn) for p, mn in reqs[5:]]
        for r in rows:
            r.future.result(timeout=120)
            assert r.t_retire is not None and r.t_reply is None   # deferred
            loop.replied(r)
            loop.replied(r)                    # a second call adds nothing
        for f in futs:
            f.result(timeout=120)
    finally:
        loop.close()
    lives = {id(r): r for r in seen}.values()
    assert len(lives) == 10
    for r in lives:
        ph = r.phases()
        assert sum(ph[k] for k in FIVE) == pytest.approx(
            r.t_reply - r.t_arrival, abs=1e-9)
        assert ph["total"] == r.t_reply - r.t_arrival
        assert ph["arrival_ttft"] == pytest.approx(
            ph["handoff"] + ph["admit_wait"] + ph["prefill"], abs=1e-9)
        assert all(v >= 0 for v in ph.values())
        if r.deferred_reply:
            assert ph["handoff"] >= 0.25 and ph["reply_hold"] > 0
        else:                                  # a bare loop replies at retire
            assert ph["handoff"] == 0 and ph["reply_hold"] == 0
    st = loop.stats()["phases_ms"]
    assert set(st) == set(slots.REQUEST_PHASES)
    assert all(v["n"] == 10 for v in st.values())
    assert st["total"]["p50"] <= st["total"]["p90"]
    hist = default_registry().get("decode_slot_phase_seconds")
    assert hist.labels(model="decode", phase="total").count >= 10


def test_server_reply_hold_is_the_wait_for_batch_mates(flags_guard):
    """Four requests packed into one batch of bucket 4, answers of 2, 2, 4
    and 14 tokens: the worker resolves them together, so the short rows
    are held until the longest retires."""
    set_flags({"FLAGS_decode_slots": 4, "FLAGS_prefill_chunk": 8})
    srv = serving.Server(serving.ServingConfig(workers=1,
                                               batch_timeout_ms=400.0))
    srv.register_decode("gpt", _gpt(45), batch_buckets=(1, 2, 4),
                        seq_buckets=(8, 16), max_new_tokens=14, max_len=32)
    srv.start()
    try:
        rt = srv._models["gpt"]
        batches, real = [], rt.execute

        def execute(batch):
            batches.append(list(batch.requests))
            return real(batch)

        rt.execute = execute
        futs = [srv.submit_decode("gpt", [np.arange(1, 6)], max_new_tokens=n)
                for n in (2, 2, 4, 14)]
        for f in futs:
            f.result(timeout=120)
        packed = max(batches, key=len)
        assert len(packed) == 4, [len(b) for b in batches]
        rows = [r.slot_rows[0] for r in packed]
        assert all(row.t_arrival == r.t_enqueue_mono
                   for row, r in zip(rows, packed))
        by_len = {row.max_new: row for row in rows}
        hold = {n: row.phases()["reply_hold"] for n, row in by_len.items()}
        # the longest row is replied as soon as the worker sees it retired;
        # each shorter one waited, beyond that, from its own retirement to
        # the longest's (the replies themselves are microseconds apart)
        assert hold[2] > hold[4] > hold[14] >= 0
        assert hold[14] < 1.0
        for n in (2, 4):
            assert hold[n] - hold[14] == pytest.approx(
                by_len[14].t_retire - by_len[n].t_retire, abs=5e-3)
        st = srv.stats("gpt")["slot_loop"]["phases_ms"]
        assert st["reply_hold"]["n"] == 4 and st["handoff"]["p90"] > 0
    finally:
        srv.stop()


def test_attn_blocks_follow_the_frontier_and_the_oldest_live_start(
        monkeypatch):
    """The span the step's attention reads (ISSUE 28), over the run of
    the test above that crosses a session restart, in blocks of 16: the
    counters are the host's arithmetic on what each step was handed; a
    row that is not generating is handed ``start = C``, so the device's
    own bound (the lowest ``start`` of ALL rows) is the live rows'; the
    span falls back to the first block when the ring restarts."""
    from paddle_tpu.nn.functional import attention
    monkeypatch.setattr(attention, "DECODE_BLOCK", 16)
    loop = _loop(seed=39, slots_=2, cache_len=32)
    seen, real = [], loop._step

    def step(*args):
        start, _finished, active, _joined, pos = args[-5:]
        seen.append((int(pos), np.array(start), np.array(active)))
        return real(*args)

    loop._step = step
    try:
        futs = [loop.submit([1, 2, 3, 4, 5, 6], n) for n in (4, 20, 20)]
        for f in futs:
            f.result(timeout=120)
    finally:
        loop.close()
    c = loop.stats()
    assert c["session_resets"] == 1 and c["steps"] == len(seen)
    assert c["attn_blocks_total"] == c["steps"] * 32 // 16
    reads = []
    for pos, start, active in seen:
        assert (start[~active] == 32).all()
        assert active.any() and (start[active] <= pos).all()
        assert start.min() == start[active].min()
        reads.append(pos // 16 + 1 - start[active].min() // 16)
    assert c["attn_blocks_read"] == sum(reads)
    restart = next(i for i in range(1, len(seen))
                   if seen[i][0] < seen[i - 1][0])
    assert reads[restart - 1] == 2 and reads[restart] == 1
    assert 0 < c["attn_blocks_read"] < c["attn_blocks_total"]
    loop.reset_stats()
    c = loop.stats()
    assert c["attn_blocks_read"] == c["attn_blocks_total"] == c["steps"] == 0


def test_attn_blocks_are_counted_for_the_plain_step_over_kv_planes_only(
        flags_guard):
    """The int8 cache is dequantised whole and the speculative step's
    verify block has no window: no span to report, so no counter."""
    set_flags({"FLAGS_kv_cache_dtype": "int8"})
    loop = _loop(seed=25)
    try:
        assert "attn_blocks_read" not in loop.stats()
    finally:
        loop.close()


@pytest.mark.parametrize("takes", [True, False],
                         ids=["in-place", "sliced"])
def test_chunk_row_is_a_fact_of_the_program_in_stats_and_the_event(
        takes, monkeypatch):
    """How the chunk program reaches its row (ISSUE 46), decided from the
    model's ``cached_forward_takes_row`` when the program is traced: in
    ``stats()`` beside ``kv_heads_per_lane_row``, in the ``generate_chunk``
    event's ``extra`` (not in the step's), and not reset with the counters."""
    from paddle_tpu.profiler import ledger
    if not takes:
        monkeypatch.delattr(GPTModel, "cached_forward_takes_row")
    want = "in_place" if takes else "sliced"
    gen = Generator(_gpt(seed=27), seq_buckets=(8, 16, 32), max_len=64,
                    site=f"test_chunk_row:{want}")
    loop = SlotLoop(gen, slots=3, cache_len=64, chunk=8)
    try:
        assert loop.submit([3, 1, 4, 1, 5, 9, 2, 6, 5, 3], 4).result(
            timeout=120).shape[-1] == 4
        assert loop.stats()["chunk_row"] == want
        loop.reset_stats()
        st = loop.stats()
        assert st["chunk_row"] == want and st["chunks"] == 0
    finally:
        loop.close()
    evs = {e["kind"]: e for e in ledger.compile_events(gen.site)}
    assert evs["generate_chunk"]["chunk_row"] == want
    assert "chunk_row" not in evs["generate_step"]


def test_reset_stats_zeroes_phases_and_slot_steps():
    loop = _loop(seed=25)
    try:
        for f in [loop.submit([1, 2, 3], 4) for _ in range(3)]:
            f.result(timeout=120)
        while loop.stats()["phase_s"]["retire"] == 0:   # committed per iteration
            time.sleep(0.005)
        st = loop.stats()
        assert set(st["phase_s"]) == set(slots.PHASES)
        assert st["phase_s"]["step_dispatch"] > 0 and st["phase_s"]["admit"] > 0
        assert st["phases_ms"]["total"]["n"] == 3
        assert st["slot_steps_emitting"] == 12
    finally:
        loop.close()
    # the driver has ended: nothing refills what the reset zeroes
    loop.reset_stats()
    st = loop.stats()
    assert st["phase_s"] == dict.fromkeys(slots.PHASES, 0.0)
    assert st["phases_ms"] == {}
    assert all(st[f"slot_steps_{k}"] == 0 for k in STATES)
    assert st["steps"] == st["emitted_tokens"] == 0


# -- A, B: spans ---------------------------------------------------------------

def test_span_records_an_event_only_inside_a_profiler_window():
    events = profiler._events()
    events.clear()
    with profiler.span("outside::window"):
        pass
    sp = profiler.span("outside::begin_end")
    sp.begin()
    sp.end()
    assert not events
    with profiler.Profiler(timer_only=True):
        with profiler.span("inside::window"):
            pass
    with profiler.span("outside::again"):
        pass
    names = {e[0] for e in events}
    assert "inside::window" in names
    assert not {n for n in names if n.startswith("outside::")}


@pytest.mark.parametrize("prefix", [False, True],
                         ids=["plain", "prefix_cache"])
def test_driver_spans_in_a_capture_flat_and_on_one_thread(tmp_path, prefix):
    """A jax.profiler capture of a tiny loop: every name of SPAN_NAMES
    appears, all on the driver thread's line, and no two overlap.  All
    but ``chunk_fetch``: this model's chunks hand back no counts, and
    their logits are not fetched (tests/test_hybrid_decoder.py reads that
    phase for a model that counts); and ``restore`` and ``publish`` only
    in a loop that has a prefix cache (the six prompts overlap in their
    tokens, and the last repeats the first: its block is restored)."""
    from paddle_tpu.serving.prefix_cache import PrefixCache
    loop = _loop(seed=27, prefix_cache=PrefixCache(8, 1) if prefix else None)
    absent = {"slot_loop::chunk_fetch"} | (
        set() if prefix else {"slot_loop::restore", "slot_loop::publish"})
    try:
        loop.submit([1, 2, 3], 2).result(timeout=120)       # thread + warm
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            time.sleep(0.12)                   # the loop idles: idle_wait
            futs = [loop.submit([rng % V for rng in range(k, k + 11)], 4)
                    for k in range(6)]
            for f in futs:
                f.result(timeout=120)
            loop.submit([rng % V for rng in range(11)], 4).result(timeout=120)
        finally:
            jax.profiler.stop_trace()
    finally:
        loop.close()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[0]
    prof = jax.profiler.ProfileData.from_file(path)
    lines = {}
    for plane in prof.planes:
        for line in plane.lines:
            evs = [(float(e.start_ns), float(e.start_ns) + float(e.duration_ns),
                    e.name) for e in line.events
                   if e.name in slots.SPAN_NAMES]
            if evs:
                lines[(plane.name, line.name)] = sorted(evs)
    assert len(lines) == 1, list(lines)
    (evs,) = lines.values()
    assert {n for _, _, n in evs} == set(slots.SPAN_NAMES) - absent
    for (_, end, a), (start, _, b) in zip(evs, evs[1:]):
        assert start >= end, (a, b, start - end)


# -- C under FLAGS_trace: the request tree ------------------------------------

def test_traced_slot_request_tree_is_complete(flags_guard):
    spec = importlib.util.spec_from_file_location(
        "obs_report", os.path.join(REPO, "tools", "obs_report.py"))
    obs_report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(obs_report)
    set_flags({"FLAGS_trace": "full", "FLAGS_decode_slots": 4,
               "FLAGS_prefill_chunk": 8})
    tracing.clear()
    srv = serving.Server(serving.ServingConfig(workers=2))
    srv.register_decode("gpt", _gpt(45), batch_buckets=(1, 2),
                        seq_buckets=(8, 16), max_new_tokens=6, max_len=32)
    srv.start()
    try:
        futs = [srv.submit_decode("gpt", [np.arange(1, 2 + n)],
                                  max_new_tokens=2 + n % 4) for n in range(6)]
        for f in futs:
            f.result(timeout=120)
    finally:
        srv.stop()
    chains = {}
    for s in tracing.finished_spans():
        chains.setdefault(s["trace_id"], []).append(s)
    chains = [ss for ss in chains.values()
              if any(s["name"] == "request" for s in ss)]
    assert len(chains) == 6
    for ss in chains:
        root = [s for s in ss if s["parent_id"] is None][0]
        assert root["name"] == "request" and root["attrs"]["kind"] == "decode"
        kids = sorted((s for s in ss if s is not root), key=lambda s: s["t0"])
        assert all(s["parent_id"] == root["span_id"] for s in kids)
        assert {s["name"] for s in kids} == {
            "queue_wait", "pack", "slot_queue", "slot_prefill", "slot_decode",
            "reply_hold", "reply"}
        ok, problems = obs_report.check_chain(ss)
        assert ok, problems
        life = [s for name in ("slot_queue", "slot_prefill", "slot_decode",
                               "reply_hold") for s in kids if s["name"] == name]
        for a, b in zip(life, life[1:]):       # cut from one set of stamps
            assert a["t0"] + a["dur_ms"] / 1e3 == pytest.approx(b["t0"],
                                                                abs=1e-5)
    # without the slot spans the scanned chain is still what is asked for
    ok, problems = obs_report.check_chain(
        [s for s in chains[0] if not s["name"].startswith("slot_")])
    assert not ok and "prefill" in problems[0]
