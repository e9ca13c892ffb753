"""Unified runtime telemetry tests: Profiler scheduler phases, recompile
ledger (events, gauges, JSONL), chrome-trace validity with
executor/jit/train-step spans, and the flag-off no-op contract.

Reference strategy parity: paddle.profiler scheduler semantics
(make_scheduler wait/warmup/active/repeat), platform/profiler.h
RecordEvent + chrome-trace dump, monitor.h StatRegistry gauges.
"""
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.profiler import (Profiler, ProfilerState, export_chrome_tracing,
                                 ledger, make_scheduler)
from paddle_tpu.utils.monitor import LogWriter, stat_get


# -- scheduler ----------------------------------------------------------------

def test_make_scheduler_phase_transitions():
    sched = make_scheduler(closed=2, ready=1, record=2, repeat=2,
                           skip_first=1)
    C, R = ProfilerState.CLOSED, ProfilerState.READY
    REC, RET = ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN
    got = [sched(i) for i in range(12)]
    #        skip  |  cycle 1           |  cycle 2           | done
    assert got == [C, C, C, R, REC, RET, C, C, R, REC, RET, C]


def test_make_scheduler_repeats_forever_by_default():
    sched = make_scheduler(closed=1, ready=0, record=1)
    assert sched(100) == ProfilerState.CLOSED
    assert sched(101) == ProfilerState.RECORD_AND_RETURN


def test_tuple_scheduler_records_in_range():
    p = Profiler(scheduler=(2, 4), timer_only=True)
    p.start()
    assert p.current_state == ProfilerState.CLOSED
    p.step()                      # -> 1
    p.step()                      # -> 2: window opens
    assert p.current_state == ProfilerState.RECORD
    assert profiler.profiling_enabled()
    p.step()                      # -> 3: last record step
    assert p.current_state == ProfilerState.RECORD_AND_RETURN
    p.step()                      # -> 4: window closed
    assert p.current_state == ProfilerState.CLOSED
    assert not profiler.profiling_enabled()
    p.stop()


def test_profiler_windows_fire_on_trace_ready_per_cycle():
    rounds = []
    p = Profiler(scheduler=make_scheduler(closed=1, ready=0, record=1,
                                          repeat=2),
                 on_trace_ready=lambda prof: rounds.append(prof.round_count),
                 timer_only=True)
    p.start()
    for _ in range(6):
        p.step()
    p.stop()
    assert rounds == [1, 2]


# -- recompile ledger ---------------------------------------------------------

def test_recompile_ledger_two_signatures():
    ledger.clear()
    c0 = stat_get("jit_compile_count")
    h0 = stat_get("jit_cache_hit")
    ms0 = stat_get("jit_compile_ms_total")

    @paddle.jit.to_static
    def g(x):
        return x * 2 + 1

    a = paddle.to_tensor(np.zeros((2, 3), "float32"))
    b = paddle.to_tensor(np.zeros((4, 3), "float32"))
    g(a)
    g(b)          # new signature -> recompile
    g(a)          # cache hit
    g(b)          # cache hit

    evs = [e for e in ledger.compile_events() if e["kind"] == "jit"
           and e["site"].endswith(".g")]
    assert len(evs) == 2, evs
    assert all(e["ms"] > 0 for e in evs)
    assert evs[0]["diff"] == ["first compile at this site"]
    # the second event's diff names the changed arg shape
    assert any("(2, 3)" in d and "(4, 3)" in d for d in evs[1]["diff"]), evs
    assert stat_get("jit_compile_count") - c0 == 2
    assert stat_get("jit_cache_hit") - h0 >= 2
    assert stat_get("jit_compile_ms_total") >= ms0


def test_recompile_ledger_executor_site():
    import paddle_tpu.static as static
    ledger.clear()
    paddle.enable_static()
    try:
        main, startup = static.Program(), static.Program()
        with static.program_guard(main, startup):
            x = static.data("x", [2, 3], "float32")
            out = static.nn.fc(x, 2)
        exe = static.Executor()
        exe.run(startup)
        xd = np.zeros((2, 3), "float32")
        exe.run(main, feed={"x": xd}, fetch_list=[out])
        exe.run(main, feed={"x": xd}, fetch_list=[out])       # cached
        exe.run(main, feed={"x": np.zeros((5, 3), "float32")},
                fetch_list=[out])                             # new feed sig
    finally:
        paddle.disable_static()
    evs = [e for e in ledger.compile_events() if e["kind"] == "executor"]
    assert len(evs) >= 2
    # the feed-shape change is named in the diff of the second compile
    assert any("(5, 3)" in d for d in evs[-1]["diff"]), evs[-1]


def test_recompile_ledger_jsonl(tmp_path):
    d = str(tmp_path / "ledger")
    ledger.set_ledger_dir(d)
    try:
        @paddle.jit.to_static
        def h(x):
            return x + 3

        h(paddle.to_tensor(np.ones((2, 2), "float32")))
        events = LogWriter.read_events(d)
        assert "jit/compile" in events
        ev = events["jit/compile"][-1]
        assert ev["kind"] == "jit" and ev["ms"] > 0 and "diff" in ev
    finally:
        ledger.set_ledger_dir(None)


# -- step-breakdown spans + chrome trace --------------------------------------

def _build_static_runner():
    import paddle_tpu.static as static
    paddle.enable_static()
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = static.data("x", [2, 3], "float32")
        out = static.nn.fc(x, 2)
    exe = static.Executor()
    exe.run(startup)
    paddle.disable_static()
    return exe, main, out


def test_profiler_scheduler_trace_has_runtime_spans(tmp_path):
    """Acceptance: a scheduled Profiler run over >= wait+warmup+active
    steps exports valid chrome-trace JSON containing executor / jit /
    train-step spans; outside record windows the spans are no-ops."""
    import paddle_tpu.nn as nn
    from paddle_tpu.parallel import TrainStep

    # request traces that earlier tests of this process finished (with
    # their instant events) are not this profiler's: start from none
    from paddle_tpu.profiler import tracing
    tracing.clear()
    exe, main, out = _build_static_runner()
    xd = np.zeros((2, 3), "float32")

    @paddle.jit.to_static
    def f(x):
        return x * 1.5

    xt = paddle.to_tensor(np.ones((4,), "float32"))
    net = nn.Linear(3, 2)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=net.parameters())
    ts = TrainStep(net, opt, loss_fn=nn.CrossEntropyLoss())
    bx = np.random.RandomState(0).randn(8, 3).astype("float32")
    by = np.random.RandomState(1).randint(0, 2, (8,)).astype("int64")

    def one_step():
        f(xt)
        paddle.enable_static()
        try:
            exe.run(main, feed={"x": xd}, fetch_list=[out])
        finally:
            paddle.disable_static()
        ts(bx, by)

    one_step()       # warm every compile cache outside the profiled run

    d = str(tmp_path / "chrome")
    p = Profiler(scheduler=make_scheduler(closed=1, ready=1, record=2),
                 on_trace_ready=export_chrome_tracing(d),
                 timer_only=True)
    p.start()
    for _ in range(5):
        one_step()
        p.step()
    p.stop()

    with open(os.path.join(d, "paddle_tpu_trace.json")) as fjson:
        trace = json.load(fjson)
    names = {e["name"] for e in trace["traceEvents"]}
    assert any(n.startswith("jit::") for n in names), names
    assert any(n.startswith("executor::") for n in names), names
    assert any(n.startswith("train_step::") for n in names), names
    assert any(n.startswith("ProfileStep#") for n in names), names
    # every event is a well-formed complete event
    for e in trace["traceEvents"]:
        assert e["ph"] == "X" and e["dur"] >= 0


def test_instrumentation_noop_when_disabled():
    """With no profiler active and the flag off, the instrumented paths
    record no events (the off-path is one branch)."""
    from paddle_tpu.framework.flags import get_flags
    assert not get_flags("FLAGS_enable_profiler")["FLAGS_enable_profiler"]
    assert not profiler.profiling_enabled()

    exe, main, out = _build_static_runner()
    xd = np.zeros((2, 3), "float32")

    @paddle.jit.to_static
    def q(x):
        return x - 1

    before = len(profiler._events())
    q(paddle.to_tensor(np.ones((3,), "float32")))
    q(paddle.to_tensor(np.ones((3,), "float32")))
    paddle.enable_static()
    try:
        exe.run(main, feed={"x": xd}, fetch_list=[out])
        exe.run(main, feed={"x": xd}, fetch_list=[out])
    finally:
        paddle.disable_static()
    new = list(profiler._events())[before:]
    assert not [n for n, _, _ in new
                if "::" in n], f"spans leaked with profiling off: {new}"


def test_enable_profiler_flag_gates_spans():
    """FLAGS_enable_profiler turns the runtime spans on without a
    Profiler (the PADDLE_TPU_PROFILE always-on mode)."""
    paddle.set_flags({"FLAGS_enable_profiler": True})
    try:
        assert profiler.profiling_enabled()
        before = len(profiler._events())

        @paddle.jit.to_static
        def r(x):
            return x + 7

        r(paddle.to_tensor(np.ones((2,), "float32")))
        r(paddle.to_tensor(np.ones((2,), "float32")))
        new = list(profiler._events())[before:]
        assert any(n.startswith("jit::") for n, _, _ in new), new
    finally:
        paddle.set_flags({"FLAGS_enable_profiler": False})


def test_summary_aggregates_span_durations():
    p = Profiler(timer_only=True)
    p.start()
    with profiler.RecordEvent("agg_op"):
        pass
    with profiler.RecordEvent("agg_op"):
        pass
    s = profiler.summary_string()
    p.stop()
    line = [ln for ln in s.splitlines() if ln.startswith("agg_op")]
    assert line and "2" in line[0].split()[1]
