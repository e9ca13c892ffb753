"""The readers of the slot loop's own measurement (PR 24): each on a
hand-made ``ctx`` (its value, and None where the program has no such
field), the idle attribution on the recorded trace, and the serving
runner's CPU rehearsal reporting them."""
import gzip
import importlib
import os

import pytest

from benchmark.layer_metrics import _slot_loop
from benchmark.tests.test_runners import OPEN, SATURATED, _run

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "train_two_steps.xplane.pb.gz")
HARNESS_SPANS = ("make_batch", "train_step_call", "fetch_loss")

STATS = {
    "slots": 4, "steps": 50, "chunks": 120, "emitted_tokens": 80,
    "slot_steps_emitting": 80, "slot_steps_prefilling": 30,
    "slot_steps_drain_blocked": 50, "slot_steps_no_demand": 40,
    "phase_s": {"idle_wait": 9.0, "admit": 0.010, "chunk_dispatch": 0.050,
                "chunk_fetch": 3.0, "activate": 0.020, "step_dispatch": 0.015,
                "step_fetch": 2.0, "retire": 0.005},
    "phases_ms": {k: {"n": 7, "p50": 10.0 * i, "p90": 100.0 * i}
                  for i, k in enumerate(("handoff", "admit_wait", "prefill",
                                         "decode", "reply_hold",
                                         "arrival_ttft", "total"), 1)},
}
# what the parent's loop.stats() has: counts only
OLD_STATS = {"slots": 4, "steps": 50, "chunks": 120, "emitted_tokens": 80}


def _read(name, stats):
    reader = importlib.import_module("benchmark.layer_metrics." + name)
    return reader.compute({"counters": {"slot_loop": stats} if stats else {}})


@pytest.mark.parametrize("name,value", [
    ("server_handoff_p90_ms", 100.0),
    ("loop_admit_wait_p90_ms", 200.0),
    ("arrival_ttft_p90_ms", 600.0),
    ("reply_hold_p90_ms", 500.0),
    ("slot_prefill_pct", 15.0),
    ("slot_drain_blocked_pct", 25.0),
    ("loop_host_ms_per_step", 2.0),
])
def test_reader_value_and_none_without_the_field(name, value):
    assert _read(name, STATS) == pytest.approx(value)
    assert _read(name, OLD_STATS) is None
    assert _read(name, None) is None


def test_chunks_per_step_reads_counters_the_parent_has_too():
    assert _read("chunks_per_step", STATS) == pytest.approx(2.4)
    assert _read("chunks_per_step", OLD_STATS) == pytest.approx(2.4)
    assert _read("chunks_per_step", {"slots": 4, "steps": 0, "chunks": 0}) is None
    assert _read("chunks_per_step", None) is None


def test_idle_by_span_on_the_recorded_trace():
    from benchmark import trace_reduce
    gaps = _slot_loop.idle_by_span(DATA, HARNESS_SPANS)
    reduced = trace_reduce.reduce_profile(trace_reduce.load(DATA), HARNESS_SPANS)
    assert set(gaps) <= {"no span", *HARNESS_SPANS}
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)
    assert _slot_loop.idle_by_span(DATA, ()) == {
        "no span": pytest.approx(sum(gaps.values()))}


def test_device_idle_attributed_finds_the_capture_where_the_harness_puts_it(
        tmp_path, monkeypatch, capsys):
    from benchmark.layer_metrics import device_idle_attributed_pct as reader
    bench_dir = tmp_path / "benchmark"
    ctx = {"cell": {"bench_dir": str(bench_dir)}, "trace": {"window_s": 1.0}}
    assert reader.compute(ctx) is None                  # no capture there
    run = tmp_path / ".cache" / "benchmark_trace" / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    with gzip.open(DATA, "rb") as f:
        (run / "host.xplane.pb").write_bytes(f.read())
    monkeypatch.setattr(_slot_loop, "driver_span_names", lambda: HARNESS_SPANS)
    gaps = _slot_loop.idle_by_span(DATA, HARNESS_SPANS)
    want = 100.0 * (1.0 - gaps.get("no span", 0.0) / sum(gaps.values()))
    assert 0 < want < 100
    assert reader.compute(ctx) == pytest.approx(want)
    assert "idle seconds by driver span: {" in capsys.readouterr().out
    assert reader.compute(dict(ctx, trace=None)) is None   # an untraced run
    # a program without the spans (the parent): nothing to read, no error
    monkeypatch.setattr(_slot_loop, "driver_span_names", lambda: None)
    assert reader.compute(ctx) is None


def test_driver_span_names_are_the_programs_own():
    from paddle_tpu.serving import slots
    assert _slot_loop.driver_span_names() == slots.SPAN_NAMES
    from benchmark.layer_metrics import loop_host_ms_per_step
    assert set(loop_host_ms_per_step.HOST_PHASES) < set(slots.PHASES)


NEW = {"server_handoff_p90_ms", "loop_admit_wait_p90_ms", "reply_hold_p90_ms",
       "slot_prefill_pct", "slot_drain_blocked_pct", "loop_host_ms_per_step"}


def test_rehearsal_reports_the_new_metrics(gpt_tiny, open_tiny, closed_tiny):
    out = _run(OPEN, gpt_tiny, open_tiny, seconds=2.0, trace=True)
    assert out["correct"] is True
    m = out["metrics"]
    assert {n + ".open" for n in NEW} | {"arrival_ttft_p90_ms"} <= set(m)
    # no device plane on the CPU: the attribution has nothing to read
    assert "device_idle_attributed_pct.open" not in m
    assert m["arrival_ttft_p90_ms"]["value"] >= m["slot_ttft_p90_ms"]["value"] > 0
    assert 0 <= m["slot_prefill_pct.open"]["value"] <= 100
    assert m["loop_host_ms_per_step.open"]["value"] > 0
    out = _run(SATURATED, gpt_tiny, closed_tiny, seconds=2.0, trace=True)
    assert out["correct"] is True
    m = out["metrics"]
    assert {n + ".saturated" for n in NEW} | {"chunks_per_step"} <= set(m)
    assert m["chunks_per_step"]["value"] > 0
    occ = m["slot_occupancy_pct.saturated"]["value"]
    assert occ + m["slot_prefill_pct.saturated"]["value"] \
        + m["slot_drain_blocked_pct.saturated"]["value"] <= 100.0 + 1e-9
