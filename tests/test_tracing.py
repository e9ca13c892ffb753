"""Request-scoped span tracing (profiler.tracing): FLAGS_trace gating and
sampling, span nesting + ring + JSONL sink, recompile-ledger auto-attach,
chrome-trace merge with the PR-1 profiler timeline, the serving request
chain (dense + decode on one server, zero steady-state recompiles with
FLAGS_trace=full), the train-step phase breakdown, and the
tools/obs_report.py joiner."""
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import serving
from paddle_tpu.framework.flags import (flags_restore, flags_snapshot,
                                        set_flags)
from paddle_tpu.profiler import ledger, tracing
from paddle_tpu.profiler.metrics import default_registry
from paddle_tpu.static import InputSpec
from paddle_tpu.utils.monitor import LogWriter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def flags_guard():
    snap = flags_snapshot()
    try:
        yield
    finally:
        flags_restore(snap)
        tracing.set_trace_dir(None)
        tracing.clear()


# -- gating + core span mechanics --------------------------------------------

def test_trace_default_off_no_spans(flags_guard):
    assert tracing.mode() == "off"
    assert not tracing.enabled()
    assert tracing.start_span("r") is None
    with tracing.span("x") as s:
        assert s is None
    before = len(tracing.finished_spans())

    @paddle.jit.to_static
    def f(x):
        return x * 2

    f(paddle.to_tensor(np.ones((2,), "float32")))
    assert len(tracing.finished_spans()) == before


def test_span_nesting_ring_and_attrs(flags_guard):
    set_flags({"FLAGS_trace": "full"})
    tracing.clear()
    with tracing.span("root", model="m") as r:
        assert tracing.current_span() is r
        with tracing.span("child") as c:
            assert c.parent_id == r.span_id
            assert c.trace_id == r.trace_id
            tracing.event("tick", k=1)
        assert tracing.current_span() is r
    assert tracing.current_span() is None
    spans = tracing.finished_spans()
    assert [s["name"] for s in spans] == ["child", "root"]
    child, root = spans
    assert root["parent_id"] is None and root["attrs"] == {"model": "m"}
    assert child["events"][0]["name"] == "tick"
    assert child["events"][0]["k"] == 1
    assert root["dur_ms"] >= child["dur_ms"] >= 0
    assert root["wall"] > 0


def test_explicit_stamp_children_and_finish_idempotent(flags_guard):
    set_flags({"FLAGS_trace": "full"})
    tracing.clear()
    import time
    r = tracing.start_span("request")
    t0 = time.monotonic()
    t1 = t0 + 0.25
    c = tracing.child(r, "queue_wait", t0, t1)
    assert abs(c.dur - 0.25) < 1e-6
    tracing.finish(r)
    tracing.finish(r)                          # idempotent
    spans = tracing.finished_spans()
    assert [s["name"] for s in spans] == ["queue_wait", "request"]
    assert abs(spans[0]["dur_ms"] - 250.0) < 0.01


def test_sampling_stride_is_deterministic(flags_guard):
    set_flags({"FLAGS_trace": "sample",
               "FLAGS_trace_sample_rate": 0.5})
    got = [tracing.start_span("r") is not None for _ in range(10)]
    assert sum(got) == 5                        # every 2nd, any phase
    set_flags({"FLAGS_trace_sample_rate": 1.0})
    assert all(tracing.start_span("r") is not None for _ in range(5))


def test_trace_jsonl_sink(flags_guard, tmp_path):
    set_flags({"FLAGS_trace": "full"})
    d = str(tmp_path / "traces")
    tracing.set_trace_dir(d)
    with tracing.span("root"):
        with tracing.span("inner"):
            pass
    evs = LogWriter.read_events(d)
    assert len(evs["trace/span"]) == 2
    names = {e["name"] for e in evs["trace/span"]}
    assert names == {"root", "inner"}


def test_ledger_compile_event_attaches_to_active_span(flags_guard):
    set_flags({"FLAGS_trace": "full"})
    tracing.clear()

    @paddle.jit.to_static
    def g(x):
        return x * 3 + 1

    with tracing.span("step") as s:
        g(paddle.to_tensor(np.ones((3, 2), "float32")))
    rec = tracing.finished_spans()[-1]
    assert rec["name"] == "step"
    compiles = [e for e in rec["events"] if e["name"] == "compile"]
    assert len(compiles) == 1
    assert compiles[0]["kind"] == "jit" and compiles[0]["ms"] > 0
    # a cache hit attaches nothing
    with tracing.span("step2"):
        g(paddle.to_tensor(np.ones((3, 2), "float32")))
    rec2 = tracing.finished_spans()[-1]
    assert not [e for e in rec2["events"] if e["name"] == "compile"]


def test_chrome_export_merges_profiler_timeline(flags_guard, tmp_path):
    from paddle_tpu import profiler
    set_flags({"FLAGS_trace": "full"})
    tracing.clear()
    with tracing.span("request", model="m"):
        with tracing.span("execute"):
            pass
    p = profiler.Profiler(timer_only=True)
    p.start()
    with profiler.RecordEvent("host_op"):
        pass
    path = str(tmp_path / "merged.json")
    tracing.export_chrome_trace(path)
    p.stop()
    with open(path) as f:
        trace = json.load(f)
    evs = trace["traceEvents"]
    pids = {e["pid"] for e in evs}
    assert pids == {0, 1}                      # host timeline + traces
    host = [e for e in evs if e["pid"] == 0]
    spans = [e for e in evs if e["pid"] == 1]
    assert any(e["name"] == "host_op" for e in host)
    assert {e["name"] for e in spans} == {"request", "execute"}
    for e in evs:
        assert e["ph"] in ("X", "i") and e["ts"] >= 0


# -- serving: the full request chain -----------------------------------------

def _export_mlp(tmp_path, name="m"):
    net = nn.Sequential(nn.Linear(6, 8), nn.ReLU(), nn.Linear(8, 3))
    net.eval()
    prefix = str(tmp_path / name)
    serving.export_for_serving(net, prefix, [InputSpec([None, 6])],
                               buckets=(1, 2, 4))
    return net, prefix


DENSE_CHAIN = {"queue_wait", "pack", "h2d", "execute", "d2h", "reply"}
DECODE_CHAIN = {"queue_wait", "pack", "prefill", "decode", "reply"}


def _chains(spans):
    by = {}
    for s in spans:
        by.setdefault(s["trace_id"], []).append(s)
    return by


def _assert_well_nested(ss):
    roots = [s for s in ss if s["parent_id"] is None]
    assert len(roots) == 1, ss
    root = roots[0]
    r0 = root["t0"]
    r1 = root["t0"] + root["dur_ms"] / 1e3
    for c in ss:
        if c is root:
            continue
        assert c["t0"] >= r0 - 5e-3, (c, root)
        assert c["t0"] + c["dur_ms"] / 1e3 <= r1 + 5e-3, (c, root)
    return root


def test_mixed_dense_decode_traffic_full_trace_zero_recompiles(
        flags_guard, tmp_path):
    """Acceptance: FLAGS_trace=full under mixed dense+decode traffic on
    one server — every completed request has a complete, well-nested
    span chain; decode spans carry the measured per_token_ms and no
    invented per-token events; the zero-steady-
    state-recompile invariant holds (tracing never adds a compile key)."""
    from paddle_tpu.text.models.gpt import GPTConfig, GPTModel
    set_flags({"FLAGS_trace": "full"})
    d = str(tmp_path / "traces")
    tracing.set_trace_dir(d)
    tracing.clear()
    _, prefix = _export_mlp(tmp_path)
    paddle.seed(11)
    gpt = GPTModel(GPTConfig.tiny(vocab_size=32, hidden_size=16,
                                  layers=1, heads=2, seq=32))
    gpt.eval()
    srv = serving.Server(serving.ServingConfig(workers=2,
                                               batch_timeout_ms=1.0))
    srv.register("mlp", prefix, buckets=(1, 2, 4))
    srv.register_decode("gpt", gpt, batch_buckets=(1, 2), seq_buckets=(8,),
                        max_new_tokens=3, max_len=16)
    srv.start()
    try:
        rng = np.random.RandomState(0)
        futs = []
        for i in range(8):
            rows = int(rng.randint(1, 4))
            futs.append(srv.submit(
                "mlp", [rng.randn(rows, 6).astype("float32")]))
            prompts = [rng.randint(1, 32, int(rng.randint(1, 8)))
                       for _ in range(int(rng.randint(1, 3)))]
            futs.append(srv.submit_decode("gpt", prompts,
                                          max_new_tokens=2))
        for f in futs:
            f.result(timeout=120)
        srv.assert_zero_steady_state_recompiles()
    finally:
        srv.stop()
    spans = LogWriter.read_events(d)["trace/span"]
    chains = _chains(spans)
    assert len(chains) == 16
    n_dense = n_decode = 0
    for tid, ss in chains.items():
        root = _assert_well_nested(ss)
        names = {s["name"] for s in ss if s["parent_id"] is not None}
        kind = root["attrs"]["kind"]
        if kind == "dense":
            assert DENSE_CHAIN <= names, (tid, names)
            n_dense += 1
        else:
            assert DECODE_CHAIN <= names, (tid, names)
            # the scan is one device program: the span carries what was
            # measured (the fenced window over its steps) and invents no
            # per-token events
            dec = [s for s in ss if s["name"] == "decode"][0]
            steps = dec["attrs"]["steps"]       # the engine's scan length
            assert steps >= 2                   # max_new_tokens=2
            assert dec["attrs"]["per_token_ms"] == pytest.approx(
                dec["dur_ms"] / steps, rel=1e-3, abs=1e-3)
            assert not [e for e in dec["events"] if e["name"] == "token"]
            n_decode += 1
        # pack spans carry bucket/padding attribution
        pack = [s for s in ss if s["name"] == "pack"][0]
        assert pack["attrs"]["bucket"] >= pack["attrs"]["batch_rows"]
        assert pack["attrs"]["padding_rows"] == \
            pack["attrs"]["bucket"] - pack["attrs"]["batch_rows"]
    assert n_dense == 8 and n_decode == 8


def test_serving_untraced_by_default(flags_guard, tmp_path):
    """FLAGS_trace=off: requests flow with no spans recorded — the
    off-path contract for the serving chain."""
    _, prefix = _export_mlp(tmp_path, "off")
    tracing.clear()
    srv = serving.Server(serving.ServingConfig(workers=1))
    srv.register("off", prefix, buckets=(1, 2, 4))
    srv.start()
    try:
        out = srv.run("off", [np.ones((2, 6), "float32")])
        assert out[0].shape[0] == 2
    finally:
        srv.stop()
    assert tracing.finished_spans() == []


def test_queue_wait_histogram_observes_requests(flags_guard, tmp_path):
    reg = default_registry()
    h = reg.get("serving_queue_wait_seconds")
    occ = reg.get("serving_batch_occupancy_rows")
    pad = reg.get("serving_padding_efficiency_ratio")
    c0, o0, p0 = h.count, occ.count, pad.count
    _, prefix = _export_mlp(tmp_path, "qw")
    srv = serving.Server(serving.ServingConfig(workers=1))
    srv.register("qw", prefix, buckets=(1, 2, 4))
    srv.start()
    try:
        for _ in range(3):
            srv.run("qw", [np.ones((1, 6), "float32")])
    finally:
        srv.stop()
    assert h.count - c0 == 3                 # one sample per request
    assert occ.count - o0 >= 1               # one per batch
    assert pad.count - p0 >= 1
    assert 0.0 < pad.quantile(0.5) <= 1.0


def test_generate_traced_at_scan_boundary(flags_guard):
    """Standalone generate() under FLAGS_trace=full: one root span with
    prefill + decode children, per-token events attributed across the
    scanned token loop, and the two compiles attached to the trace."""
    from paddle_tpu.text.generation import Generator
    from paddle_tpu.text.models.gpt import GPTConfig, GPTModel
    set_flags({"FLAGS_trace": "full"})
    tracing.clear()
    paddle.seed(5)
    m = GPTModel(GPTConfig.tiny(vocab_size=32, hidden_size=16, layers=1,
                                heads=2, seq=32))
    m.eval()
    gen = Generator(m, seq_buckets=(8,), max_len=16)
    out = gen.generate(np.ones((1, 4), np.int32), max_new_tokens=3)
    assert out.numpy().shape == (1, 3)
    spans = tracing.finished_spans()
    root = [s for s in spans if s["name"] == "generate"][0]
    names = {s["name"] for s in spans
             if s["trace_id"] == root["trace_id"]}
    assert {"generate", "prefill", "decode"} <= names
    dec = [s for s in spans if s["name"] == "decode"][0]
    assert dec["attrs"]["steps"] == 3
    assert dec["attrs"]["per_token_ms"] == pytest.approx(
        dec["dur_ms"] / 3, rel=1e-3, abs=1e-3)
    assert not [e for e in dec["events"] if e["name"] == "token"]
    # the prefill+decode compiles were pinned to the root span
    compiles = [e for e in root["events"] if e["name"] == "compile"]
    assert {c["kind"] for c in compiles} == {"generate_prefill",
                                             "generate_decode"}
    # a second call is all cache hits: no compile events on its trace
    tracing.clear()
    gen.generate(np.ones((1, 4), np.int32), max_new_tokens=3)
    root2 = [s for s in tracing.finished_spans()
             if s["name"] == "generate"][0]
    assert not [e for e in root2["events"] if e["name"] == "compile"]


# -- training: per-phase step breakdown --------------------------------------

def test_train_step_phase_breakdown(flags_guard):
    from paddle_tpu.parallel import TrainStep
    set_flags({"FLAGS_trace": "full"})
    reg = default_registry()
    hist = reg.get("train_step_phase_seconds")
    prep0 = hist.labels(phase="host_prep").count
    disp0 = hist.labels(phase="dispatch").count
    fence0 = hist.labels(phase="device_fence").count
    net = nn.Linear(4, 2)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=net.parameters())
    ts = TrainStep(net, opt, loss_fn=nn.CrossEntropyLoss())
    bx = np.random.RandomState(0).randn(8, 4).astype("float32")
    by = np.random.RandomState(1).randint(0, 2, (8,)).astype("int64")
    for _ in range(3):
        ts(bx, by)
    # first step is the fresh compile (host_prep only); the two steady
    # steps record all three segments
    assert hist.labels(phase="host_prep").count - prep0 == 3
    assert hist.labels(phase="dispatch").count - disp0 == 2
    assert hist.labels(phase="device_fence").count - fence0 == 2
    site = [e for e in ledger.compile_events()
            if e["kind"] == "train_step"
            and "Linear" in e["site"]]
    # tracing never adds a compile key: exactly one fresh signature
    assert len({e["key"] for e in site[-1:]}) == 1
    set_flags({"FLAGS_trace": "off"})
    ts(bx, by)
    assert hist.labels(phase="host_prep").count - prep0 == 3   # unchanged


# -- obs_report ---------------------------------------------------------------

def _synth_trace(trace_dir, complete=True, kind="dense"):
    import time
    tracing.set_trace_dir(trace_dir)
    t = time.monotonic()
    r = tracing.start_span("request", t0=t - 0.012, kind=kind,
                           model="m", rows=1)
    tracing.child(r, "queue_wait", t - 0.010, t - 0.008)
    tracing.child(r, "pack", t - 0.008, t - 0.007, bucket=2,
                  batch_rows=1, padding_rows=1)
    if complete:
        if kind == "dense":
            tracing.child(r, "h2d", t - 0.007, t - 0.006)
            tracing.child(r, "execute", t - 0.006, t - 0.002)
            tracing.child(r, "d2h", t - 0.002, t - 0.001)
        else:
            tracing.child(r, "prefill", t - 0.007, t - 0.005)
            tracing.child(r, "decode", t - 0.005, t - 0.001)
        tracing.child(r, "reply", t - 0.001, t)
    tracing.finish(r)
    return r.trace_id


def test_obs_report_joins_traces_and_metrics(flags_guard, tmp_path):
    set_flags({"FLAGS_trace": "full"})
    d = str(tmp_path / "tr")
    good = _synth_trace(d, complete=True)
    good_dec = _synth_trace(d, complete=True, kind="decode")
    bad = _synth_trace(d, complete=False)
    obs = _load_tool("obs_report")
    traces = obs.load_traces(d)
    assert set(traces) == {good, good_dec, bad}
    ok, _ = obs.check_chain(traces[good])
    assert ok
    ok, problems = obs.check_chain(traces[bad])
    assert not ok and "missing" in problems[0]
    mpath = str(tmp_path / "m.prom")
    from paddle_tpu.profiler.metrics import write_textfile
    write_textfile(mpath)
    report, rc = obs.build_report(traces, metrics_path=mpath)
    assert rc == 1                              # the incomplete chain
    assert report["complete"] == 2
    assert report["kinds"] == {"dense": 1, "decode": 1}
    assert report["incomplete"]
    assert report["total_ms"]["p99"] > 0
    assert "queue_wait" in report["phases_ms"]
    # drop the bad chain -> clean report, rc 0
    del traces[bad]
    report, rc = obs.build_report(traces, slo_p99_ms=1e9)
    assert rc == 0 and report["slo_met"] is True
    w = obs.waterfall(traces[good])
    assert "queue_wait" in w and "execute" in w
    # CLI end-to-end on the same dir (still has the bad chain on disk)
    rc = obs.main(["--trace-dir", d, "--json"])
    assert rc == 1


def test_obs_report_waterfall_marks_compiles(flags_guard, tmp_path):
    import time
    set_flags({"FLAGS_trace": "full"})
    d = str(tmp_path / "tr")
    tracing.set_trace_dir(d)
    t = time.monotonic()
    r = tracing.start_span("request", t0=t - 0.012, kind="decode",
                           model="g", rows=1)
    tracing.child(r, "queue_wait", t - 0.010, t - 0.009)
    tracing.child(r, "pack", t - 0.009, t - 0.008, bucket=1,
                  batch_rows=1, padding_rows=0)
    tracing.child(r, "prefill", t - 0.008, t - 0.006)
    dec = tracing.start_span("decode", parent=r, t0=t - 0.006)
    dec.event("compile", site="serving:g", kind="serving_recompile",
              ms=12.0)
    tracing.finish(dec, end=t - 0.001)
    tracing.child(r, "reply", t - 0.001, t)
    tracing.finish(r)
    obs = _load_tool("obs_report")
    traces = obs.load_traces(d)
    w = obs.waterfall(traces[r.trace_id])
    assert "[1 COMPILE]" in w
