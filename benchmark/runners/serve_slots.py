"""Runner ``serve_slots``: a decoder served through ``serving.Server`` ->
``register_decode`` -> ``submit_decode`` with the continuous-batching slot
loop (``FLAGS_decode_slots``).

Set-up builds the model through the program's constructors, installs the
benchmark's weights (made on the device from the seed, in the served
dtype), starts the server (which compiles or loads the step and the chunk
program) and lets the traffic's own ramp warm every dispatch path.  Once
the window has closed, the plain float32 reference runs once over each
sampled prompt with its served tokens, one layer at a time beside the idle
server
(``benchmark/reference/<family>.py``); compared is the widest gap by which
a served token's logit lies below the reference's best.
"""
from __future__ import annotations

import gc
import importlib
import json
import time

import numpy as np

import jax

from benchmark import harness

MODEL = "model"


def _valid(rec, vocab) -> bool:
    """A resolved request carries exactly its token budget, every id inside
    the vocabulary."""
    if rec.error is not None or rec.tokens is None:
        return False
    ids = np.asarray(rec.tokens[0])
    return (ids.shape == (1, rec.max_new) and int(ids.min()) >= 0
            and int(ids.max()) < vocab)


def _stop(srv, timeout: float = 240.0):
    """Stop the server and wait until its slot loop has really ended:
    ``Server.stop`` joins its threads with timeouts of its own, and rows
    still decoding keep the planes alive."""
    import threading
    srv.stop(drain=False)
    deadline = time.monotonic() + timeout
    for t in threading.enumerate():
        if t.name.startswith("slot-loop-"):
            t.join(max(0.0, deadline - time.monotonic()))


def _sample(finished, k: int, seed: int):
    """``k`` finished requests drawn from the seed, the longest among them."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: r.prompt.size + r.max_new)
    rest = [r for r in finished if r is not longest]
    rng = np.random.default_rng(int(seed) + 2)
    picks = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in picks]


def boot(cfg: dict, seed: int, phases=None):
    """The server, started and warm, carrying the benchmark's weights:
    (server, its slot loop, submit(prompt, max_new) -> Future, the
    benchmark's canonical view of those weights)."""
    from paddle_tpu import serving
    from paddle_tpu.framework.flags import set_flags
    family, sv = cfg["family"], cfg["serve"]
    ref = importlib.import_module(f"benchmark.reference.{family}")
    models = importlib.import_module(f"benchmark.models.{family}")
    set_flags({"FLAGS_decode_slots": int(sv["slots"]),
               "FLAGS_prefill_chunk": int(sv["prefill_chunk"]),
               "FLAGS_decode_max_len": int(sv["max_len"])})
    mark = phases.mark if phases else (lambda name: None)
    mark("imports")
    weights = ref.init_weights(cfg, seed)
    mapped = models.to_program(weights)
    del weights
    jax.block_until_ready(mapped)
    mark("weights")
    model = models.build(cfg, mapped)
    # the benchmark's own handle on its weights, for the reference: the
    # program's parameters hold the same buffers, nothing is copied
    view = harness.canonical_view(mapped, models.leaf_ids(cfg))
    mark("build_model")
    srv = serving.Server(serving.ServingConfig(
        workers=int(sv["workers"]), queue_capacity=int(sv["queue_capacity"])))
    srv.register_decode(MODEL, model, batch_buckets=tuple(sv["batch_buckets"]),
                        seq_buckets=tuple(sv["seq_buckets"]),
                        max_new_tokens=int(sv["max_new_tokens"]),
                        max_len=int(sv["max_len"]))
    srv.start()
    mark("server_start(compile_or_load)")

    def submit(prompt, max_new):
        return srv.submit_decode(MODEL, [prompt], max_new_tokens=max_new)

    return srv, srv._models[MODEL]._loop, submit, view


def run(cell: dict, seed: int, seconds: float, trace: bool,
        t_process_start: float) -> dict:
    cfg, traffic = cell["config"], cell["traffic"]
    family = cfg["family"]
    ref = importlib.import_module(f"benchmark.reference.{family}")
    gen = importlib.import_module(f"benchmark.generators.{traffic['kind']}")
    checks = harness.Checks()

    phases = harness.Phases(t_process_start)
    srv, loop, submit, weights = boot(cfg, seed, phases)

    mark = {}

    def on_open():
        loop.reset_stats()
        mark["compiles"] = len(srv.compile_events_since_warmup())
        mark["prof"] = harness.ProfilerSlice(
            trace, cell["bench_dir"], time.monotonic(), seconds,
            float(traffic.get("trace_slice_s", 5.0)))

    def on_close():
        mark["memory"] = harness.device_bytes_now()
        mark["prof"].stop()
        mark["stats"] = loop.stats()
        mark["ttft_s"] = list(loop._ttft)
        mark["compiles"] = len(srv.compile_events_since_warmup()) \
            - mark["compiles"]

    records, t_open, t_close = gen.drive(
        traffic, seed, seconds, submit, vocab_size=cfg["vocab_size"],
        slots=int(cfg["serve"]["slots"]), on_open=on_open,
        on_close=on_close, span=harness.span)
    setup_s = t_open - t_process_start
    phases.seconds["ramp"] = float(traffic["ramp_s"])
    phases.report()

    # -- what had resolved by now (the server keeps running underneath) -------
    vocab = cfg["vocab_size"]
    window_s = t_close - t_open
    drain_s = float(traffic.get("drain_s", 0.0))
    seen = {r.index: (r.done, _valid(r, vocab)) for r in records}
    good = [r for r in records if seen[r.index][1]
            and t_open <= seen[r.index][0] < t_close]
    due_in = [r for r in records if t_open <= r.due < t_close]
    failed = sum(1 for done, ok in seen.values() if done is not None and not ok)
    if drain_s:     # an open loop: a due request that never resolved is missing
        failed += sum(1 for r in due_in if seen[r.index][0] is None)
    sample = _sample(good, int(cfg["check"]["sample_requests"]), seed)

    # -- the reference, beside the server (one layer at a time) --------------
    t_ref = time.monotonic()
    widest, compared = 0.0, 0
    with jax.default_matmul_precision("highest"):
        for r in sample:
            gaps = np.asarray(ref.served_gaps(cfg, weights, r.prompt,
                                              np.asarray(r.tokens[0])[0]))
            widest = max(widest, float(gaps.max()))
            compared += gaps.size
    reference_s = time.monotonic() - t_ref
    print(f"reference: {len(sample)} requests, {compared} served tokens, "
          f"{reference_s:.1f} s", flush=True)
    checks.add("served_tokens_compared", compared, 1, kind="min")
    checks.add("served_gap_rel_widest", widest, cfg["limits"]["served_gap_rel"])
    checks.add("window_failed_requests", failed, 0)
    # the server is left as it is: rows of a closed loop are still decoding,
    # and waiting for them would add tens of seconds to every run; run.py
    # ends the process once the result line is out

    # -- what the client saw -------------------------------------------------
    drain_end = t_close + drain_s
    latency_ms = [1e3 * ((seen[r.index][0] if seen[r.index][1] else drain_end)
                         - r.due) for r in due_in]
    served_latency_ms = [1e3 * (seen[r.index][0] - r.sent) for r in good]
    late_ms = [1e3 * (r.sent - r.due) for r in due_in if r.sent is not None]
    # a closed loop times a fixed job: the seconds from the window's opening
    # to its ``job_requests``-th answer (the traffic file's number).  A job
    # that the window does not see finished reads the window's seconds.
    tokens_out = sum(r.max_new for r in good)
    answered = sorted(seen[r.index][0] - t_open for r in good)
    print(f"window: {len(good)} requests resolved, {tokens_out} tokens, "
          f"{window_s:.3f} s, the last at "
          f"{answered[-1] if answered else float('nan'):.3f} s", flush=True)
    e2e = {"setup_s": setup_s}
    job = int(traffic.get("job_requests", 0))
    if job:
        e2e["batch_job_s"] = (answered[job - 1] if len(answered) >= job
                              else window_s)
        print(f"job: {job} answers, {min(job, len(answered))} of them inside "
              f"the window, {e2e['batch_job_s']:.3f} s; every answer at (s) "
              + " ".join(f"{t:.2f}" for t in answered), flush=True)
    if latency_ms:
        e2e["request_p50_ms"] = harness.percentile(latency_ms, 50)
        e2e["request_p90_ms"] = harness.percentile(latency_ms, 90)
    valid_columns = sum(r.max_new * r.prompt.size
                        + r.max_new * (r.max_new - 1) // 2 for r in good)
    return {
        "correct": checks.correct, "attempted": len(records), "failed": failed,
        "checks": checks.rows, "reference_s": reference_s,
        "setup_phases_s": phases.seconds,
        "memory_at_close_bytes": mark["memory"],
        "end_to_end": e2e,
        "trace_dir": mark["prof"].dir,
        "span_names": harness.SPAN_NAMES,
        "ctx": {
            "config": cfg, "traffic": traffic, "family": family,
            "window": {"seconds": window_s, "requests_done": len(good),
                       "requests_due": len(due_in), "tokens": tokens_out,
                       "valid_kv_columns": valid_columns},
            "counters": {"slot_loop": mark["stats"],
                         "slot_ttft_s": mark["ttft_s"],
                         "steady_compiles": mark["compiles"],
                         "loadgen_late_ms": late_ms,
                         "request_latency_ms": latency_ms,
                         "served_latency_ms": served_latency_ms},
            "programs": {"step": "jit_step", "chunk": "jit_chunk"},
        },
    }


def control(cell: dict, seeds, seconds: float = 10.0) -> list:
    """The program's and the control's readings, one row per seed, in ONE
    process: for each seed a server with that seed's weights, a short
    window of the cell's own traffic at the cell's own load, and on the
    same sampled prompts and served tokens the reference gives (a) the
    widest gap of the served tokens and (b) the widest gap of the tokens
    that the nearest lower precision puts first."""
    from benchmark.reference.common import CONTROL_PRECISION
    cfg, traffic = cell["config"], cell["traffic"]
    family = cfg["family"]
    ref = importlib.import_module(f"benchmark.reference.{family}")
    gen = importlib.import_module(f"benchmark.generators.{traffic['kind']}")
    low = CONTROL_PRECISION[cfg["dtype"]]
    rows = []
    for seed in seeds:
        srv, loop, submit, weights = boot(cfg, seed)
        records, t_open, t_close = gen.drive(
            traffic, seed, seconds, submit, vocab_size=cfg["vocab_size"],
            slots=int(cfg["serve"]["slots"]), span=harness.span)
        t_wait = time.monotonic() + 60
        while time.monotonic() < t_wait and any(r.done is None for r in records):
            time.sleep(0.05)
        good = [r for r in records if r.done is not None
                and _valid(r, cfg["vocab_size"]) and t_open <= r.done < t_close]
        sample = _sample(good, int(cfg["check"]["sample_requests"]), seed)
        row = {"seed": seed, "precision": low, "requests": len(sample),
               "tokens": 0, "program_gap": 0.0, "control_gap": 0.0}
        with jax.default_matmul_precision("highest"):
            for r in sample:
                served = np.asarray(r.tokens[0])[0]
                row["program_gap"] = max(row["program_gap"], float(np.max(
                    ref.served_gaps(cfg, weights, r.prompt, served))))
                row["control_gap"] = max(row["control_gap"], float(np.max(
                    ref.control_gaps(cfg, weights, r.prompt, served, low))))
                row["tokens"] += served.size
        rows.append(row)
        print("CONTROL " + json.dumps(row), flush=True)
        _stop(srv)
        del srv, loop, submit, weights
        gc.collect()
    return rows
