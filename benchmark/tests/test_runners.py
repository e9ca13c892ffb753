"""The runners end to end at tiny sizes on the CPU — ``run_cell`` with the
look for a chip skipped, through a function argument — sound, and with the
timed path broken underneath: ``correct`` must then come out false."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as bench_run

TRAIN, SATURATED, OPEN = ("bert-large-pretrain-s512", "gpt2-xl-batch-saturated",
                          "gpt2-xl-chat-open")


def _run(workload, cfg, traffic=None, seconds=1.0, trace=False, seed=2 ** 31 + 11):
    import time
    return bench_run.run_cell(workload, seed, seconds, trace, config=cfg,
                              traffic=traffic, check_device=False,
                              t_start=time.monotonic())


def _checks(out):
    return {c["name"]: c for c in out["checks"]}


def test_train_cell_sound(bert_tiny):
    out = _run(TRAIN, bert_tiny)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 3
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert out["metrics"]["train_tokens_per_s"]["value"] > 0
    c = _checks(out)
    assert c["grad_diff_rel"]["value"] < 1e-4          # float32 on the CPU
    assert c["delta_norm_rel_worst_leaf"]["value"] < 1e-3
    assert set(out["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}


def test_train_cell_traced_reports_layer_metrics(bert_tiny):
    out = _run(TRAIN, bert_tiny, trace=True)
    assert out["correct"] is True
    # no device plane on the CPU: the trace's readers find nothing and are
    # left out; the host's and the counters' readers report
    assert set(out["metrics"]) == {"train_step_call_ms", "steady_compiles.train"}
    assert out["metrics"]["steady_compiles.train"]["value"] == 0


def test_train_step_that_keeps_its_state_is_not_correct(bert_tiny, monkeypatch):
    from paddle_tpu.parallel import TrainStep
    real = TrainStep.__call__

    def frozen(self, inputs, label=None):
        keep = jax.tree_util.tree_map(jnp.copy, self.state)
        loss = real(self, inputs, label)
        self._state = keep                     # the step returns its state unchanged
        return loss

    monkeypatch.setattr(TrainStep, "__call__", frozen)
    out = _run(TRAIN, bert_tiny)
    c = _checks(out)
    assert out["correct"] is False
    assert not c["delta_norm_rel_worst_leaf"]["ok"]
    assert not c["grad_norm_rel_worst_leaf"]["ok"]


def test_train_step_that_drops_half_the_batch_is_not_correct(bert_tiny, monkeypatch):
    from paddle_tpu.parallel import TrainStep
    real = TrainStep.__call__

    def half(self, inputs, label=None):
        cut = tuple(None if x is None else np.concatenate(
            [x[:len(x) // 2]] * 2) for x in inputs)    # second half never seen
        return real(self, cut, label)

    monkeypatch.setattr(TrainStep, "__call__", half)
    out = _run(TRAIN, bert_tiny)
    c = _checks(out)
    assert out["correct"] is False
    assert not all(c[f"loss_rel_step{k}"]["ok"] for k in (1, 2, 3))
    assert not c["grad_diff_rel"]["ok"]


def test_saturated_cell_sound(gpt_tiny, closed_tiny):
    out = _run(SATURATED, gpt_tiny, closed_tiny, seconds=2.0)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"batch_job_s", "setup_s"}
    assert 0 < out["metrics"]["batch_job_s"]["value"] < 2.0    # the job finished
    c = _checks(out)
    assert c["served_gap_rel_widest"]["value"] == 0.0   # float32: greedy tokens
    assert c["served_tokens_compared"]["value"] >= 10


def test_job_that_the_window_does_not_see_finished_reads_the_window(
        gpt_tiny, closed_tiny, monkeypatch, capsys):
    """The job is a fixed number of answers: a server that stops answering
    before it is done cannot shorten it."""
    import re
    import time
    from concurrent.futures import Future
    from paddle_tpu.serving import Server
    real = Server.submit_decode
    t_hang = []

    def hangs_later(self, *a, **kw):
        t_hang.append(time.monotonic())
        if t_hang[-1] - t_hang[0] > closed_tiny["ramp_s"] + 1.5:
            return Future()                    # never resolves
        return real(self, *a, **kw)

    monkeypatch.setattr(Server, "submit_decode", hangs_later)
    out = _run(SATURATED, gpt_tiny, dict(closed_tiny, job_requests=10 ** 6),
               seconds=4.0)
    said = re.search(r"job: 1000000 answers, (\d+) of them inside the window, "
                     r"([\d.]+) s", capsys.readouterr().out)
    assert 0 < int(said.group(1)) < 10 ** 6
    assert out["metrics"]["batch_job_s"]["value"] == pytest.approx(4.0, abs=1e-6)


def test_open_cell_sound_and_traced(gpt_tiny, open_tiny):
    out = _run(OPEN, gpt_tiny, open_tiny, seconds=2.0)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"request_p50_ms", "request_p90_ms", "setup_s"}
    assert 0 < out["metrics"]["request_p50_ms"]["value"] \
        <= out["metrics"]["request_p90_ms"]["value"]
    out = _run(OPEN, gpt_tiny, open_tiny, seconds=2.0, trace=True)
    assert out["correct"] is True
    assert {"loadgen_late_p95_ms", "slot_occupancy_pct.open", "slot_ttft_p90_ms",
            "steady_compiles.open"} <= set(out["metrics"])
    assert 0 < out["metrics"]["slot_occupancy_pct.open"]["value"] <= 100


def test_served_token_altered_where_it_is_produced_is_not_correct(
        gpt_tiny, closed_tiny, monkeypatch):
    from paddle_tpu.serving.slots import SlotLoop
    real = SlotLoop._emit

    def altered(self, slot, toks):
        return real(self, slot, [(t + 1) % gpt_tiny["vocab_size"] for t in toks])

    monkeypatch.setattr(SlotLoop, "_emit", altered)
    out = _run(SATURATED, gpt_tiny, closed_tiny, seconds=2.0)
    assert out["correct"] is False
    assert not _checks(out)["served_gap_rel_widest"]["ok"]


def test_off_the_chip_the_command_fails(monkeypatch, capsys):
    with pytest.raises(SystemExit) as e:
        bench_run.require_chips(1)
    assert e.value.code != 0
