"""``tools/slot_replay.py``: the slot loop's admission rules replayed on the
host for the state-space cell's own traffic.  The numbers are those PERF.md
section 6 (PR 40) gives as predicted, beside what the chip then read (62
answers and 45.5% of the slot-steps prefilling inside the cold round; the
round's end 40.0 s; 117-124 answers in a steady window)."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tools import slot_replay                                  # noqa: E402


def _cell():
    return (slot_replay._load("configs", "nemotron-3-nano-ep8-serve"),
            slot_replay._load("traffic", "reason1k-closed-2S"))


@pytest.mark.parametrize("ramp,first_seen,start,window,by_34,prefilling", [
    (8.0, 1, 1024, 62, 31, 44.8),     # the window inside the cold round
    (8.0, 2, 1024, 62, 31, 44.8),     # the race for the first admission
    (52.0, 1, 1024, 123, 92, 0.6),    # the cell as committed: steady
    (8.0, 27, 512, 31, 21, 71.4),     # ... were a one-chunk prompt seen first
], ids=["cold-round", "two-seen", "steady", "one-chunk-first"])
def test_the_replay_of_the_state_space_cell(ramp, first_seen, start, window,
                                            by_34, prefilling):
    config, traffic = _cell()
    out = slot_replay.replay(config, traffic, step_s=0.029, chunk_s=0.0435,
                             ramp_s=ramp, first_seen=first_seen)
    assert out["start"] == start
    assert len(out["answers"]) == window
    assert sum(1 for a in out["answers"] if a <= 34.0) == by_34
    assert out["prefilling_pct"] == pytest.approx(prefilling, abs=0.1)
    assert out["answers"] == sorted(out["answers"])
    if start == 1024:
        # 1,024 steps from column 1,024 to 2,048 and the first round's chunks
        assert out["round_end_s"] == pytest.approx(39.7, abs=0.1)


@pytest.mark.parametrize("share,ramp,window,lag", [
    (0.0, 52.0, 123, 0.0),       # the benchmark's traffic: every row by count
    (1.0, 52.0, 123, 0.235),     # every answer by the end token: one slot-step
    (0.5, 52.0, 123, 0.117),     #   each, 1 in ~425 of a 384-token answer's
    (1.0, 8.0, 61, 0.113),       # in the cold round a late slot costs an answer
], ids=["by-count", "by-end-token", "half", "by-end-token-cold"])
def test_the_replay_follows_the_retirement_timing(share, ramp, window, lag):
    """One step in flight: a slot freed by count is admitted into in the same
    iteration as when the loop read before it dispatched (the numbers above
    stand as they were), one freed by the end token an iteration later, after
    a step that passed its row by; the round's end is the frontier's and does
    not move."""
    config, traffic = _cell()
    out = slot_replay.replay(config, traffic, step_s=0.029, chunk_s=0.0435,
                             ramp_s=ramp, end_token_share=share)
    assert len(out["answers"]) == window
    assert out["retire_lag_pct"] == pytest.approx(lag, abs=0.001)
    assert out["round_end_s"] == pytest.approx(39.7, abs=0.1)
    assert out["emitting_pct"] + out["prefilling_pct"] \
        + out["retire_lag_pct"] == pytest.approx(100.0, abs=1e-6)


def test_the_committed_ramp_steps_over_the_replayed_round():
    """``ramp_s`` is the round's end plus a median answer's seconds: at
    least ten seconds past what the replay gives at the measured step."""
    config, traffic = _cell()
    out = slot_replay.replay(config, traffic, step_s=0.029, chunk_s=0.0435)
    assert traffic["ramp_s"] >= out["round_end_s"] + 10
    assert out["prefilling_pct"] < 2
    # the job ends inside the window with a tenth to spare
    assert out["answers"][int(traffic["job_requests"]) - 1] * 1.1 < 45.0


def test_the_tool_prints_one_line(capsys):
    slot_replay.main(["nemotron-3-nano-ep8-serve", "reason1k-closed-2S",
                      "--step-ms", "29", "--chunk-ms", "43.5", "--ramp", "8"])
    line = json.loads(capsys.readouterr().out)
    assert line["answers_in_window"] == 62 and line["answers_by"] == 31


@pytest.mark.parametrize("ramp,first_seen,window,by_34,prefilling", [
    (0.0, 1, 27, 14, 11.7),     # a window opened cold: the round inside it
    (25.0, 1, 43, 34, 7.7),     # the cell as sized: steady
    (25.0, 2, 43, 34, 7.7),     # no race for the first admission
], ids=["cold-round", "steady", "two-seen"])
def test_the_replay_of_the_long_document_cell(ramp, first_seen, window,
                                              by_34, prefilling):
    """The linear / block-sparse cell's traffic (PR 44) at the traced 19.5
    ms step and 32.3 ms chunk: every prompt pads to 24 chunks of 512, so
    the frontier starts at column 12,288 whichever requests the first
    admission sees, and the cold round is 24 slots x 24 chunks + the steps
    between them."""
    config = slot_replay._load("configs", "minicpm-sala-pp2-serve")
    traffic = slot_replay._load("traffic", "doc12k-closed-2S")
    out = slot_replay.replay(config, traffic, step_s=0.0195, chunk_s=0.0323,
                             ramp_s=ramp, first_seen=first_seen)
    assert out["start"] == 12288
    assert len(out["answers"]) == window
    assert sum(1 for a in out["answers"] if a <= 34.0) == by_34
    assert out["prefilling_pct"] == pytest.approx(prefilling, abs=0.1)
    # 24 x 24 chunks of 32.3 ms and the steps that let them in; on the chip
    # the steady window read 7.53% prefilling, 41-43 answers, the 33rd at
    # 32.9-35.1 s (PERF.md section 6, PR 44)
    assert out["round_end_s"] == pytest.approx(19.1, abs=0.1)


@pytest.mark.parametrize("ramp,window,by_34", [
    (8.0, 244, 187),        # the window opens a second after the cold round
    (20.0, 273, 201),       # the round + one median answer: steady
], ids=["after-the-round", "steady"])
def test_the_replay_of_the_delta_rule_cell_at_128_slots(ramp, window, by_34):
    """The delta-rule / latent cell's traffic (PR 48) at 128 slots and the
    traced 25.33 ms step and 26.54 ms chunk: every prompt pads to 2 chunks
    of 512, so the frontier starts at column 1,024 whichever requests the
    first admission sees, and the cold round is 128 rows x 2 chunks + the
    steps between them, 6.9 s.  On the chip six seeds at ``ramp_s`` 20 then
    read 263-276 answers a window and 199-205 by 34 s (191 in the one run
    that a 2 s host stall held up): PERF.md section 6, PR 48."""
    config = slot_replay._load("configs", "gigachat3.5-ep16-serve")
    config = dict(config, serve=dict(config["serve"], slots=128))
    traffic = slot_replay._load("traffic", "reason1k-2chunk-closed-2S")
    for first_seen in (1, 2):       # one chunk count: no first-admission race
        out = slot_replay.replay(config, traffic, step_s=0.025332,
                                 chunk_s=0.026541, ramp_s=ramp,
                                 first_seen=first_seen)
        assert out["start"] == 1024
        assert len(out["answers"]) == window
        assert sum(1 for a in out["answers"] if a <= 34.0) == by_34
        assert out["round_end_s"] == pytest.approx(6.87, abs=0.02)
        assert out["prefilling_pct"] < 0.5


def test_the_replay_of_the_delta_rule_cell_as_committed():
    """The cell as committed (160 slots, ``ramp_s`` 23, 225 answers a job)
    at the traced 28.50 ms step and 25.81 ms chunk: the cold round ends at
    8.3 s, and the ramp is that + one median answer's 512 steps.  On the
    chip six seeds read 289-297 answers a window, 219-227 by 34 s and the
    225th at 33.79-34.66 s (PERF.md section 6, PR 48): the replay's
    constant step is 1-3% fast."""
    config = slot_replay._load("configs", "gigachat3.5-ep16-serve")
    traffic = slot_replay._load("traffic", "reason1k-2chunk-closed-2S")
    assert (config["serve"]["slots"], traffic["ramp_s"],
            traffic["job_requests"]) == (160, 23.0, 225)
    out = slot_replay.replay(config, traffic, step_s=0.0284953,
                             chunk_s=0.0258096)
    assert out["start"] == 1024
    assert out["round_end_s"] == pytest.approx(8.34, abs=0.02)
    assert traffic["ramp_s"] >= out["round_end_s"] + 512 * 0.0284953
    assert len(out["answers"]) == 298
    assert sum(1 for a in out["answers"] if a <= 34.0) == 228
    # the job ends inside the window with a tenth to spare
    assert out["answers"][traffic["job_requests"] - 1] * 1.1 < 45.0
