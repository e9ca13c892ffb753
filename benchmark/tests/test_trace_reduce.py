"""The reduction from a capture to busy/idle, per-program device time, top
operations and labelled gaps, on a recorded trace: the first two steps of
the BERT-large cell's first traced run on the chip (PR 23), operation names
cut to 160 characters, host lines cut to the harness's own spans."""
import os

import pytest

from benchmark import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "train_two_steps.xplane.pb.gz")


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_profile(
        trace_reduce.load(DATA), ("make_batch", "train_step_call", "fetch_loss"))


def test_union_merges_overlaps():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [[0, 3], [5, 8]]


def test_busy_and_window(reduced):
    assert reduced["chips"] == 1
    assert reduced["window_s"] == pytest.approx(0.388751, rel=1e-4)
    assert reduced["busy_s"] == pytest.approx(0.388517, rel=1e-4)
    assert 0 < reduced["busy_s"] <= reduced["window_s"]


def test_program_device_time(reduced):
    step = reduced["programs"]["jit_step"]          # fingerprint stripped
    assert step["count"] == 2
    assert step["median_s"] == pytest.approx(0.184408, rel=1e-4)
    assert step["total_s"] == pytest.approx(0.368817, rel=1e-4)


def test_top_ops_and_gaps(reduced):
    ops = reduced["top_ops"]
    assert len(ops) <= 10 and ops[0][0] == "all fusion kOutput"
    assert ops[0][1] == pytest.approx(0.21996, rel=1e-3)
    singles = [n for n, _ in ops if n.startswith("%")]
    assert singles and all(len(n) <= 120 for n in singles)
    assert "%fusion.1789 f32[30522,1024] kLoop" in singles
    gaps = dict(reduced["idle_gaps"])
    assert len(gaps) <= 10
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)
    assert set(gaps) <= {"no span", "make_batch", "train_step_call",
                         "fetch_loss"}


def test_names():
    hlo = ("%fusion.1044 = (bf16[16,512,4096]{2,1,0:T(8,128)(2,1)}, bf16[16]) "
           "fusion(bf16[4096]{0} %x), kind=kOutput, calls=%fused.1")
    assert trace_reduce.short_name(hlo) == "%fusion.1044 bf16[16,512,4096] kOutput"
    assert trace_reduce.category(hlo) == "all fusion kOutput"
    assert trace_reduce.category("%copy-done.12 = f32[8]{0} copy-done(%c)") == \
        "all copy-done"


def test_no_capture_reads_nothing(tmp_path):
    assert trace_reduce.reduce(str(tmp_path)) is None
    assert trace_reduce.reduce(None) is None
