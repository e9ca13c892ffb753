"""Kimi-K2.5's cell at a tiny size on the CPU: the runner serves the model
end to end with the prefix cache on and holds one hit and one miss against
the reference; the new readers on canned counters and a canned trace; and
the parent's case, in which they find nothing and say so."""
import importlib
import json
import os
import time

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.runners import serve_slots_prefix

CELL = "kimi-ep32-docqa8k-saturated"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _load(rel):
    with open(os.path.join(ROOT, "benchmark", rel)) as f:
        return json.load(f)


@pytest.fixture
def kimi_tiny():
    cfg = _load("configs/kimi-k2.5-ep32-serve.json")
    over = _load("tests/data/kimi_tiny.json")["over"]
    cfg["serve"].update(over.pop("serve"))
    cfg.update(over)
    return cfg


@pytest.fixture
def docqa_tiny():
    tr = _load("traffic/docqa8k-closed-1S.json")
    tr.update(ramp_s=1.5, pool_docs=64, trace_slice_s=0.5, job_requests=6,
              doc_len={"dist": "lognormal", "median": 22, "sigma": 0.1,
                       "min": 18, "max": 26},
              question_len={"dist": "lognormal", "median": 4, "sigma": 0.5,
                            "min": 2, "max": 8},
              max_new_tokens={"dist": "lognormal", "median": 4, "sigma": 0.5,
                              "min": 2, "max": 6})
    return tr


def test_runner_serves_the_model_end_to_end(kimi_tiny, docqa_tiny):
    from paddle_tpu.framework.flags import flag
    out = bench_run.run_cell(CELL, 2 ** 31 + 5, 3.0, True, config=kimi_tiny,
                             traffic=docqa_tiny, check_device=False,
                             t_start=time.monotonic())
    assert out["correct"] is True and out["failed"] == 0
    c = {r["name"]: r for r in out["checks"]}
    # float32 on the CPU: the served tokens are the reference's greedy
    # tokens up to near-ties of the absorbed against the per-head order
    assert c["served_gap_rel_widest"]["value"] < 1e-4
    assert c["served_tokens_compared"]["value"] >= 4      # a hit and a miss
    m = out["metrics"]
    # no device plane on the CPU: the counters' readers report
    assert {"steady_compiles.docqa", "slot_occupancy_pct.docqa",
            "moe_held_assignment_pct.docqa", "chunks_per_step.docqa",
            "moe_tokens_per_held_expert.docqa", "prefix_hit_token_pct.docqa",
            "prefix_blocks_evicted.docqa",
            "loop_host_ms_per_step.docqa"} <= set(m)
    assert m["steady_compiles.docqa"]["value"] == 0
    assert 25 < m["moe_held_assignment_pct.docqa"]["value"] < 75   # 4 of 8
    # 2 of 3 asks and more can hit; their 5 blocks of 4 of ~26 tokens
    assert 30 < m["prefix_hit_token_pct.docqa"]["value"] < 80
    assert "prefix_restore_device_pct.docqa" not in m
    # the runner put the flags back
    assert flag("prefix_cache") is False


def test_the_sample_is_the_longest_hit_and_the_longest_miss():
    class R:
        def __init__(self, n, new, ask=None):
            self.prompt, self.max_new = np.zeros(n, np.int32), new
            if ask is not None:
                self.ask = ask
    reqs = [R(10, 2, 0), R(12, 9, 0), R(30, 1, 2), R(11, 1, 1), R(14, 20, 3)]
    miss, hit = serve_slots_prefix._hit_and_miss(reqs, 2, 0)
    assert (miss.prompt.size, miss.ask) == (12, 0)
    assert (hit.prompt.size, hit.ask) == (14, 3)
    assert serve_slots_prefix._hit_and_miss(reqs[:2], 2, 0) == [reqs[1]]
    assert serve_slots_prefix._hit_and_miss([], 2, 0) == []
    # records of another generator have no ``ask``: all misses
    assert serve_slots_prefix._hit_and_miss([R(5, 5), R(6, 5)], 2, 0)[0] \
        .prompt.size == 6


# -- the new readers on canned counters ----------------------------------------

STATS = {"slots": 4, "steps": 50, "prompt_tokens_admitted": 40_000,
         "prefix_lookups": 6, "prefix_hits": 4, "prefix_hit_tokens": 28_672,
         "restore_pushes": 56, "prefix_blocks_published": 34,
         "prefix_blocks_evicted": 7}
# what the parent's loop.stats() has of these
OLD_STATS = {"slots": 4, "steps": 50, "prefix_hit_tokens": 0,
             "restore_pushes": 0}


def _read(name, ctx):
    return importlib.import_module(
        "benchmark.layer_metrics." + name).compute(ctx)


def _ctx(stats):
    return {"counters": {"slot_loop": stats} if stats else {}}


def test_prefix_hit_token_pct():
    assert _read("prefix_hit_token_pct", _ctx(STATS)) == pytest.approx(71.68)
    assert _read("prefix_hit_token_pct", _ctx(OLD_STATS)) is None
    assert _read("prefix_hit_token_pct", _ctx(None)) is None
    none = dict(STATS, prompt_tokens_admitted=0, prefix_hit_tokens=0)
    assert _read("prefix_hit_token_pct", _ctx(none)) is None


def test_prefix_blocks_evicted():
    assert _read("prefix_blocks_evicted", _ctx(STATS)) == 7.0
    assert _read("prefix_blocks_evicted",
                 _ctx(dict(STATS, prefix_blocks_evicted=0))) == 0.0
    assert _read("prefix_blocks_evicted", _ctx(OLD_STATS)) is None
    # the counter is there but the cache is off: nothing was looked up
    off = dict(STATS, prefix_lookups=0, prefix_blocks_evicted=0)
    assert _read("prefix_blocks_evicted", _ctx(off)) is None
    assert _read("prefix_blocks_evicted", _ctx(None)) is None


def test_prefix_restore_device_pct():
    programs = {"jit_step": {"count": 9, "median_s": 0.011, "total_s": 0.1},
                "jit_chunk": {"count": 30, "median_s": 0.05, "total_s": 1.5},
                "jit_push": {"count": 45, "median_s": 4e-4, "total_s": 0.018},
                "jit_pull": {"count": 20, "median_s": 1e-4, "total_s": 0.002}}
    tr = {"busy_s": 1.6, "window_s": 2.0, "programs": programs}
    assert _read("prefix_restore_device_pct", {"trace": tr}) \
        == pytest.approx(1.25)
    only = {k: v for k, v in programs.items() if k != "jit_pull"}
    assert _read("prefix_restore_device_pct",
                 {"trace": dict(tr, programs=only)}) == pytest.approx(1.125)
    # the parent's slice: neither program ran
    bare = {k: v for k, v in programs.items() if k.endswith(("step", "chunk"))}
    assert _read("prefix_restore_device_pct",
                 {"trace": dict(tr, programs=bare)}) is None
    assert _read("prefix_restore_device_pct", {"trace": None}) is None
    assert _read("prefix_restore_device_pct", {}) is None


def test_the_latent_rooflines_read_this_family(kimi_tiny):
    """``latent_step_roofline_pct`` / ``latent_chunk_roofline_pct`` (the
    accepted readers) find the counters they need in this family's loop
    (``attn_columns_*`` of a latent plane without selector) and its
    ``counts.step`` / ``counts.chunk``."""
    from benchmark.counts import kimi_k2 as counts
    cfg = _load("configs/kimi-k2.5-ep32-serve.json")
    stats = {"steps": 100, "chunks": 200, "emitted_tokens": 3000,
             "moe_assignments_held": 6000, "chunk_moe_assignments_held": 5200,
             "attn_columns_selected": 100 * 30 * 7600 * 5,
             "attn_columns_valid": 100 * 30 * 7600 * 5,
             "chunk_tokens": 200 * 500,
             "chunk_attn_columns_selected": 200 * 500 * 4000 * 5,
             "chunk_attn_columns_valid": 200 * 500 * 4000 * 5}
    peaks = _load("peaks.json")["TPU v5 lite"]
    tr = {"programs": {"jit_step": {"median_s": 0.0125},
                       "jit_chunk": {"median_s": 0.060}}}
    ctx = {"counters": {"slot_loop": stats}, "trace": tr, "peaks": peaks,
           "family": "kimi_k2", "config": cfg,
           "programs": {"step": "jit_step", "chunk": "jit_chunk"}}
    least = counts.step(cfg, 30.0, 8.0, 30 * 7600 * 5.0, 30 * 7600 * 5.0)
    want = 100 * max(least["bytes"] / peaks["hbm_bytes_per_s"],
                     least["flops"] / peaks["bf16_flops_per_s"]) / 0.0125
    assert _read("latent_step_roofline_pct", ctx) == pytest.approx(want)
    assert 40 < want < 100
    assert 10 < _read("latent_chunk_roofline_pct", ctx) < 100
