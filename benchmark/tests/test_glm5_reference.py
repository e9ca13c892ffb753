"""GLM-5's cell at a tiny size on the CPU: the runner serves the model end
to end with the prefix cache over latent and selector-key planes and holds
one hit and one miss against the reference; the reference's shapes and
parameter count at the published widths; its selector against a sort-based
top-k; and the new readers on canned counters and a recorded capture."""
import importlib
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark import trace_reduce
from benchmark.counts import glm_moe_dsa as counts
from benchmark.layer_metrics import _selector_scope
from benchmark.reference import glm_moe_dsa as ref

CELL = "glm5-ep16-docqa16k-saturated"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CAPTURE = os.path.join(ROOT, "benchmark", "tests", "data",
                       "train_two_steps.xplane.pb.gz")


def _load(rel):
    with open(os.path.join(ROOT, "benchmark", rel)) as f:
        return json.load(f)


@pytest.fixture
def glm5_tiny():
    cfg = _load("configs/glm-5-ep16-serve.json")
    over = _load("tests/data/glm5_tiny.json")["over"]
    cfg["serve"].update(over.pop("serve"))
    cfg.update(over)
    return cfg


@pytest.fixture
def docqa_tiny():
    tr = _load("traffic/docqa16k-closed-1S.json")
    tr.update(ramp_s=1.5, pool_docs=64, trace_slice_s=0.5, job_requests=6,
              doc_len={"dist": "lognormal", "median": 22, "sigma": 0.1,
                       "min": 18, "max": 26},
              question_len={"dist": "lognormal", "median": 4, "sigma": 0.5,
                            "min": 2, "max": 8},
              max_new_tokens={"dist": "lognormal", "median": 4, "sigma": 0.5,
                              "min": 2, "max": 6})
    return tr


def test_runner_serves_the_model_end_to_end(glm5_tiny, docqa_tiny):
    from paddle_tpu.framework.flags import flag
    out = bench_run.run_cell(CELL, 2 ** 31 + 7, 3.0, True, config=glm5_tiny,
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
            "prefix_hit_token_pct.docqa", "prefix_blocks_evicted.docqa",
            "sparse_selected_pct", "chunk_sparse_selected_pct.docqa16k",
            "loop_host_ms_per_step.docqa"} <= set(m)
    assert m["steady_compiles.docqa"]["value"] == 0
    assert 30 < m["prefix_hit_token_pct.docqa"]["value"] < 80
    # 6 of a context of ~26-32 columns in a step; the chunks' tokens have
    # 1 to ~30, so more of theirs is read
    assert 15 < m["sparse_selected_pct"]["value"] < 35
    assert m["sparse_selected_pct"]["value"] \
        < m["chunk_sparse_selected_pct.docqa16k"]["value"] < 70
    assert "step_selector_ms.docqa16k" not in m
    assert "prefix_restore_device_pct.docqa" not in m
    assert flag("prefix_cache") is False


# -- the reference at the published widths ---------------------------------------

def test_leaf_shapes_and_parameter_count_at_the_configuration():
    cfg = _load("configs/glm-5-ep16-serve.json")
    shapes = ref.leaf_shapes(cfg)
    n = lambda name: int(np.prod(shapes[name][0]))           # noqa: E731
    assert sum(int(np.prod(s)) for s, _ in shapes.values()) \
        == counts.params(cfg) == 3_909_632_768                 # 3,909.6 M
    by_part = counts.parameters(cfg)
    assert by_part["attention"] == 5 * sum(n(f"l3.{k}") for k in (
        "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "w_uk", "w_uv", "o"))
    assert by_part["selector"] == 5 * sum(n(f"l3.{k}") for k in (
        "idx_q", "idx_k", "idx_k_g", "idx_k_b", "idx_w")) == 5 * 9_371_904
    assert by_part["experts"] == 4 * 16 * 37_748_736
    assert shapes["l0.w_uk"][0] == (64, 512, 192)
    assert shapes["l0.w_uv"][0] == (64, 512, 256)
    assert shapes["l4.idx_q"][0] == (2048, 32 * 128)
    assert shapes["l4.exp_g"][0] == (16, 6144, 2048)
    assert shapes["l4.router"][0] == (6144, 256)
    assert shapes["head"][0] == (6144, 19360) and "l0.router" not in shapes
    assert "l1.ffn_g" not in shapes and shapes["l0.ffn_g"][0] == (6144, 12288)
    assert not any(k.endswith("gate") for k in shapes)
    # every key of the catalog's config is in the file under its own name,
    # and only the five listed ones differ (the guide's rule for `reduced`)
    assert cfg["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                              "n_routed_experts", "vocab_size",
                              "num_nextn_predict_layers"]
    published = {"hidden_size": 6144, "num_attention_heads": 64,
                 "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
                 "v_head_dim": 256, "q_lora_rank": 2048, "kv_lora_rank": 512,
                 "index_n_heads": 32, "index_head_dim": 128,
                 "index_topk": 2048, "intermediate_size": 12288,
                 "moe_intermediate_size": 2048, "num_experts_per_tok": 8,
                 "routed_scaling_factor": 2.5, "n_shared_experts": 1,
                 "n_routed_experts_published": 256,
                 "vocab_size_published": 154880,
                 "num_hidden_layers_published": 78,
                 "max_position_embeddings": 202752}
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_parameters"] == {"rope_theta": 1000000,
                                      "rope_type": "default"}


def test_the_selector_against_a_sort_based_top_k(glm5_tiny):
    """``selection`` (blocks of queries, ``dots3.selected``: a threshold
    from ``lax.top_k`` and the lower column first among equals) against a
    stable argsort of the same float32 scores, on 40 tokens of which some
    share their input (exact ties)."""
    cfg = dict(glm5_tiny, reference_pad=8)
    w = ref._layer_weights(ref.init_weights(cfg, 3), 1)
    key, inv = ref._cfg_key(cfg), ref.rotary_frequencies(cfg)
    x = np.array(jax.random.normal(jax.random.key(5), (40, 32)))
    x[[3, 8, 9, 20, 31]] = x[3]
    xn = ref.rms_norm(jnp.asarray(x), w["in_norm"], 1e-5)
    ar = ref.Arith("float32")
    c_q = ref.rms_norm(ar.einsum("th,hr->tr", xn, w["q_a"]), w["q_a_norm"],
                       1e-5)
    got = np.asarray(ref.selection(xn, c_q, w, inv, cfg_key=key))
    qi, wi, ki = ref.selector_parts(ar, xn, c_q, w, cfg, inv)
    scores = np.asarray(ref.selector_scores(ar, qi, wi, ki))
    assert got.shape == (40, 40)
    for t in range(40):
        order = np.argsort(-scores[t, :t + 1], kind="stable")[:6]
        want = np.zeros(40, bool)
        want[order] = True
        np.testing.assert_array_equal(got[t], want)
    assert (got.sum(-1) == np.minimum(np.arange(40) + 1, 6)).all()


# -- the new readers ---------------------------------------------------------------

def _read(name, ctx):
    return importlib.import_module(
        "benchmark.layer_metrics." + name).compute(ctx)


def test_chunk_sparse_selected_pct():
    stats = {"steps": 10, "chunk_attn_columns_selected": 512 * 1800 * 5,
             "chunk_attn_columns_valid": 512 * 8192 * 5,
             "attn_columns_selected": 1, "attn_columns_valid": 4}
    ctx = {"counters": {"slot_loop": stats}}
    assert _read("chunk_sparse_selected_pct", ctx) \
        == pytest.approx(100 * 1800 / 8192)
    # a loop without such layers, a window in which no chunk ran, no loop
    for other in ({"steps": 10}, dict(stats, chunk_attn_columns_valid=0),
                  None):
        assert _read("chunk_sparse_selected_pct",
                     {"counters": {"slot_loop": other}}) is None


def test_selector_ms_on_a_recorded_capture(monkeypatch):
    """``step_selector_ms`` / ``chunk_selector_ms``: the recorded capture
    reduced with the ONE component listed; ops whose scope path holds
    ``selector`` at any depth fall into it, the others into none."""
    from benchmark.layer_metrics import _program_scopes as ps
    capture = trace_reduce.load(CAPTURE)
    (modules, ops), = ps._device_lines(capture)
    a, nb, _ = modules[0]
    names = sorted({ps.instruction_name(op[2]) for op in ops
                    if a <= op[0] and op[1] >= nb})
    table = {n: {"scope": "attention/latent_attention"} for n in names}
    picked = names[:: len(names) // 3][:3]
    table[picked[0]] = {
        "scope": "attention/latent_attention/selector/score/while/body"}
    table[picked[1]] = {"scope": "attention/latent_attention/selector/select"}
    table[picked[2]] = {"scope": "attention/latent_attention/selector"}
    monkeypatch.setattr(ps, "program_scopes", lambda: {"jit_step": table})
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: CAPTURE)
    ctx = {"trace": {"busy_s": 1.0}, "cell": {"bench_dir": os.path.join(
        ROOT, "benchmark")}, "programs": {"step": "jit_step",
                                          "chunk": "jit_chunk"}}
    got = _read("step_selector_ms", ctx)
    whole = ps.reduce_profile(capture, {"jit_step": table}, ps.load_buckets())
    assert 0 < got < whole["programs"]["jit_step"]["buckets"]["attention"]
    # its three parts, each under its own innermost component, add up to it
    parts = ps.reduce_profile(capture, {"jit_step": table}, {
        "score": "score", "select": "select", "selector": "own"})
    parts = parts["programs"]["jit_step"]["buckets"]
    assert set(parts) == {"score", "select", "own"} \
        and all(v > 0 for v in parts.values())
    assert got == pytest.approx(sum(parts.values()))
    # one reduction serves both readers; the chunk program did not run here
    assert "_selector_scope" in ctx
    assert _read("chunk_selector_ms", ctx) is None
    # a program without the component reads 0, not None: nothing ran under it
    ctx2 = dict(ctx)
    del ctx2["_selector_scope"]
    monkeypatch.setattr(ps, "program_scopes", lambda: {
        "jit_step": {n: {"scope": "attention"} for n in names}})
    assert _read("step_selector_ms", ctx2) == 0.0


def test_selector_ms_finds_nothing_without_a_table_or_a_capture(monkeypatch):
    from benchmark.layer_metrics import _program_scopes as ps
    base = {"cell": {"bench_dir": os.path.join(ROOT, "benchmark")},
            "programs": {"step": "jit_step", "chunk": "jit_chunk"}}
    assert _read("step_selector_ms", dict(base, trace=None)) is None
    assert _read("chunk_selector_ms", dict(base)) is None
    # the parent's case: a program that hands out no scope tables
    monkeypatch.setattr(ps, "program_scopes", lambda: None)
    assert _read("step_selector_ms", dict(base, trace={"busy_s": 1.0})) is None
    # a table of another program than the one that ran
    monkeypatch.setattr(ps, "program_scopes", lambda: {"jit_other": {}})
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: CAPTURE)
    assert _read("step_selector_ms", dict(base, trace={"busy_s": 1.0})) is None
    assert _selector_scope.COMPONENT == "selector"


def test_the_latent_rooflines_read_this_family():
    """``latent_step_roofline_pct`` / ``latent_chunk_roofline_pct`` (the
    accepted readers) with this family's ``counts.step`` / ``counts.chunk``
    at the issue's predicted times: both shares above 0 and under 100."""
    cfg = _load("configs/glm-5-ep16-serve.json")
    stats = {"steps": 100, "chunks": 200, "emitted_tokens": 1500,
             "moe_assignments_held": 30000, "chunk_moe_assignments_held": 25600,
             "attn_columns_selected": 100 * 15 * 2048 * 5,
             "attn_columns_valid": 100 * 15 * 16000 * 5,
             "chunk_tokens": 200 * 500,
             "chunk_attn_columns_selected": 200 * 500 * 1900 * 5,
             "chunk_attn_columns_valid": 200 * 500 * 8000 * 5}
    peaks = _load("peaks.json")["TPU v5 lite"]
    tr = {"programs": {"jit_step": {"median_s": 0.017},
                       "jit_chunk": {"median_s": 0.060}}}
    ctx = {"counters": {"slot_loop": stats}, "trace": tr, "peaks": peaks,
           "family": "glm_moe_dsa", "config": cfg,
           "programs": {"step": "jit_step", "chunk": "jit_chunk"}}
    least = counts.step(cfg, 15.0, 44.0, 15 * 2048 * 5.0, 15 * 16000 * 5.0)
    want = 100 * max(least["bytes"] / peaks["hbm_bytes_per_s"],
                     least["flops"] / peaks["bf16_flops_per_s"]) / 0.017
    assert _read("latent_step_roofline_pct", ctx) == pytest.approx(want)
    assert 20 < want < 100
    assert 5 < _read("latent_chunk_roofline_pct", ctx) < 100


# -- the routing's near-ties -------------------------------------------------------

def _router(logits_of):
    """A router over 16 experts whose logit for expert ``e`` is ``u[:, e]``
    (identity weights), no correction: top 2 of ``sigmoid(u)``."""
    cfg = {"num_experts_per_tok": 2, "norm_topk_prob": True,
           "routed_scaling_factor": 2.5}
    lw = {"router": jnp.eye(16), "router_b": jnp.zeros(16)}
    return cfg, lw, jnp.asarray(logits_of, jnp.float32)


def test_route_is_the_accepted_one_where_the_adjacent_pair_is_held():
    """Where the last expert chosen or the first left out is held here, the
    closest pair IS that pair: ids, weights, margins and the flipped choice
    are ``dots3.route``'s; elsewhere this file's margin may be finite where
    that one is infinite, never the reverse."""
    from benchmark.reference import dots3
    ar = ref.Arith("float32")
    cfg = {"num_experts_per_tok": 3, "norm_topk_prob": True,
           "routed_scaling_factor": 2.5}
    lw = {"router": jax.random.normal(jax.random.key(1), (32, 16)) * 32 ** -.5,
          "router_b": jax.random.normal(jax.random.key(2), (16,)) * 0.01}
    u = jax.random.normal(jax.random.key(0), (300, 32))
    flip = jnp.arange(300) % 2 == 0
    for held in ((0, 4), (3, 5), (0, 16)):
        want, mine = dots3.route(ar, u, lw, cfg, held), \
            ref.route(ar, u, lw, cfg, held)
        np.testing.assert_array_equal(want[0], mine[0])
        np.testing.assert_array_equal(want[1], mine[1])
        theirs = np.isfinite(np.asarray(want[2]))
        np.testing.assert_array_equal(np.asarray(want[2])[theirs],
                                      np.asarray(mine[2])[theirs])
        assert np.isfinite(np.asarray(mine[2])).sum() >= theirs.sum() > 0
        want, mine = dots3.route(ar, u, lw, cfg, held, flip), \
            ref.route(ar, u, lw, cfg, held, flip)
        np.testing.assert_array_equal(np.asarray(want[0])[theirs],
                                      np.asarray(mine[0])[theirs])


@pytest.mark.parametrize("held_rank", [3, 0], ids=["held_just_below",
                                                   "held_just_above"])
def test_route_resolves_a_three_way_near_tie(held_rank):
    """Experts ranked 1 and 2 (the last chosen, the first left out) absent
    and 3e-4 apart, a HELD expert 3e-4 beyond them: ``dots3.route`` sees
    nothing to resolve (margin infinite); here the margin is the held
    expert's distance across the boundary, and the flip brings it in (or
    takes it out), as a bfloat16 program that orders the three otherwise
    would."""
    from benchmark.reference import dots3
    ar = ref.Arith("float32")
    # logits of experts 0..3 in descending order of score, the rest far below
    row = np.full(16, -4.0, np.float32)
    row[:4] = [1.0, 0.5010, 0.5000, 0.4990] if held_rank == 3 \
        else [0.5010, 0.5000, 0.4990, 0.0]
    cfg, lw, u = _router(np.tile(row, (5, 1)))
    held = (held_rank, held_rank + 1)
    assert not np.isfinite(np.asarray(dots3.route(ar, u, lw, cfg, held)[2])).any()
    ids, w, margin = ref.route(ar, u, lw, cfg, held)
    np.testing.assert_array_equal(np.asarray(ids), np.tile([0, 1], (5, 1)))
    s = np.asarray(jax.nn.sigmoid(jnp.asarray(row)))
    want = s[1] - s[3] if held_rank == 3 else s[0] - s[2]
    np.testing.assert_allclose(np.asarray(margin), want, rtol=1e-4)
    assert 0 < want < 1e-3
    flip = jnp.asarray([True, False, True, False, True])
    ids2, w2, _ = ref.route(ar, u, lw, cfg, held, flip)
    other = [0, 3] if held_rank == 3 else [2, 1]
    np.testing.assert_array_equal(np.asarray(ids2)[::2], np.tile(other, (3, 1)))
    np.testing.assert_array_equal(np.asarray(ids2)[1::2], np.tile([0, 1], (2, 1)))
    np.testing.assert_allclose(np.asarray(w2).sum(-1), 2.5, rtol=1e-6)
    # a held expert too far beyond the boundary is no tie: its margin says so
    row[3 if held_rank == 3 else 0] += -0.2 if held_rank == 3 else 0.2
    _, _, far = ref.route(ar, jnp.asarray(np.tile(row, (5, 1))), lw, cfg, held)
    assert (np.asarray(far) > 0.02).all()
