"""The ``minicpm_sala`` reference against a second, naive per-token loop; its
controls; its counts against the leaf shapes and a hand count; the
configuration file against the published values written out here; the new
readers on a table made by hand; the cell at a tiny size through the
runner."""
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.counts import minicpm_sala as counts
from benchmark.layer_metrics import (_sala_scope, chunk_linear_attention_ms,
                                     chunk_sparse_attention_ms,
                                     linear_scan_roofline_pct,
                                     linear_update_roofline_pct,
                                     sparse_blocks_selected_pct,
                                     sparse_read_roofline_pct,
                                     step_linear_attention_ms,
                                     step_sparse_attention_ms)
from benchmark.reference import minicpm_sala as ref

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "sala-pp2-doc12k-saturated"
CONFIG = "configs/minicpm-sala-pp2-serve.json"


def _load(rel):
    with open(os.path.join(HERE, rel)) as f:
        return json.load(f)


@pytest.fixture
def tiny():
    cfg = _load(CONFIG)
    over = _load("tests/data/minicpm_sala_tiny.json")["over"]
    cfg["serve"].update(over.pop("serve"))
    cfg.update(over)
    return cfg


LIGHTNING, MINICPM4 = "lightning-attn", "minicpm4"
# what https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json
# publishes (the keys that say something of the model's shape: the catalog
# row's ``config``)
PUBLISHED = {
    "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 16384,
    "lightning_head_dim": 128, "lightning_nh": 32, "lightning_nkv": 32,
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "max_position_embeddings": 524288, "model_type": "minicpm_sala",
    "mixer_types": [MINICPM4] + [LIGHTNING] * 8 + [MINICPM4]
    + [LIGHTNING] * 6 + [MINICPM4] * 2 + [LIGHTNING] * 4 + [MINICPM4]
    + [LIGHTNING] * 6 + [MINICPM4] * 3,
    "num_attention_heads": 32, "num_hidden_layers": 32,
    "num_key_value_heads": 2, "qk_norm": True, "rand_init": False,
    "rms_norm_eps": 1e-06, "vocab_size": 73448, "rope_theta": 10000,
    "scale_emb": 12, "scale_depth": 1.4, "mup_denominator": 32,
    "dim_model_base": 256, "tie_word_embeddings": False,
    "use_output_gate": True, "use_output_norm": True,
    "attn_use_output_gate": True,
}


def test_configuration_repeats_the_published_values():
    cfg = _load(CONFIG)
    assert cfg["reduced"] == ["num_hidden_layers", "mixer_types"]
    assert len(PUBLISHED["mixer_types"]) == 32
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert cfg[key + "_published"] == value, key
        else:
            assert cfg[key] == value, key
    # one stage of two: published layers 9-24, the model's own mix 1 : 3
    first = cfg["first_published_layer"]
    assert (first, cfg["num_hidden_layers"]) == (9, 16)
    assert cfg["mixer_types"] == PUBLISHED["mixer_types"][first:first + 16]
    kinds = ref.layer_kinds(cfg)
    assert [i for i, k in enumerate(kinds) if k == MINICPM4] == [0, 7, 8, 13]
    assert kinds.count(LIGHTNING) == 12
    assert PUBLISHED["mixer_types"].count(MINICPM4) * 3 \
        == PUBLISHED["mixer_types"].count(LIGHTNING)
    # neither published half has it
    assert PUBLISHED["mixer_types"][:16].count(MINICPM4) == 2
    assert PUBLISHED["mixer_types"][16:].count(MINICPM4) == 6
    for key in ("decay_slopes", "output_norm", "output_gate", "sparse_config",
                "per_token_rule", "state_dtype", "residual_stream"):
        assert cfg["assumed"][key], key
    assert cfg["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64, "topk": 64,
        "init_blocks": 1, "window_size": 2048, "dense_len": 8192}
    # the scalings keep the published depth and base
    assert ref.residual_scale(cfg) == pytest.approx(1.4 / math.sqrt(32))
    assert ref.logit_divisor(cfg) == 16.0
    assert ref.published_index(cfg, 1) == 10
    sv = cfg["serve"]
    assert sv["workers"] == 2 * sv["slots"] and sv["prefill_chunk"] == 512
    assert sv["max_new_tokens"] == 768 and sv["max_len"] <= 24576


def test_the_program_is_built_with_the_published_scalings():
    """The adapter derives the three muP scalings and the linear layers'
    depths from the configuration's keys by itself; pinned to the numbers
    of the published model (1.4 / sqrt(32), 4096 / 256, layers 9-24 of 32),
    and the reference's own helpers, written apart, give the same."""
    from benchmark.models import minicpm_sala as adapter
    cfg = _load(CONFIG)
    pc = adapter.program_config(cfg)
    assert pc.embed_scale == 12.0 and pc.logit_divisor == 16.0
    assert pc.residual_scale == pytest.approx(0.2474873734, rel=1e-9)
    assert [round(d * 31) for d in pc.linear_decay_depth] \
        == list(range(9, 25))
    assert pc.linear_decay_depth == pytest.approx(
        [l / 31 for l in range(9, 25)], rel=1e-12)
    assert pc.sparse_gate and pc.layer_types.count("sparse_attention") == 4
    assert [i for i, k in enumerate(pc.layer_types)
            if k == "sparse_attention"] == [0, 7, 8, 13]
    assert pc.residual_scale == pytest.approx(ref.residual_scale(cfg))
    assert pc.logit_divisor == ref.logit_divisor(cfg)
    assert [ref.published_index(cfg, i) for i in range(16)] \
        == list(range(9, 25)) and ref.published_depth(cfg) == 32


def test_counts_against_the_leaf_shapes():
    cfg = _load(CONFIG)
    leaves = sum(math.prod(s) for s, _ in ref.leaf_shapes(cfg).values())
    p = counts.parameters(cfg)
    assert sum(p.values()) == leaves == 5_039_448_064
    assert counts.linear_parameters(cfg) + counts.mlp_parameters(cfg) \
        == 285_212_672 + 2 * 128 + 4096
    assert counts.sparse_parameters(cfg) + counts.mlp_parameters(cfg) \
        == 253_755_392 + 2 * 128
    assert counts.weight_bytes(cfg) == 2 * leaves
    assert counts.state_bytes_per_row_layer(cfg) == 2_097_152
    assert counts.kv_bytes_per_column(cfg) == 4 * 1024
    # a step of 24 rows at 12k: 9.48 GB of weights + 1.21 GB of states +
    # 0.44 GB of chosen blocks and pooled keys = 13.6 ms at 819 GB/s
    s = counts.step(cfg, 24, 0, 24 * 12200)
    assert s["bytes"] == pytest.approx(
        2 * (leaves - 73448 * 4096) + 24 * 4096 * 2
        + 24 * 12 * 2 * 2_097_152
        + 24 * (4096 * 4096 + ((12200 - 32) // 16 + 1) * 4 * 512))
    assert s["bytes"] / 819e9 == pytest.approx(13.6e-3, rel=0.01)
    # a chunk: 2 x 512 x 4.44 G parameters = 4.54 TFLOP, 23 ms at the peak
    c = counts.chunk(cfg, 512, 0, 512 * 6144)
    assert c["flops"] / 197e12 == pytest.approx(23.8e-3, rel=0.01)
    # 24 rows x 64 blocks a layer = 101 MB a layer
    r = counts.sparse_read(cfg, 24 * 64, 0)
    assert r["bytes"] == 24 * 64 * 65536
    assert counts.linear_update(cfg, 24)["bytes"] == 24 * 12 * 4_194_304
    assert counts.linear_scan(cfg, 512)["flops"] == 512 * 12 * 5 * 32 * 128 ** 2


# -- a second formulation: one token at a time, everything written out -----------

def _naive(cfg, w, ids):
    """Logits ``[T, V]``: numpy, float64, a python loop over tokens; the
    sparse layer by explicit lists of blocks."""
    f = np.float64
    W = {k: np.asarray(v, f) for k, v in w.items()}
    eps, r = cfg["rms_norm_eps"], ref.residual_scale(cfg)
    sp = cfg["sparse_config"]
    T = len(ids)

    def norm(x, g):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * g

    def rope(x, t, base):
        d = x.shape[-1]
        ang = t * base ** (-np.arange(0, d, 2) / d)
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                               x2 * np.cos(ang) + x1 * np.sin(ang)], -1)

    def sigmoid(x):
        return 1 / (1 + np.exp(-x))

    x = W["embed"][ids] * cfg["scale_emb"]
    for i, kind in enumerate(ref.layer_kinds(cfg)):
        L = {k[len(f"l{i}."):]: v for k, v in W.items()
             if k.startswith(f"l{i}.")}
        u = norm(x, L["op_norm"])
        out = np.zeros_like(x)
        if kind == LIGHTNING:
            H, d = cfg["lightning_nh"], cfg["lightning_head_dim"]
            s = np.asarray(ref.decay_slopes(cfg, i), f)
            S = np.zeros((H, d, d))
            for t in range(T):
                q = rope(norm((u[t] @ L["q"]).reshape(H, d), L["q_norm"]), t,
                         cfg["rope_theta"])
                k = rope(norm((u[t] @ L["k"]).reshape(H, d), L["k_norm"]), t,
                         cfg["rope_theta"])
                v = (u[t] @ L["v"]).reshape(H, d)
                S = np.exp(-s)[:, None, None] * S + k[:, :, None] * v[:, None]
                o = np.einsum("hk,hkv->hv", q, S) / math.sqrt(d)
                o = o / np.sqrt((o * o).mean(-1, keepdims=True) + eps)
                o = o.reshape(-1) * L["o_norm"] * sigmoid(u[t] @ L["gate"])
                out[t] = o @ L["o"]
        else:
            H, KV, d = cfg["num_attention_heads"], \
                cfg["num_key_value_heads"], cfg["head_dim"]
            rep = H // KV
            q = norm((u @ L["q"]).reshape(T, H, d), L["q_norm"])
            k = norm((u @ L["k"]).reshape(T, KV, d), L["k_norm"])
            v = (u @ L["v"]).reshape(T, KV, d)
            for t in range(T):
                n, o = t + 1, np.zeros((H, d))
                for g in range(KV):
                    cols = list(range(n))
                    if n > sp["dense_len"]:
                        J = (n - sp["kernel_size"]) // sp["kernel_stride"] + 1
                        c = np.stack([k[sp["kernel_stride"] * j:
                                        sp["kernel_stride"] * j
                                        + sp["kernel_size"], g].mean(0)
                                      for j in range(J)])
                        a = np.zeros(J)
                        for h in range(g * rep, (g + 1) * rep):
                            e = np.exp(c @ q[t, h] / math.sqrt(d))
                            a += e / e.sum()
                        nb = -(-n // sp["block_size"])
                        score = []
                        for b in range(nb):
                            lo, hi = b * sp["block_size"], \
                                (b + 1) * sp["block_size"]
                            over = [a[j] for j in range(J)
                                    if sp["kernel_stride"] * j < hi
                                    and sp["kernel_stride"] * j
                                    + sp["kernel_size"] > lo]
                            forced = b < sp["init_blocks"] \
                                or hi > n - sp["window_size"]
                            score.append(np.inf if forced
                                         else max(over, default=-np.inf))
                        order = sorted(range(nb), key=lambda b: (-score[b], b))
                        chosen = set(order[:sp["topk"]])
                        cols = [s_ for s_ in cols
                                if s_ // sp["block_size"] in chosen]
                    for h in range(g * rep, (g + 1) * rep):
                        e = np.exp(k[cols, g] @ q[t, h] / math.sqrt(d))
                        o[h] = (e / e.sum()) @ v[cols, g]
                out[t] = (o.reshape(-1) * sigmoid(u[t] @ L["gate"])) @ L["o"]
        x = x + r * out
        u = norm(x, L["ffn_norm"])
        g_ = u @ L["ffn_g"]
        x = x + r * ((g_ * sigmoid(g_) * (u @ L["ffn_u"])) @ L["ffn_d"])
    return (norm(x, W["norm_f"]) / ref.logit_divisor(cfg)) @ W["head"].T


def test_reference_equals_a_naive_per_token_loop(tiny):
    """64 tokens: the last 32 are past ``dense_len`` and choose 4 of 5-8
    blocks, on three sparse layers, one token and one block at a time."""
    cfg = tiny
    w = ref.init_weights(cfg, 3)
    ids = np.random.default_rng(2).integers(0, cfg["vocab_size"], 64) \
        .astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.served_logits(
            cfg, w, ids[:1], np.concatenate([ids[1:], [0]])))
    want = _naive(cfg, w, ids)
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert np.abs(want).max() > 0.1


def test_the_state_control_rounds_the_state_and_nothing_else():
    key = jax.random.key(0)
    T, H, d = 96, 4, 16
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (T, H, d))
               for i in range(3))
    slopes = jnp.asarray([0.3, 0.1, 0.03, 0.01])
    exact, S = ref.recurrence(q, k, v, slopes, final_state=True)
    low = ref.recurrence(q, k, v, slopes, jnp.bfloat16)
    err = np.abs(np.asarray(low - exact)).max(axis=(1, 2))
    scale = float(np.abs(np.asarray(exact)).max())
    assert 1e-4 < err[-32:].mean() / scale < 3e-2
    assert err[:4].mean() < err[-32:].mean()
    assert S.shape == (H, d, d) and S.dtype == jnp.float32


@pytest.mark.parametrize("control,fails", [("float8_e4m3", True),
                                           ("bfloat16_state", False)])
def test_the_controls_at_the_tiny_size(tiny, control, fails):
    """float8 operands move the best logit of a position by 2.4% of the
    largest logit, the state rounded to bfloat16 after every token by 0.1%
    at sequences of 72 (read here as numbers, not as which token comes
    first: at this size the embedding x 12 is half the final state, only 2
    of 32 positions change their first token under float8, and another
    build of the same program flips those).  ``control_gaps`` runs, and a
    sound program's own choice reads 0."""
    cfg = tiny
    w = ref.init_weights(cfg, 6)
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, 96, 40).astype(np.int32)
    served = np.asarray(jnp.argmax(ref.served_logits(
        cfg, w, prompt, rng.integers(0, 96, 32).astype(np.int32)), -1))
    own = np.asarray(ref.served_gaps(cfg, w, prompt, served[:1]))
    np.testing.assert_array_equal(own, [0.0])    # the reference's own choice
    low = np.asarray(ref.control_gaps(cfg, w, prompt, served, control))
    assert low.shape == (1,) and low[0] >= 0
    best, _, big, _ = ref._served(cfg, w, prompt, served, "float32")
    moved = np.abs(np.asarray(ref._served(cfg, w, prompt, served, control)[0])
                   - np.asarray(best)).max() / np.asarray(big).max()
    assert (moved > 5e-3) == fails and moved > 1e-4


# -- the readers of the two scopes ------------------------------------------------

_READERS = [step_linear_attention_ms, chunk_linear_attention_ms,
            step_sparse_attention_ms, chunk_sparse_attention_ms,
            linear_update_roofline_pct, linear_scan_roofline_pct,
            sparse_read_roofline_pct, sparse_blocks_selected_pct]


@pytest.mark.parametrize("reader", _READERS,
                         ids=[r.__name__.rsplit(".", 1)[1] for r in _READERS])
def test_a_reader_finds_nothing_where_the_program_names_no_such_scope(reader):
    """On a program without the scopes or the counters (the parent of the
    PR that added them), and on a run without a capture: None, no raise."""
    ctx = {"trace": None, "programs": {"step": "jit_step",
                                       "chunk": "jit_chunk"},
           "counters": {"slot_loop": {"steps": 10, "chunks": 3}},
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
           "family": "minicpm_sala", "config": _load(CONFIG)}
    assert reader.compute(ctx) is None
    # a capture whose programs name other scopes only
    ctx["_sala_scope"] = {"programs": {
        "jit_step": {"has_table": True, "buckets": {}},
        "jit_chunk": {"has_table": True, "buckets": {}}}}
    assert reader.compute(ctx) is None


def test_the_readers_on_a_table_made_by_hand():
    cfg = _load(CONFIG)
    ctx = {"programs": {"step": "jit_step", "chunk": "jit_chunk"},
           "counters": {"slot_loop": {
               "steps": 100, "chunks": 30, "ssm_rows_updated": 2400,
               "chunk_ssm_tokens": 30 * 500,
               "sparse_blocks_valid": 100 * 24 * 4 * 190,
               "sparse_blocks_selected": 100 * 24 * 4 * 64,
               "pooled_entries_scored": 100 * 24 * 4 * 760}},
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
           "family": "minicpm_sala", "config": cfg,
           "_sala_scope": {"programs": {
               "jit_step": {"has_table": True, "buckets": {
                   "linear_attention/update": 2.5,
                   "linear_attention/plane_copy": 0.5,
                   "sparse_attention/pool": 0.25,
                   "sparse_attention/select": 0.5,
                   "sparse_attention/read": 1.5}},
               "jit_chunk": {"has_table": True, "buckets": {
                   "linear_attention/scan": 3.0, "linear_attention": 0.5,
                   "sparse_attention/select": 1.0,
                   "sparse_attention/read": 6.0}}}}}
    assert step_linear_attention_ms.compute(ctx) == pytest.approx(2.5)
    assert chunk_linear_attention_ms.compute(ctx) == pytest.approx(3.5)
    assert step_sparse_attention_ms.compute(ctx) == pytest.approx(2.25)
    assert chunk_sparse_attention_ms.compute(ctx) == pytest.approx(7.0)
    # 24 rows a step x 12 layers x 2 x 2,097,152 bytes at 819 GB/s = 1.475
    # ms, over the update's 2.5 ms and the state planes' own copies' 0.5
    # (which the time under the scope, above, does not hold)
    assert linear_update_roofline_pct.compute(ctx) == pytest.approx(
        100 * (24 * 12 * 2 * 2_097_152 / 819e9) / 3e-3, rel=1e-6)
    # 500 tokens a chunk: the operations bind (0.0798 ms) over the bytes
    assert linear_scan_roofline_pct.compute(ctx) == pytest.approx(
        100 * (500 * 12 * 2_621_440 / 197e12) / 3e-3, rel=1e-6)
    # 24 x 4 x (64 blocks of 64 KB + 760 entries of 512 B) over select + read
    assert sparse_read_roofline_pct.compute(ctx) == pytest.approx(
        100 * (24 * 4 * (64 * 65536 + 760 * 512) / 819e9) / 2e-3, rel=1e-6)
    assert sparse_blocks_selected_pct.compute(ctx) == pytest.approx(
        100 * 64 / 190)
    assert _sala_scope.ms(ctx, "step", "sparse_attention", ("read",)) == 1.5


def test_only_the_state_planes_copies_join_the_update():
    """``plane_copy_ms``: of a step's ops without a scope, the compiler's
    copies of a whole state plane and nothing else (not another plane's
    copy, not another layer's op, not a copy that lies under the scope
    already)."""
    from types import SimpleNamespace as NS
    plane = counts.linear_state_plane(_load(CONFIG))
    assert plane == "f32[24,32,128,128]"
    layout = "{3,2,1,0:T(8,128)}"
    ops = [("%fusion.1 = f32[24,4096]{1,0} fusion(%p), kind=kLoop", 1000),
           (f"%copy-start.2 = ({plane}{layout}, {plane}{layout}, u32[]) "
            "copy-start(%p.3)", 10),
           (f"%copy-done.2 = {plane}{layout} copy-done(%copy-start.2)", 300),
           (f"%copy.9 = {plane}{layout} copy(%p.4)", 50),
           ("%copy-done.1 = bf16[4096,4096]{1,0} copy-done(%copy-start.1)",
            40),
           ("%dynamic-update-slice.27 = pred[24,2,1,24576]{3,2,1,0} "
            "dynamic-update-slice(%a, %b)", 170)]
    events, modules, t = [], [], 0
    for _ in range(3):
        modules.append(NS(name="jit_step(123)", start_ns=t,
                          duration_ns=sum(ns for _, ns in ops)))
        for name, ns in ops:
            events.append(NS(name=name, start_ns=t, duration_ns=ns))
            t += ns
        t += 100
    profile = NS(planes=[NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=modules),
        NS(name="XLA Ops", events=events)])])
    table = {"fusion.1": {"scope": "attention/linear_attention/update"},
             "copy-start.2": {"scope": ""}, "copy-done.2": {"scope": ""},
             "copy.9": {"scope": "attention/linear_attention/update"},
             "copy-done.1": {"scope": ""},
             "dynamic-update-slice.27": {"scope": ""}}
    assert _sala_scope.plane_copy_ms(profile, {"jit_step": table}, plane) \
        == {"jit_step": pytest.approx(310e-6)}
    # a program that hands out no table, a plane of another shape
    assert _sala_scope.plane_copy_ms(profile, {}, plane) == {}
    assert _sala_scope.plane_copy_ms(profile, {"jit_step": table},
                                     "f32[48,64,64,128]") == {}


def test_cell_at_a_tiny_size_is_sound_and_traced(tiny):
    """The cell through the runner on the CPU: ``correct``, and every
    per-layer metric that does not need a device trace reads a number."""
    traffic = _load("traffic/doc12k-closed-2S.json")
    traffic.update(ramp_s=0.5, pool_requests=64, trace_slice_s=0.5,
                   job_requests=4,
                   prompt_len={"dist": "lognormal", "median": 70, "sigma": 0.3,
                               "min": 40, "max": 120},
                   max_new_tokens={"dist": "lognormal", "median": 5,
                                   "sigma": 0.5, "min": 2, "max": 8})
    cfg = dict(tiny, reference_pad=8)
    cfg["serve"] = dict(cfg["serve"], queue_capacity=64)
    out = bench_run.run_cell(CELL, 11, 4.0, True, config=cfg, traffic=traffic,
                             check_device=False)
    assert out["correct"] is True and out["failed"] == 0
    got = set(out["metrics"])
    assert {"slot_occupancy_pct.reason", "slot_prefill_pct.reason",
            "slot_drain_blocked_pct.reason", "chunks_per_step.reason",
            "loop_host_ms_per_step.reason", "steady_compiles.reason",
            "sparse_blocks_selected_pct"} <= got
    assert "attn_span_read_pct.reason" not in got
    # every context is past dense_len 32: 4 blocks of the 6-16 it has
    assert 25 < out["metrics"]["sparse_blocks_selected_pct"]["value"] < 70
    out = bench_run.run_cell(CELL, 12, 4.0, False, config=cfg, traffic=traffic,
                             check_device=False)
    assert set(out["metrics"]) == {"batch_job_s", "setup_s"}
