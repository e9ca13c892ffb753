"""The ``lfm2_moe`` reference against a second, independent formulation; its
counts against a hand count; the configuration file against the published
values written out here; the cell at a tiny size through the runner."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.counts import lfm2 as counts
from benchmark.reference import lfm2 as ref

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "lfm2-pp2-reason-saturated"


def _load(rel):
    with open(os.path.join(HERE, rel)) as f:
        return json.load(f)


@pytest.fixture
def lfm2_tiny():
    cfg = _load("configs/lfm2-8b-a1b-pp2-serve.json")
    over = _load("tests/data/lfm2_tiny.json")["over"]
    cfg["serve"].update(over.pop("serve"))
    cfg.update(over)
    return cfg


# what https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json
# publishes (the keys that say something of the model's shape)
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv", "full_attention",
                    "conv", "conv", "conv", "full_attention", "conv", "conv",
                    "conv", "full_attention", "conv", "conv", "full_attention",
                    "conv", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
}


def test_configuration_repeats_the_published_values():
    cfg = _load("configs/lfm2-8b-a1b-pp2-serve.json")
    assert cfg["reduced"] == ["num_hidden_layers"]
    for key, value in PUBLISHED.items():
        if key == "num_hidden_layers":
            assert cfg[key] == 12 and cfg[key + "_published"] == value
        else:
            assert cfg[key] == value, key
    # the cut keeps the published 1 : 3 of attention to conv after the two
    # dense layers, and every kind of layer
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    assert kinds.count("full_attention") == 3 and kinds.count("conv") == 9
    for key in ("deployment", "reduced_why", "assumed"):
        assert cfg[key]
    assert set(cfg["assumed"]) >= {"tie_word_embeddings", "head_dim",
                                   "routing_denominator", "expert_bias"}


def test_counts_against_a_hand_count(lfm2_tiny):
    cfg = _load("configs/lfm2-8b-a1b-pp2-serve.json")
    # ISSUE 31's arithmetic, in parameters
    assert counts.mixer_parameters(cfg, "conv") == 4 * 2048 ** 2 + 3 * 2048
    assert counts.mixer_parameters(cfg, "full_attention") == \
        2 * 2048 * 2048 + 2 * 2048 * 512 + 128
    assert counts.expert_parameters(cfg) == 3 * 2048 * 1792      # 11.0 M
    p = counts.parameters(cfg)
    assert p["embedding"] == 65536 * 2048                         # 134.2 M
    assert p["dense_ffn"] == 2 * 3 * 2048 * 7168
    assert p["experts"] == 10 * 32 * 3 * 2048 * 1792
    assert counts.weight_bytes(cfg) == pytest.approx(7.86e9, rel=2e-3)
    # the cache: K and V of 3 layers x 8 heads x 64, 6 KB a token
    assert counts.kv_bytes_per_column(cfg) == 2 * 3 * 8 * 64 * 2 == 6144
    # 9 conv layers x 2 entries x 2048 x 2 bytes, read and written
    assert counts.state_bytes_per_row(cfg) == 2 * 73728
    # a full step: 128 rows, 5,120 assignments, every expert touched
    s = counts.step(cfg, 128, 128 * 4 * 10, 128 * 600)
    assert counts.touched_experts(cfg, 512) == pytest.approx(32, abs=1e-4)
    assert s["bytes"] == pytest.approx(
        counts.weight_bytes(cfg) + 128 * 600 * 6144 + 128 * 147456, rel=1e-6)
    per_token = 2 * (9 * counts.mixer_parameters(cfg, "conv")
                     + 3 * counts.mixer_parameters(cfg, "full_attention")) \
        + 2 * 6 * 2048 * 7168 + 10 * 2 * 2048 * 32
    assert s["flops"] == pytest.approx(
        128 * (per_token + 2 * 2048 * 65536) + 2 * 3 * 2048 * 1792 * 5120
        + 4 * 32 * 64 * 3 * 128 * 600)
    # a chunk of 300 tokens at contexts 1..300: the head once, the columns once
    c = counts.chunk(cfg, 300, 300 * 40, 300 * 301 / 2)
    assert c["flops"] == pytest.approx(
        300 * per_token + 2 * 2048 * 65536 + 2 * 3 * 2048 * 1792 * 12000
        + 4 * 32 * 64 * 3 * 45150)
    assert c["bytes"] < counts.weight_bytes(cfg) + 301 * 6144 + 147456
    # the tiny size by hand: 4 conv + 2 attention layers, 2 dense, 4 MoE
    t = counts.parameters(lfm2_tiny)
    assert t["mixers"] == 4 * (4 * 64 * 64 + 3 * 64) \
        + 2 * (2 * 64 * 64 + 2 * 64 * 32 + 32)
    assert t["experts"] == 4 * 8 * 3 * 64 * 32 and t["router"] == 4 * (64 * 8 + 8)
    assert t["norms"] == 13 * 64 and t["embedding"] == 96 * 64


def _second_formulation(cfg, w, ids):
    """The same model by other means: the convolution through
    ``lax.conv_general_dilated`` (depthwise, left-padded), grouped
    attention through one ``einsum`` over (KV head, queries a head), the
    experts all at once through a dense ``[T, E]`` weight matrix."""
    f32, eps = jnp.float32, cfg["norm_eps"]
    h, H, KV = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    d, k = ref.head_dim(cfg), cfg["num_experts_per_tok"]
    T = ids.shape[0]

    def norm(x, g):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g

    def rope(x):                        # [T, n, d], halves paired
        inv = float(cfg["rope_theta"]) ** (-jnp.arange(0, d, 2) / d)
        ang = jnp.arange(T)[:, None, None] * inv
        a, b = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                                b * jnp.cos(ang) + a * jnp.sin(ang)], -1)

    x = w["embed"][ids].astype(f32)
    for i, kind in enumerate(ref.layer_kinds(cfg)):
        lw = {n: a.astype(f32) for n, a in ref._layer_weights(w, i).items()}
        xn = norm(x, lw["op_norm"])
        if kind == "conv":
            b, c, xx = jnp.split(xn @ lw["in_proj"], 3, -1)
            u = (b * xx).T[None]                              # [1, h, T]
            conv = jax.lax.conv_general_dilated(
                u, lw["conv"][:, None, :], (1,), [(2, 0)],
                feature_group_count=h, precision="highest")[0].T
            x = x + (c * conv) @ lw["out_proj"]
        else:
            q = rope(norm((xn @ lw["q"]).reshape(T, H, d), lw["q_norm"]))
            kk = rope(norm((xn @ lw["k"]).reshape(T, KV, d), lw["k_norm"]))
            v = (xn @ lw["v"]).reshape(T, KV, d)
            q = q.reshape(T, KV, H // KV, d)
            s = jnp.einsum("tgrd,sgd->grts", q, kk,
                           precision="highest") / np.sqrt(d)
            s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
            o = jnp.einsum("grts,sgd->tgrd", jax.nn.softmax(s, -1), v,
                           precision="highest").reshape(T, H * d)
            x = x + o @ lw["o"]
        un = norm(x, lw["ffn_norm"])
        if i < cfg["num_dense_layers"]:
            x = x + (jax.nn.silu(un @ lw["ffn_g"]) * (un @ lw["ffn_u"])) \
                @ lw["ffn_d"]
            continue
        s = jax.nn.sigmoid(un @ lw["router"])
        _, top = jax.lax.top_k(s + lw["router_b"], k)
        chosen = jnp.zeros_like(s).at[jnp.arange(T)[:, None], top].set(1.0)
        wt = s * chosen / ((s * chosen).sum(-1, keepdims=True) + 1e-6)
        y = jnp.einsum("tef,efh->teh",
                       jax.nn.silu(jnp.einsum("th,ehf->tef", un, lw["exp_g"]))
                       * jnp.einsum("th,ehf->tef", un, lw["exp_u"]),
                       lw["exp_d"])
        x = x + jnp.einsum("te,teh->th", wt, y)
    return norm(x, w["norm_f"].astype(f32)) @ w["embed"].astype(f32).T


def test_reference_equals_a_second_formulation(lfm2_tiny):
    cfg = lfm2_tiny
    w = ref.init_weights(cfg, 3)
    ids = np.random.default_rng(2).integers(0, cfg["vocab_size"], 21) \
        .astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(_second_formulation(cfg, w, jnp.asarray(ids)))
        got = np.asarray(ref.served_logits(
            cfg, w, ids[:1], np.concatenate([ids[1:], [0]])))
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert np.abs(want).max() > 1.0             # logits of unit spread


def test_blocked_head_keeps_what_the_comparison_needs(lfm2_tiny):
    """The tied head in vocabulary blocks (here 3 of 40 rows, the last one
    overlapping) against the whole logits: best, picked, magnitude, argmax,
    and so the gaps."""
    cfg = dict(lfm2_tiny)
    w = ref.init_weights(cfg, 4)
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, 96, 9).astype(np.int32)
    served = rng.integers(0, 96, 6).astype(np.int32)
    whole = np.asarray(ref.served_logits(cfg, w, prompt, served))
    padded, at, n = ref._padded(cfg, prompt, served)
    x = ref._hidden(cfg, w, jnp.asarray(padded), "float32")
    picks = np.zeros(at.shape, np.int32)
    picks[:n] = served
    best, got, big, arg = (np.asarray(a)[:n] for a in ref._head(
        x, w["norm_f"], w["embed"], jnp.asarray(at), jnp.asarray(picks),
        eps=cfg["norm_eps"], precision="float32", block=40))
    np.testing.assert_allclose(best, whole.max(-1), atol=1e-6)
    np.testing.assert_allclose(got, whole[np.arange(n), served], atol=1e-6)
    np.testing.assert_allclose(big, np.abs(whole).max(-1), atol=1e-6)
    np.testing.assert_array_equal(arg, whole.argmax(-1))
    # six tokens are one block: the mean of the six gaps
    gaps = np.asarray(ref.served_gaps(cfg, w, prompt, served))
    each = (whole.max(-1) - whole[np.arange(n), served]) / np.abs(whole).max(-1)
    np.testing.assert_allclose(gaps, [each.mean()], rtol=1e-5)
    assert gaps[0] > 0.05
    # (the first position's logits depend on the prompt alone)
    own = np.asarray(ref.served_gaps(cfg, w, prompt, whole.argmax(-1)[:1]))
    np.testing.assert_array_equal(own, [0.0])    # the reference's own choice


def test_gaps_are_compared_in_blocks_of_64_tokens():
    """One token in ten far off reads as the routing's discontinuity does
    in a sound run; the block means say so, where the widest single gap
    would not.  Blocks are consecutive and hold at least 64 tokens."""
    gaps = np.zeros(200, np.float32)
    gaps[::10] = 0.2
    got = ref._block_means(gaps)
    assert got.shape == (3,) and got.max() < 0.03
    np.testing.assert_allclose(got[0], gaps[:67].mean(), rtol=1e-6)
    assert ref._block_means(gaps[:63]).shape == (1,)
    # a run of 64 tokens gone wrong (a wrapped plane, a lost state) shows
    gaps[100:164] = 0.3
    assert ref._block_means(gaps).max() > 0.15


def test_control_precision_fails_the_limit_at_the_tiny_size(lfm2_tiny):
    """float8 operands put other tokens first than float32 does: the
    control's mean gap is of another order than a sound program's (0
    here)."""
    cfg = lfm2_tiny
    w = ref.init_weights(cfg, 6)
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, 96, 24).astype(np.int32)
    served = np.asarray(jnp.argmax(ref.served_logits(
        cfg, w, prompt, rng.integers(0, 96, 8).astype(np.int32)), -1))
    low = np.asarray(ref.control_gaps(cfg, w, prompt, served, "float8_e4m3"))
    assert low.shape == (1,) and low[0] > cfg["limits"]["served_gap_rel"]


def test_cell_at_a_tiny_size_is_sound_and_traced(lfm2_tiny):
    """The cell through the runner on the CPU: ``correct``, and every
    per-layer metric that does not need a device trace reads a number."""
    traffic = _load("traffic/reason-closed-2S.json")
    traffic.update(ramp_s=0.5, pool_requests=64, trace_slice_s=0.5,
                   job_requests=6,
                   prompt_len={"dist": "lognormal", "median": 10, "sigma": 0.8,
                               "min": 3, "max": 30},
                   max_new_tokens={"dist": "lognormal", "median": 5,
                                   "sigma": 0.5, "min": 2, "max": 8})
    cfg = dict(lfm2_tiny, reference_pad=8)
    cfg["serve"] = dict(cfg["serve"], queue_capacity=64)
    out = bench_run.run_cell(CELL, 11, 3.0, True, config=cfg, traffic=traffic,
                             check_device=False)
    assert out["correct"] is True and out["failed"] == 0
    got = set(out["metrics"])
    assert {"slot_occupancy_pct.reason", "slot_prefill_pct.reason",
            "slot_drain_blocked_pct.reason", "chunks_per_step.reason",
            "loop_host_ms_per_step.reason", "attn_span_read_pct.reason",
            "steady_compiles.reason", "moe_rows_per_expert_step",
            "moe_expert_load_max_ratio"} <= got
    rows = out["metrics"]["moe_rows_per_expert_step"]["value"]
    assert 0 < rows <= 3 * 2 / 8
    out = bench_run.run_cell(CELL, 12, 3.0, False, config=cfg, traffic=traffic,
                             check_device=False)
    assert set(out["metrics"]) == {"batch_job_s", "setup_s"}
