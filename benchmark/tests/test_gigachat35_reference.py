"""The ``gigachat3_5`` reference against a second, naive per-token loop; its
controls; its counts against the leaf shapes and a hand count; the
configuration file against the published values written out here; the new
reader and the accepted linear readers on tables made by hand; the cell at
a tiny size through the runner."""
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.counts import gigachat3_5 as counts
from benchmark.layer_metrics import (_delta_scope, chunk_delta_solve_ms,
                                     chunk_linear_attention_ms,
                                     hybrid_chunk_roofline_pct,
                                     hybrid_step_roofline_pct,
                                     linear_scan_roofline_pct,
                                     linear_update_roofline_pct,
                                     moe_held_assignment_pct,
                                     moe_held_load_max_ratio,
                                     moe_held_rows_per_expert_step,
                                     step_linear_attention_ms)
from benchmark.reference import gigachat3_5 as ref
from benchmark.reference.common import Arith

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "gigachat35-ep16-reason1k-saturated"
CONFIG = "configs/gigachat3.5-ep16-serve.json"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def _load(rel):
    with open(os.path.join(HERE, rel)) as f:
        return json.load(f)


@pytest.fixture
def tiny():
    cfg = _load(CONFIG)
    over = _load("tests/data/gigachat3_5_tiny.json")["over"]
    cfg["serve"].update(over.pop("serve"))
    cfg.update(over)
    return cfg


# what https://huggingface.co/ai-sage/GigaChat3.5-432B-A28B/blob/main/
# config.json publishes (the catalog row's ``config``)
PUBLISHED = {
    "vocab_size": 128256, "max_position_embeddings": 262144,
    "hidden_size": 7168, "intermediate_size": 18432,
    "moe_intermediate_size": 2048, "num_hidden_layers": 40,
    "nextn_is_sparse": False, "num_attention_heads": 64,
    "n_shared_experts": 1, "n_routed_experts": 256,
    "routed_scaling_factor": 2.5, "kv_lora_rank": 512, "q_lora_rank": 1536,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
    "qk_head_dim": 192, "n_group": 1, "topk_group": 1,
    "num_experts_per_tok": 8, "first_k_dense_replace": 3,
    "norm_topk_prob": True, "rope_interleave": True,
    "num_key_value_heads": 64, "hidden_act": "silu", "rms_norm_eps": 1e-06,
    "rope_theta": 100000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 8,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 32768,
                     "type": "yarn"},
    "attention_bias": False, "norm_type": "ZeroCenteredGatedNorm",
    "layernorm_type": "pre_post", "layernorm_gating_weight": 2,
    "gated_attention": True, "use_shared_expert_sigmoid": False,
    "use_mla_scaling_factor": True,
    "linear_attention_type": "GigaChat35GatedDeltaNet",
    "full_attention_layers": [3, 7, 11, 15, 19, 23, 27, 31, 35, 39],
    "linear_key_head_dim": 128, "linear_value_head_dim": 128,
    "linear_conv_kernel_dim": 4, "linear_num_key_heads": 32,
    "linear_num_value_heads": 64,
    "linear_gating_type": "gated_rmsnorm_sigmoid_zero_centered",
    "linear_sigmoid_gate_scale": 2, "linear_attn_o_norm_eps": 1e-06,
    "swiglu_limit": 10, "tie_word_embeddings": False,
    "num_nextn_predict_layers": 2, "model_type": "gigachat3_5",
    "tf_legacy_loss": False,
}
ASSUMED = ("norm", "block", "attention_gate", "rotary", "rope_scaling",
           "l2_norm", "linear_output", "swiglu_limit", "routing",
           "left_out", "written_from", "state_dtype", "residual_stream",
           "linear_scan_chunk", "initial_weights")


def test_configuration_repeats_the_published_values():
    cfg = _load(CONFIG)
    assert cfg["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "full_attention_layers",
        "n_routed_experts", "vocab_size", "num_nextn_predict_layers"]
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert cfg[key + "_published"] == value, key
        else:
            assert cfg[key] == value, key
    # published layers 2-6: the last dense layer + one period, 1 full : 3
    first = cfg["first_published_layer"]
    assert (first, cfg["num_hidden_layers"]) == (2, 5)
    assert [i + first for i in cfg["full_attention_layers"]] == [3]
    assert ref.layer_kinds(cfg) == [ref.LINEAR, ref.FULL] + [ref.LINEAR] * 3
    assert cfg["first_k_dense_replace"] == 1
    # the floors: >= 8 experts held, >= an eighth of the vocabulary
    assert cfg["experts_held"] == [0, 16] and cfg["n_routed_experts"] == 16
    assert cfg["vocab_size"] * 8 == 128256 and "16 chips" in cfg["deployment"]
    for key in ASSUMED:
        assert cfg["assumed"][key], key
    sv = cfg["serve"]
    assert sv["workers"] == 2 * sv["slots"] and sv["prefill_chunk"] == 512
    assert sv["attn_block"] == 512 and sv["max_new_tokens"] == 1024
    assert sv["max_len"] == 8192 and sv["slots"] in (96, 128, 160)
    assert not sv.get("prefix_cache")


def test_the_program_is_built_from_the_configurations_keys():
    from benchmark.models import gigachat3_5 as adapter
    pc = adapter.program_config(_load(CONFIG))
    assert pc.layer_types == ("gated_delta", "latent_attention",
                              "gated_delta", "gated_delta", "gated_delta")
    assert (pc.dense_layers, pc.num_experts, pc.held_experts,
            pc.experts_per_token, pc.shared_experts) == (1, 256, (0, 16), 8, 1)
    assert (pc.delta_key_heads, pc.delta_value_heads, pc.delta_key_dim,
            pc.delta_value_dim, pc.delta_taps, pc.delta_chunk) == (
                32, 64, 128, 128, 4, 64)
    assert (pc.num_heads, pc.nope_dim, pc.latent_rope_dim, pc.v_dim,
            pc.q_rank, pc.kv_rank) == (64, 128, 64, 128, 1536, 512)
    assert (pc.block_norms, pc.norm_gain, pc.norm_gain_scale, pc.ffn_limit,
            pc.latent_gate, pc.routing_norm_eps) == (
                "pre_post", "sigmoid", 2.0, 10.0, "feature", None)
    assert pc.rope_scaling["factor"] == 8 and pc.latent_rope_base == 1e5
    assert not pc.tie_embeddings and pc.routed_scaling == 2.5


def test_counts_against_the_leaf_shapes_and_a_hand_count():
    cfg = _load(CONFIG)
    leaves = sum(math.prod(s) for s, _ in ref.leaf_shapes(cfg).values())
    p = counts.parameters(cfg)
    assert sum(p.values()) == leaves == counts.params(cfg)
    assert leaves == pytest.approx(4731.6e6, rel=5e-5)
    # ISSUE 48's arithmetic: a linear mixer 235.86 M, a full one 159.84 M,
    # an expert 44.04 M, a dense FFN 396.36 M, table + head 229.83 M
    assert counts.linear_parameters(cfg) == pytest.approx(235.86e6, rel=1e-4)
    assert counts.full_parameters(cfg) == pytest.approx(159.84e6, rel=1e-4)
    assert counts.expert_parameters(cfg) == 3 * 7168 * 2048
    assert p["dense_ffn"] == 3 * 7168 * 18432
    assert p["embedding"] + p["head"] == 2 * 16032 * 7168
    assert counts.layers(cfg, "E") == 4 and counts.held_experts(cfg) == 16
    assert counts.layers(cfg, counts.LINEAR) == 4
    assert counts.layers(cfg, counts.FULL) == 1
    assert counts.published_experts(cfg) == 256
    assert counts.experts_per_token(cfg) == 8
    assert counts.state_bytes_per_row_layer(cfg) == 4_194_304
    assert counts.conv_bytes_per_row_layer(cfg) == 98_304
    assert counts.latent_bytes_per_column(cfg) == 1152
    slots = cfg["serve"]["slots"]
    assert counts.linear_state_plane(cfg) == f"f32[{slots},64,128,128]"
    # -- the update, by hand: 128 rows x 4 layers x (4 MiB read + 4 MiB
    # written) = 4.29 GB = 5.24 ms at 819 GB/s; 7 x 64 x 128 x 128 a token
    # a layer = 3.76 GFLOP = 0.02 ms: the bytes bind
    u = counts.linear_update(cfg, 128)
    assert u["bytes"] == 128 * 4 * 2 * 4_194_304
    assert u["flops"] == 128 * 4 * 7 * 64 * 128 * 128
    assert u["bytes"] / 819e9 == pytest.approx(5.24e-3, rel=0.01)
    # -- the scan, by hand, a scan chunk of 64 tokens a value head: the
    # inverse 2/3 x 64^3 = 174,763; two right-hand sides 64^2 x 256 =
    # 1,048,576; the weighted sum 64^2 x 128 = 524,288; three products of
    # 2 x 64 x 128 x 128 = 6,291,456; a KEY head 2 x 2 x 64^2 x 128 =
    # 2,097,152.  (64 x 8,039,083 + 32 x 2,097,152) / 64 tokens = 9.09 M a
    # token a layer; 512 tokens x 4 layers = 18.6 GFLOP = 0.094 ms at the
    # peak, over 33.6 MB of states = 0.041 ms: the operations bind
    per_value_head = 2 / 3 * 64 ** 3 + 64 ** 2 * 256 + 64 ** 2 * 128 \
        + 3 * 2 * 64 * 128 * 128
    per_token = (64 * per_value_head + 32 * 4 * 64 ** 2 * 128) / 64
    assert counts.scan_flops_per_token_layer(cfg) == pytest.approx(per_token)
    assert per_token == pytest.approx(9.09e6, rel=1e-3)
    s = counts.linear_scan(cfg, 512)
    assert s["bytes"] == 4 * 2 * 4_194_304
    assert s["flops"] == pytest.approx(512 * 4 * per_token)
    assert s["flops"] / 197e12 == pytest.approx(0.0945e-3, rel=0.01)
    # more than the recurrence's own 7.34 M a token a layer, less than twice
    assert 1.0 < per_token / counts.update_flops_per_token_layer(cfg) < 2.0
    # -- a step of 128 rows at 1.5k of context, 4,096 assignments: 9.0 GB of
    # weights (15.6 of the 16 held experts touched a layer) + 4.39 GB of
    # states and convolution inputs + 0.22 GB of latent rows = 16.7 ms
    st = counts.step(cfg, 128, 128 * 8 * 4, 128 * 1500)
    fixed = leaves - p["experts"] - p["embedding"]
    touched = 4 * 16 * (1 - (255 / 256) ** 1024)
    assert st["bytes"] == pytest.approx(
        2 * (fixed + 128 * 7168 + touched * 3 * 7168 * 2048)
        + 128 * 1500 * 1152 + 128 * 4 * 2 * (4_194_304 + 98_304))
    assert st["bytes"] / 819e9 == pytest.approx(16.7e-3, rel=0.02)
    assert st["flops"] / 197e12 < st["bytes"] / 819e9
    # -- a chunk of 512 tokens: 2 x 512 x 1.68 G parameters every token
    # meets = 1.72 TFLOP, + 1,024 held assignments x 2 x 44.04 M = 0.09, +
    # the scans' 0.02 and the attention's 0.02 = 1.85 TFLOP = 9.4 ms at the
    # peak, under its 9.3 GB of weights' 11.4 ms
    ch = counts.chunk(cfg, 512, 512 * 8 * 4, 512 * 600)
    assert ch["flops"] / 197e12 == pytest.approx(9.4e-3, rel=0.01)
    assert ch["bytes"] / 819e9 == pytest.approx(11.4e-3, rel=0.03)


# -- a second formulation: one token at a time, everything written out -----------

def _naive(cfg, w, ids):
    """The same model by another route: every token's row computed by
    itself from the rows before it, the convolution as a sum over a
    window, the delta rule as matrix algebra on ``S`` (``S (I - beta k
    k^T)`` written out), the attention a loop over heads, the experts a
    loop over the tokens' own choices.  numpy float64."""
    f = lambda a: np.asarray(a, np.float64)                     # noqa: E731
    eps, g = cfg["rms_norm_eps"], cfg["layernorm_gating_weight"]
    L, ld = cfg["swiglu_limit"], ref.linear_dims(cfg)
    G, H, N, P = ld["G"], ld["H"], ld["N"], ld["P"]
    sig = lambda x: 1 / (1 + np.exp(-x))                        # noqa: E731
    silu = lambda x: x * sig(x)                                 # noqa: E731

    def norm(x, wt):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) \
            * (g * sig(f(wt)))

    def ffn(u, wg, wu, wd):
        return (silu(np.minimum(u @ f(wg), L))
                * np.clip(u @ f(wu), -L, L)) @ f(wd)
    x = f(w["embed"])[np.asarray(ids)]
    T = x.shape[0]
    inv = f(ref.yarn_frequencies(cfg))
    for i, kind in enumerate(ref.layer_kinds(cfg)):
        lw = {k[len(f"l{i}."):]: v for k, v in w.items()
              if k.startswith(f"l{i}.")}
        u = norm(x, lw["in_norm"])
        if kind == ref.LINEAR:
            qkv = u @ f(lw["qkv"])
            taps = f(lw["conv"])
            conv = np.zeros_like(qkv)
            for t in range(T):
                for j in range(taps.shape[1]):
                    s = t - (taps.shape[1] - 1) + j
                    if s >= 0:
                        conv[t] += taps[:, j] * qkv[s]
            conv = silu(conv)
            q, k, v = np.split(conv, [ld["keys"], 2 * ld["keys"]], -1)
            unit = lambda a: a / np.sqrt((a * a).sum(-1, keepdims=True)    # noqa: E731,E501
                                         + ref.L2_EPS)
            q = unit(q.reshape(T, G, N)) * N ** -0.5
            k = unit(k.reshape(T, G, N))
            v = v.reshape(T, H, P)
            beta = sig(u @ f(lw["b"]))
            alpha = np.exp(-np.exp(f(lw["A_log"])) * np.log1p(np.exp(
                u @ f(lw["a"]) + f(lw["dt_bias"]))))
            o = np.zeros((T, H, P))
            S = np.zeros((H, P, N))
            for t in range(T):
                for h in range(H):
                    kt, qt = k[t, h // (H // G)], q[t, h // (H // G)]
                    S[h] = alpha[t, h] * S[h] @ (
                        np.eye(N) - beta[t, h] * np.outer(kt, kt)) \
                        + beta[t, h] * np.outer(v[t, h], kt)
                    o[t, h] = S[h] @ qt
            o = o / np.sqrt((o * o).mean(-1, keepdims=True)
                            + cfg["linear_attn_o_norm_eps"])
            o = o * (1 + f(lw["o_norm"]))
            o = o.reshape(T, H * P) * cfg["linear_sigmoid_gate_scale"] \
                * sig(u @ f(lw["z"]))
            mix = o @ f(lw["out"])
        else:
            d = ref.dims(cfg)
            Hh, dn, dr, dv, rkv = (d[k] for k in ("H", "dn", "dr", "dv",
                                                  "rkv"))

            def rot(a, t):
                ang = t * inv
                a1, a2 = a[..., :dr // 2], a[..., dr // 2:]
                return np.concatenate([a1 * np.cos(ang) - a2 * np.sin(ang),
                                       a2 * np.cos(ang) + a1 * np.sin(ang)],
                                      -1)
            c_q = norm(u @ f(lw["q_a"]), lw["q_a_norm"])
            qq = (c_q @ f(lw["q_b"])).reshape(T, Hh, dn + dr)
            kv = u @ f(lw["kv_a"])
            c = norm(kv[:, :rkv], lw["kv_a_norm"])
            k_r = np.stack([rot(kv[t, rkv:], t) for t in range(T)])
            scale = ref.softmax_scale(cfg)
            o = np.zeros((T, Hh, dv))
            for h in range(Hh):
                k_n, vv = c @ f(lw["w_uk"])[h], c @ f(lw["w_uv"])[h]
                for t in range(T):
                    s = (k_n[:t + 1] @ qq[t, h, :dn]
                         + k_r[:t + 1] @ rot(qq[t, h, dn:], t)) * scale
                    p = np.exp(s - s.max())
                    o[t, h] = (p / p.sum()) @ vv[:t + 1]
            mix = (o.reshape(T, Hh * dv) * sig(u @ f(lw["gate"]))) \
                @ f(lw["o"])
        x = x + norm(mix, lw["in_post"])
        u = norm(x, lw["ffn_norm"])
        if i < cfg["first_k_dense_replace"]:
            y = ffn(u, lw["ffn_g"], lw["ffn_u"], lw["ffn_d"])
        else:
            lo, hi = cfg["experts_held"]
            s = sig(u @ f(lw["router"]))
            y = ffn(u, lw["sh_g"], lw["sh_u"], lw["sh_d"])
            for t in range(T):
                top = np.argsort(-(s[t] + f(lw["router_b"])),
                                 kind="stable")[:cfg["num_experts_per_tok"]]
                for e in top:
                    if lo <= e < hi:
                        y[t] += s[t, e] / s[t, top].sum() \
                            * cfg["routed_scaling_factor"] * ffn(
                                u[t], lw["exp_g"][e - lo], lw["exp_u"][e - lo],
                                lw["exp_d"][e - lo])
        x = x + norm(y, lw["ffn_post"])
    return norm(x, w["norm_f"]) @ f(w["head"]).T


def test_reference_equals_a_naive_per_token_loop(tiny):
    cfg = tiny
    w = ref.init_weights(cfg, 3)
    ids = np.random.default_rng(4).integers(0, 96, 40).astype(np.int32)
    got = np.asarray(ref.served_logits(cfg, w, ids[:1],
                                       np.concatenate([ids[1:], [0]])))
    want = _naive(cfg, w, ids)
    # float32 against float64, logits ~1 wide
    np.testing.assert_allclose(got, want, atol=3e-5)
    assert np.abs(want).max() > 0.5


def test_the_state_control_rounds_the_state_and_nothing_else():
    key = jax.random.key(0)
    T, H, d = 96, 4, 16
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731,E501
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (T, H, d))
               for i in range(3))
    q, k = unit(q) / 4, unit(k)
    alpha = jnp.exp(-0.1 * jax.random.uniform(jax.random.fold_in(key, 3),
                                              (T, H)))
    beta = jax.random.uniform(jax.random.fold_in(key, 4), (T, H))
    exact, S = ref.recurrence(q, k, v, alpha, beta, final_state=True)
    low = ref.recurrence(q, k, v, alpha, beta, jnp.bfloat16)
    err = np.abs(np.asarray(low - exact)).max(axis=(1, 2))
    scale = float(np.abs(np.asarray(exact)).max())
    assert 1e-4 < err[-32:].mean() / scale < 3e-2
    assert err[:4].mean() < err[-32:].mean()
    assert S.shape == (H, d, d) and S.dtype == jnp.float32


def test_the_clamp_is_the_equations():
    u = jnp.asarray(np.random.default_rng(0).normal(0, 4, (6, 8)),
                    jnp.float32)
    wg, wu, wd = (jnp.asarray(np.random.default_rng(i).normal(0, 1, s),
                              jnp.float32)
                  for i, s in ((1, (8, 12)), (2, (8, 12)), (3, (12, 8))))
    g, v = np.asarray(u @ wg), np.asarray(u @ wu)
    assert (g > 3).any() and (np.abs(v) > 3).any()
    want = (np.minimum(g, 3) / (1 + np.exp(-np.minimum(g, 3)))
            * np.clip(v, -3, 3)) @ np.asarray(wd)
    np.testing.assert_allclose(
        np.asarray(ref.swiglu(Arith("float32"), u, wg, wu, wd, 3.0)), want,
        atol=1e-4)


def test_the_float8_control_at_the_tiny_size(tiny):
    """float8 operands move the best logit of a position by percents of the
    largest logit; ``control_gaps`` runs, and the reference's own choice
    reads 0."""
    cfg = tiny
    w = ref.init_weights(cfg, 6)
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, 96, 24).astype(np.int32)
    served = np.asarray(jnp.argmax(ref.served_logits(
        cfg, w, prompt, rng.integers(0, 96, 16).astype(np.int32)), -1))
    own = np.asarray(ref.served_gaps(cfg, w, prompt, served[:1]))
    np.testing.assert_array_equal(own, [0.0])
    low = np.asarray(ref.control_gaps(cfg, w, prompt, served, "float8_e4m3"))
    assert low.shape == (1,) and (low >= 0).all()        # one block of 16
    exact = np.asarray(ref.served_logits(cfg, w, prompt, served))
    moved = np.abs(np.asarray(ref.served_logits(
        cfg, w, prompt, served, "float8_e4m3")) - exact).max() \
        / np.abs(exact).max()
    assert moved > 5e-3


# -- the readers ------------------------------------------------------------------

_READERS = [chunk_delta_solve_ms, step_linear_attention_ms,
            chunk_linear_attention_ms, linear_update_roofline_pct,
            linear_scan_roofline_pct]


@pytest.mark.parametrize("reader", _READERS,
                         ids=[r.__name__.rsplit(".", 1)[1] for r in _READERS])
def test_a_reader_finds_nothing_where_the_program_names_no_such_scope(reader):
    """On a program without the scopes (the parent of the PR that added
    them, a lightning layer's scan), and on a run without a capture: None,
    no raise."""
    ctx = {"trace": None, "programs": {"step": "jit_step",
                                       "chunk": "jit_chunk"},
           "counters": {"slot_loop": {"steps": 10, "chunks": 3}},
           "peaks": PEAKS, "family": "gigachat3_5", "config": _load(CONFIG)}
    assert reader.compute(ctx) is None
    empty = {"programs": {"jit_step": {"has_table": True, "buckets": {}},
                          "jit_chunk": {"has_table": True, "buckets": {}}}}
    ctx["_sala_scope"], ctx["_delta_scope"] = empty, empty
    assert reader.compute(ctx) is None


def test_the_readers_on_a_table_made_by_hand():
    """The accepted linear readers take this family's operations and bytes
    from its own counts, and the new one its own part of the scan."""
    cfg = _load(CONFIG)
    counters = {"steps": 100, "chunks": 30, "emitted_tokens": 100 * 120,
                "ssm_rows_updated": 100 * 120, "chunk_ssm_tokens": 30 * 500,
                "chunk_tokens": 30 * 500,
                "moe_assignments": 32 * (100 * 120 + 30 * 500),
                "chunk_moe_assignments": 32 * 30 * 500,
                "moe_assignments_held": 2 * (100 * 120 + 30 * 500),
                "chunk_moe_assignments_held": 2 * 30 * 500,
                "moe_expert_tokens_max": 40,
                "kv_columns_valid": 100 * 120 * 1500,
                "chunk_kv_columns_valid": 30 * 500 * 600}
    ctx = {"programs": {"step": "jit_step", "chunk": "jit_chunk"},
           "counters": {"slot_loop": counters}, "peaks": PEAKS,
           "family": "gigachat3_5", "config": cfg,
           "trace": {"programs": {"jit_step": {"median_s": 0.025},
                                  "jit_chunk": {"median_s": 0.016}}},
           "_sala_scope": {"programs": {
               "jit_step": {"has_table": True, "buckets": {
                   "linear_attention/update": 7.0,
                   "linear_attention/plane_copy": 1.0}},
               "jit_chunk": {"has_table": True, "buckets": {
                   "linear_attention/scan": 2.0}}}},
           "_delta_scope": {"programs": {
               "jit_step": {"has_table": True, "buckets": {}},
               "jit_chunk": {"has_table": True, "buckets": {
                   "solve": 0.75, "carry": 1.25}}}}}
    assert chunk_delta_solve_ms.compute(ctx) == pytest.approx(0.75)
    assert _delta_scope.ms(ctx, "chunk", "carry") == pytest.approx(1.25)
    assert _delta_scope.ms(ctx, "step", "solve") is None
    assert step_linear_attention_ms.compute(ctx) == pytest.approx(7.0)
    assert chunk_linear_attention_ms.compute(ctx) == pytest.approx(2.0)
    # 120 rows a step x 4 layers x 2 x 4,194,304 bytes at 819 GB/s over the
    # update's 7 ms and the state planes' own copies' 1 ms
    assert linear_update_roofline_pct.compute(ctx) == pytest.approx(
        100 * (120 * 4 * 2 * 4_194_304 / 819e9) / 8e-3, rel=1e-6)
    # 500 tokens a chunk: the operations bind over the bytes
    assert linear_scan_roofline_pct.compute(ctx) == pytest.approx(
        100 * (500 * 4 * counts.scan_flops_per_token_layer(cfg) / 197e12)
        / 2e-3, rel=1e-6)
    step = counts.step(cfg, 120, 32 * 120, 120 * 1500)
    assert hybrid_step_roofline_pct.compute(ctx) == pytest.approx(
        100 * step["bytes"] / 819e9 / 0.025, rel=1e-6)
    assert 0 < hybrid_chunk_roofline_pct.compute(ctx) < 100
    # 2 held assignments a token: 240 a step over 4 layers x 16 experts
    assert moe_held_rows_per_expert_step.compute(ctx) == pytest.approx(
        240 / 64)
    assert moe_held_assignment_pct.compute(ctx) == pytest.approx(6.25)
    # a full chunk's even share is 512 x 8 / 256 = 16 rows an expert
    assert moe_held_load_max_ratio.compute(ctx) == pytest.approx(40 / 16)


def test_cell_at_a_tiny_size_is_sound_and_traced(tiny):
    """The cell through the runner on the CPU: ``correct``, and every
    per-layer metric that does not need a device trace reads a number."""
    traffic = _load("traffic/reason1k-2chunk-closed-2S.json")
    traffic.update(ramp_s=0.5, pool_requests=64, trace_slice_s=0.5,
                   job_requests=4,
                   prompt_len={"dist": "lognormal", "median": 28,
                               "sigma": 0.1, "min": 17, "max": 32},
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
            "moe_held_rows_per_expert_step", "moe_held_load_max_ratio",
            "moe_held_assignment_pct"} <= got
    # 4 of 16 experts held: a quarter of the choices when routing is even
    assert 10 < out["metrics"]["moe_held_assignment_pct"]["value"] < 45
    out = bench_run.run_cell(CELL, 12, 4.0, False, config=cfg, traffic=traffic,
                             check_device=False)
    assert set(out["metrics"]) == {"batch_job_s", "setup_s"}


def test_the_traffic_pads_every_prompt_to_two_chunks():
    from benchmark.generators import requests
    traffic = _load("traffic/reason1k-2chunk-closed-2S.json")
    assert (traffic["kind"], traffic["clients_per_slot"],
            traffic["shape_seed"], traffic["pool_requests"],
            traffic["trace_slice_s"]) == ("closed_loop", 2, 20261005, 2048,
                                          1.0)
    pool = requests.pool(traffic, 16032, 1)
    plen = np.asarray([p.size for p, _ in pool])
    mnew = np.asarray([m for _, m in pool])
    assert plen.min() >= 513 and plen.max() <= 1024
    assert set(-(-plen // 512)) == {2}
    assert 128 <= mnew.min() and mnew.max() <= 1024
    assert 480 < np.median(mnew) < 545 and 860 < np.median(plen) < 930
    assert max(int(p.max()) for p, _ in pool) < 16032
