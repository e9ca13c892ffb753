"""The ``nemotron_h`` reference against a second, independent formulation;
its seeded draws and its two controls; its counts against a hand count; the
configuration file against the published values written out here; the new
readers on a table made by hand; the cell at a tiny size through the
runner."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.counts import nemotron_h as counts
from benchmark.layer_metrics import (_state_space_scope, chunk_state_space_ms,
                                     moe_held_load_max_ratio,
                                     moe_held_rows_per_expert_step,
                                     ssm_scan_roofline_pct,
                                     state_update_roofline_pct,
                                     step_state_space_ms)
from benchmark.reference import nemotron_h as ref

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "nemotron3-ep8-reason1k-saturated"
CONFIG = "configs/nemotron-3-nano-ep8-serve.json"


def _load(rel):
    with open(os.path.join(HERE, rel)) as f:
        return json.load(f)


@pytest.fixture
def tiny():
    cfg = _load(CONFIG)
    over = _load("tests/data/nemotron_tiny.json")["over"]
    cfg["serve"].update(over.pop("serve"))
    cfg.update(over)
    return cfg


# what https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/
# blob/main/config.json publishes (the keys that say something of the
# model's shape: the catalog row's ``config``)
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2,
    "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072,
}


def test_configuration_repeats_the_published_values():
    cfg = _load(CONFIG)
    assert cfg["reduced"] == ["n_routed_experts", "vocab_size"]
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert cfg[key] == value // 8 and cfg[key + "_published"] == value
        else:
            assert cfg[key] == value, key
    # all 52 layers in the published order: no cut in depth
    kinds = ref.layer_kinds(cfg)
    assert len(kinds) == 52 and [kinds.count(k) for k in "ME*"] == [23, 23, 6]
    assert cfg["experts_held"] == [0, 16]
    for key in ("deployment", "reduced_why", "assumed"):
        assert cfg[key]
    assert set(cfg["assumed"]) >= {
        "mamba_inner_width", "attention_rotary", "time_step_limit",
        "gate_before_norm", "router_bias", "state_dtype", "residual_stream",
        "initial_weights"}
    assert cfg["serve"]["slots"] in (48, 40, 32) and cfg["dtype"] == "bfloat16"


def test_counts_against_a_hand_count(tiny):
    cfg = _load(CONFIG)
    # ISSUE 40's arithmetic, in parameters
    assert counts.mamba_parameters(cfg) == 2688 * 10304 + 6144 * 5 \
        + 3 * 64 + 4096 + 4096 * 2688                               # 38.74 M
    assert counts.attention_parameters(cfg) == 2688 * 128 * (64 + 4)  # 23.40 M
    assert counts.expert_parameters(cfg) == 2 * 2688 * 1856         # 9.98 M
    assert counts.shared_parameters(cfg) == 2 * 2688 * 3712
    assert counts.router_parameters(cfg) == 2688 * 128 + 128
    p = counts.parameters(cfg)
    assert p["embedding"] == p["head"] == 16384 * 2688              # 44.0 M
    assert p["experts"] == 23 * 16 * 2 * 2688 * 1856
    assert p["norms"] == 53 * 2688
    assert sum(p.values()) == pytest.approx(5258.4e6, rel=1e-4)
    assert counts.weight_bytes(cfg) == pytest.approx(10.52e9, rel=1e-3)
    # K and V of 6 layers x 2 heads x 128, 6 KB a token
    assert counts.kv_bytes_per_column(cfg) == 2 * 6 * 2 * 128 * 2 == 6144
    # a row's state a layer: float32 [64, 64, 128] + 3 x 6144 bfloat16
    assert counts.state_bytes_per_row_layer(cfg) == 2_097_152 + 36_864
    assert counts.recurrence_flops_per_token_layer(cfg) == 5 * 64 * 64 * 128
    assert counts.state_update(cfg, 48) == {
        "bytes": 48 * 23 * 2 * 2_134_016, "flops": 48 * 23 * 2_621_440}
    assert counts.scan(cfg, 300) == {
        "bytes": 23 * 2 * 2_134_016, "flops": 300 * 23 * 2_621_440}
    # a full step: 48 rows, 6,624 assignments over 128 experts (288 a
    # layer: an expert held here is touched with 1 - (127/128)^288 = 0.896)
    assert counts.touched_experts(cfg, 288) == pytest.approx(16 * 0.8955,
                                                             rel=1e-3)
    assert counts.held_assignments(cfg, 6624) == 828
    s = counts.step(cfg, 48, 6624, 48 * 1500)
    fixed = sum(v for k, v in p.items() if k not in ("experts", "embedding"))
    assert s["bytes"] == pytest.approx(
        2 * (fixed + 48 * 2688 + 23 * 16 * 0.8955 * 2 * 2688 * 1856)
        + 48 * 1500 * 6144 + 48 * 23 * 2 * 2_134_016, rel=1e-4)
    per_token = 23 * (2 * counts.mamba_parameters(cfg) + 2_621_440) \
        + 6 * 2 * counts.attention_parameters(cfg) \
        + 23 * 2 * (2688 * 128 + 128 + 2 * 2688 * 3712)
    assert s["flops"] == pytest.approx(
        48 * (per_token + 2 * 2688 * 16384) + 2 * 2 * 2688 * 1856 * 828
        + 4 * 32 * 128 * 6 * 48 * 1500)
    # a chunk of 300 tokens at contexts 1..300: the head once, the columns
    # once, the row's states once
    c = counts.chunk(cfg, 300, 300 * 6 * 23, 300 * 301 / 2)
    assert c["flops"] == pytest.approx(
        300 * per_token + 2 * 2688 * 16384
        + 2 * 2 * 2688 * 1856 * 300 * 6 * 23 / 8 + 4 * 32 * 128 * 6 * 45150)
    assert c["bytes"] < counts.weight_bytes(cfg) + 301 * 6144 \
        + 23 * 2 * 2_134_016
    # the tiny size by hand: 4 state-space, 4 expert layers, 1 attention
    t = counts.parameters(tiny)
    assert t["mamba"] == 4 * (64 * (64 + 128 + 4) + 128 * 5 + 12 + 64
                              + 64 * 64)
    assert t["attention"] == 64 * 16 * (8 + 4)
    assert t["experts"] == 4 * 8 * 2 * 64 * 32 and t["router"] == 4 * 520
    assert t["shared"] == 4 * 2 * 64 * 64 and t["norms"] == 10 * 64


def test_the_seeded_draws_span_the_range_a_trained_models_do(tiny):
    w = ref.init_weights(tiny, 3)
    step = np.asarray(jax.nn.softplus(w["l0.dt_bias"]))
    assert step.dtype == np.float32 and (step >= 0.001 - 1e-6).all() \
        and (step <= 0.1 + 1e-6).all()
    rate = np.exp(np.asarray(w["l2.A_log"]))
    assert (rate >= 1).all() and (rate <= 16).all() and rate.std() > 0
    np.testing.assert_array_equal(w["l0.D"], np.ones(4, np.float32))
    assert w["l1.router_b"].dtype == jnp.float32
    assert float(jnp.abs(w["l1.router_b"]).max()) < 0.1
    # the same seed gives the same tree; another seed another
    again, other = ref.init_weights(tiny, 3), ref.init_weights(tiny, 4)
    assert all(bool((w[k] == again[k]).all()) for k in w)
    assert not bool((w["l0.in_proj"] == other["l0.in_proj"]).all())
    assert set(w) == set(ref.leaf_shapes(tiny))


def _second_formulation(cfg, w, ids):
    """The same model by other means: the recurrence as its closed form
    (every pair ``s <= t`` weighted by the decay between them: quadratic,
    no state), the convolution through ``lax.conv_general_dilated``,
    grouped attention through one ``einsum``, the experts all at once
    through a dense ``[T, E]`` weight matrix."""
    f32, eps = jnp.float32, cfg["norm_eps"]
    d = ref.dims(cfg)
    H, P, G, N = d["H"], d["P"], d["G"], d["N"]
    AH, KV, ad = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    T, k = ids.shape[0], cfg["num_experts_per_tok"]

    def norm(x, g):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g

    def relu2(u, up, down):
        return jnp.square(jax.nn.relu(u @ up)) @ down

    x = w["embed"][ids].astype(f32)
    for i, kind in enumerate(ref.layer_kinds(cfg)):
        lw = {n: a.astype(f32) for n, a in ref._layer_weights(w, i).items()}
        u = norm(x, lw["norm"])
        if kind == "M":
            z, xbc, dt = jnp.split(u @ lw["in_proj"],
                                   [d["inner"], d["inner"] + d["conv_dim"]], -1)
            conv = jax.lax.conv_general_dilated(
                xbc.T[None], lw["conv"][:, None, :], (1,), [(3, 0)],
                feature_group_count=d["conv_dim"], precision="highest")[0].T
            xbc = jax.nn.silu(conv + lw["conv_b"])
            xs, b, c = jnp.split(xbc, [d["inner"], d["inner"] + G * N], -1)
            xs = xs.reshape(T, H, P)
            b = jnp.repeat(b.reshape(T, G, N), H // G, 1)
            c = jnp.repeat(c.reshape(T, G, N), H // G, 1)
            dt = jax.nn.softplus(dt + lw["dt_bias"])             # [T, H]
            cs = jnp.cumsum(dt * -jnp.exp(lw["A_log"]), 0)
            decay = jnp.exp(cs[:, None] - cs[None, :])          # [t, s, H]
            decay = jnp.where(jnp.tril(jnp.ones((T, T), bool))[..., None],
                              decay, 0.0)
            score = jnp.einsum("thn,shn->tsh", c, b, precision="highest")
            y = jnp.einsum("tsh,shp->thp", score * decay * dt[None], xs,
                           precision="highest") + lw["D"][:, None] * xs
            y = (y.reshape(T, -1) * jax.nn.silu(z)).reshape(T, G, -1)
            y = y / jnp.sqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
            x = x + (y.reshape(T, -1) * lw["norm_g"]) @ lw["out_proj"]
        elif kind == "*":
            q = (u @ lw["q"]).reshape(T, KV, AH // KV, ad)
            kk = (u @ lw["k"]).reshape(T, KV, ad)
            v = (u @ lw["v"]).reshape(T, KV, ad)
            s = jnp.einsum("tgrd,sgd->grts", q, kk,
                           precision="highest") / np.sqrt(ad)
            s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
            o = jnp.einsum("grts,sgd->tgrd", jax.nn.softmax(s, -1), v,
                           precision="highest").reshape(T, AH * ad)
            x = x + o @ lw["o"]
        else:
            s = jax.nn.sigmoid(u @ lw["router"])
            _, top = jax.lax.top_k(s + lw["router_b"], k)
            chosen = jnp.zeros_like(s).at[jnp.arange(T)[:, None], top].set(1.)
            wt = 2.5 * s * chosen / ((s * chosen).sum(-1, keepdims=True)
                                     + 1e-20)
            y = jnp.einsum("tef,efh->teh", jnp.square(jax.nn.relu(
                jnp.einsum("th,ehf->tef", u, lw["exp_u"]))), lw["exp_d"])
            x = x + jnp.einsum("te,teh->th", wt, y) \
                + relu2(u, lw["sh_u"], lw["sh_d"])
    return norm(x, w["norm_f"].astype(f32)) @ w["head"].astype(f32).T


def test_reference_equals_a_second_formulation(tiny):
    cfg = tiny
    w = ref.init_weights(cfg, 3)
    ids = np.random.default_rng(2).integers(0, cfg["vocab_size"], 29) \
        .astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(_second_formulation(cfg, w, jnp.asarray(ids)))
        got = np.asarray(ref.served_logits(
            cfg, w, ids[:1], np.concatenate([ids[1:], [0]])))
    np.testing.assert_allclose(got, want, atol=3e-5)
    assert np.abs(want).max() > 1.0             # logits of unit spread


def test_a_share_of_the_experts_leaves_the_others_part_out(tiny):
    """The reference given experts ``[2, 5)`` of 8: what it adds is the
    uncut layer's less the other experts' parts, the shared expert counted
    in both."""
    from benchmark.reference.common import Arith
    w = ref.init_weights(tiny, 8)
    lw = ref._layer_weights(w, 1)
    u = jax.random.normal(jax.random.key(1), (13, 64))
    ar = Arith("float32")
    cut = dict(lw, exp_u=lw["exp_u"][2:5], exp_d=lw["exp_d"][2:5])
    with jax.default_matmul_precision("highest"):
        whole = ref.experts(ar, u, lw, tiny, (0, 8))
        part = ref.experts(ar, u, cut, tiny, (2, 5))
        rest = ref.experts(ar, u, dict(lw, exp_u=lw["exp_u"][:2],
                                       exp_d=lw["exp_d"][:2]), tiny, (0, 2),
                           shared=False) \
            + ref.experts(ar, u, dict(lw, exp_u=lw["exp_u"][5:],
                                      exp_d=lw["exp_d"][5:]), tiny, (5, 8),
                          shared=False)
    np.testing.assert_allclose(part + rest, whole, atol=1e-5)
    assert float(jnp.abs(rest).max()) > 1e-2


def test_the_state_control_rounds_the_state_and_nothing_else():
    """``recurrence`` with the state rounded to bfloat16 after every token
    departs from the float32 one by the rounding's order, and more the
    longer the sequence; with no ``state_dtype`` nothing is rounded."""
    key = jax.random.key(0)
    T, H, P, N = 96, 4, 8, 16
    x, b, c = (jax.random.normal(jax.random.fold_in(key, i), s)
               for i, s in enumerate(((T, H, P), (T, H, N), (T, H, N))))
    dt = jnp.full((T, H), 0.01)
    a = -jnp.arange(1.0, H + 1)
    exact = ref.recurrence(x, dt, b, c, a)
    low = ref.recurrence(x, dt, b, c, a, jnp.bfloat16)
    err = np.abs(np.asarray(low - exact)).max(axis=(1, 2))
    scale = float(np.abs(np.asarray(exact)).max())
    assert 1e-4 < err[-32:].mean() / scale < 3e-2
    assert err[:4].mean() < err[-32:].mean()


@pytest.mark.parametrize("control,fails", [("float8_e4m3", True),
                                           ("bfloat16_state", False)])
def test_the_controls_at_the_tiny_size(tiny, control, fails):
    """float8 operands put other tokens first than float32 does: that
    control's mean gap is of another order than a sound program's (0
    here).  The state control runs the same comparison with the state
    alone rounded; at this size (sequences of 64) it moves no token far."""
    cfg = tiny
    w = ref.init_weights(cfg, 6)
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, 96, 24).astype(np.int32)
    served = np.asarray(jnp.argmax(ref.served_logits(
        cfg, w, prompt, rng.integers(0, 96, 40).astype(np.int32)), -1))
    own = np.asarray(ref.served_gaps(cfg, w, prompt, served[:1]))
    np.testing.assert_array_equal(own, [0.0])    # the reference's own choice
    low = np.asarray(ref.control_gaps(cfg, w, prompt, served, control))
    assert low.shape == (1,) and low[0] >= 0
    # (a sound float32 program reads under 1e-4 here; float8 0.04 over
    # these 40 tokens)
    assert (low[0] > 0.01) == fails


# -- the readers of the ``state_space`` scope ------------------------------------------

_READERS = [step_state_space_ms, chunk_state_space_ms,
            state_update_roofline_pct, ssm_scan_roofline_pct,
            moe_held_rows_per_expert_step, moe_held_load_max_ratio]


@pytest.mark.parametrize("reader", _READERS,
                         ids=[r.__name__.rsplit(".", 1)[1] for r in _READERS])
def test_a_reader_finds_nothing_where_the_program_names_no_such_scope(reader):
    """On a program without the scope or the counters (the parent of the PR
    that added them), and on a run without a capture: None, no raise."""
    ctx = {"trace": None, "programs": {"step": "jit_step",
                                       "chunk": "jit_chunk"},
           "counters": {"slot_loop": {"steps": 10, "chunks": 3}},
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
           "family": "nemotron_h", "config": _load(CONFIG)}
    assert reader.compute(ctx) is None
    # a capture whose programs name other scopes only
    ctx["_state_space_scope"] = {"programs": {
        "jit_step": {"has_table": True, "buckets": {}},
        "jit_chunk": {"has_table": True, "buckets": {}}}}
    assert reader.compute(ctx) is None


def test_the_readers_on_a_table_made_by_hand():
    cfg = _load(CONFIG)
    ctx = {"programs": {"step": "jit_step", "chunk": "jit_chunk"},
           "counters": {"slot_loop": {"steps": 100, "chunks": 30,
                                      "ssm_rows_updated": 4000,
                                      "chunk_ssm_tokens": 30 * 400}},
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
           "family": "nemotron_h", "config": cfg,
           "_state_space_scope": {"programs": {
               "jit_step": {"has_table": True, "buckets": {
                   "state_space": 2.0, "conv": 0.5, "update": 8.0}},
               "jit_chunk": {"has_table": True, "buckets": {
                   "state_space": 4.0, "conv": 0.25, "scan": 1.5}}}}}
    assert step_state_space_ms.compute(ctx) == pytest.approx(10.5)
    assert chunk_state_space_ms.compute(ctx) == pytest.approx(5.75)
    # 40 rows a step x 23 layers x 2 x 2,134,016 bytes at 819 GB/s = 4.794 ms
    assert state_update_roofline_pct.compute(ctx) == pytest.approx(
        100 * (40 * 23 * 2 * 2_134_016 / 819e9) / 8e-3, rel=1e-6)
    # 400 tokens a chunk: the operations bind (0.1224 ms) over the bytes
    assert ssm_scan_roofline_pct.compute(ctx) == pytest.approx(
        100 * (400 * 23 * 2_621_440 / 197e12) / 1.5e-3, rel=1e-6)
    assert _state_space_scope.ms(ctx, "step", ("update",)) == 8.0
    ctx["counters"]["slot_loop"].update(moe_assignments_held=100 * 23 * 32
                                        + 999, chunk_moe_assignments_held=999)
    assert moe_held_rows_per_expert_step.compute(ctx) == pytest.approx(2.0)
    # a chunk's 512 tokens x 6 a token over the published 128: 24 an expert
    ctx["counters"]["slot_loop"]["moe_expert_tokens_max"] = 171
    assert moe_held_load_max_ratio.compute(ctx) == pytest.approx(171 / 24)
    # a family whose counts do not say how it routes: nothing, no raise
    assert moe_held_load_max_ratio.compute(dict(ctx, family="gpt")) is None


def test_cell_at_a_tiny_size_is_sound_and_traced(tiny):
    """The cell through the runner on the CPU: ``correct``, and every
    per-layer metric that does not need a device trace reads a number."""
    traffic = _load("traffic/reason1k-closed-2S.json")
    traffic.update(ramp_s=0.5, pool_requests=64, trace_slice_s=0.5,
                   job_requests=6,
                   prompt_len={"dist": "lognormal", "median": 24, "sigma": 0.6,
                               "min": 3, "max": 60},
                   max_new_tokens={"dist": "lognormal", "median": 5,
                                   "sigma": 0.5, "min": 2, "max": 8})
    cfg = dict(tiny, reference_pad=8)
    cfg["serve"] = dict(cfg["serve"], queue_capacity=64)
    out = bench_run.run_cell(CELL, 11, 3.0, True, config=cfg, traffic=traffic,
                             check_device=False)
    assert out["correct"] is True and out["failed"] == 0
    got = set(out["metrics"])
    assert {"slot_occupancy_pct.reason", "slot_prefill_pct.reason",
            "slot_drain_blocked_pct.reason", "chunks_per_step.reason",
            "loop_host_ms_per_step.reason", "attn_span_read_pct.reason",
            "steady_compiles.reason", "moe_held_assignment_pct",
            "moe_held_rows_per_expert_step", "moe_held_load_max_ratio"} <= got
    assert out["metrics"]["moe_held_assignment_pct"]["value"] == 100.0
    # 3 of 8 experts a token, all held: at most slots x 3 / 8 rows a step
    assert 0 < out["metrics"]["moe_held_rows_per_expert_step"]["value"] \
        <= 3 * 3 / 8
    out = bench_run.run_cell(CELL, 12, 3.0, False, config=cfg, traffic=traffic,
                             check_device=False)
    assert set(out["metrics"]) == {"batch_job_s", "setup_s"}
