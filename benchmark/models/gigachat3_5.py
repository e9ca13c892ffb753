"""The program's hybrid decoder built as GigaChat3.5's language model
(``gigachat3_5``: gated delta-rule linear layers beside gated latent
attention, a dense SwiGLU or a share of sigmoid-routed experts, norms with
sigmoid gains before and after each half, clamped SwiGLUs, an untied
head), through its public constructors, and given the benchmark's weights.

The canonical weight tree is ``benchmark/reference/gigachat3_5.py``'s
(flat, ``l<i>.<leaf>``); this file is the one place that knows the
program's parameter names.
"""
from __future__ import annotations

from . import common
from paddle_tpu.text.models.hybrid_conv import (DELTA, LATENT,
                                                HybridConvConfig,
                                                HybridConvDecoder)

TOP = {"embed": "embed.weight", "head": "lm_head", "norm_f": "norm.weight"}
LAYER = {
    "in_norm": "operator_norm.weight", "in_post": "operator_post_norm.weight",
    "ffn_norm": "ffn_norm.weight", "ffn_post": "ffn_post_norm.weight",
    # a linear layer's mixer
    "qkv": "mixer.qkv_proj", "z": "mixer.z_proj", "b": "mixer.b_proj",
    "a": "mixer.a_proj", "conv": "mixer.conv", "dt_bias": "mixer.dt_bias",
    "A_log": "mixer.A_log", "o_norm": "mixer.norm", "out": "mixer.out_proj",
    # a full layer's
    "q_a": "mixer.q_a", "q_a_norm": "mixer.q_a_norm.weight",
    "q_b": "mixer.q_b", "kv_a": "mixer.kv_a",
    "kv_a_norm": "mixer.kv_a_norm.weight", "w_uk": "mixer.w_uk",
    "w_uv": "mixer.w_uv", "gate": "mixer.gate", "o": "mixer.o_proj",
    "ffn_g": "ffn.w_gate", "ffn_u": "ffn.w_up", "ffn_d": "ffn.w_down",
    "router": "ffn.router", "router_b": "ffn.router_bias",
    "exp_g": "ffn.w_gate", "exp_u": "ffn.w_up", "exp_d": "ffn.w_down",
    "sh_g": "ffn.shared.w_gate", "sh_u": "ffn.shared.w_up",
    "sh_d": "ffn.shared.w_down",
}


def _ids(leaves) -> dict:
    """{program parameter name: canonical leaf}."""
    out = {}
    for leaf in leaves:
        if leaf in TOP:
            out[TOP[leaf]] = leaf
        else:
            layer, key = leaf.split(".", 1)
            out[f"layers.{layer[1:]}.{LAYER[key]}"] = leaf
    return out


def leaf_ids(cfg: dict) -> dict:
    """{program parameter name: canonical leaf id}; every leaf is a top
    leaf of the flat tree."""
    from benchmark.reference.gigachat3_5 import leaf_shapes
    return _ids(leaf_shapes(cfg))


def to_program(weights: dict) -> dict:
    """{program parameter name: array} from the canonical tree."""
    return {name: weights[leaf] for name, leaf in _ids(weights).items()}


def program_config(cfg: dict):
    """Derived HERE from the configuration's own keys, not taken from the
    reference's helpers: a rule wrong on one side only is what ``correct``
    can see."""
    n = cfg["num_hidden_layers"]
    full = set(cfg["full_attention_layers"])
    sv = cfg.get("serve", {})
    return HybridConvConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=tuple(LATENT if i in full else DELTA for i in range(n)),
        dense_layers=cfg["first_k_dense_replace"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["n_routed_experts_published"],
        held_experts=tuple(cfg["experts_held"]),
        shared_experts=cfg["n_shared_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        routed_scaling=float(cfg["routed_scaling_factor"]),
        norm_topk=bool(cfg["norm_topk_prob"]), routing_norm_eps=None,
        num_heads=cfg["num_attention_heads"],
        nope_dim=cfg["qk_nope_head_dim"],
        latent_rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
        q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
        latent_rope_base=float(cfg["rope_theta"]),
        rope_scaling=cfg.get("rope_scaling"),
        latent_gate="feature" if cfg["gated_attention"] else False,
        latent_block=int(sv.get("prefill_chunk", 512)),
        attn_block=int(sv.get("attn_block", 512)),
        delta_key_heads=cfg["linear_num_key_heads"],
        delta_value_heads=cfg["linear_num_value_heads"],
        delta_key_dim=cfg["linear_key_head_dim"],
        delta_value_dim=cfg["linear_value_head_dim"],
        delta_taps=cfg["linear_conv_kernel_dim"],
        delta_chunk=int(cfg.get("linear_scan_chunk", 64)),
        delta_gate_scale=float(cfg["linear_sigmoid_gate_scale"]),
        block_norms=cfg["layernorm_type"], norm_gain="sigmoid",
        norm_gain_scale=float(cfg["layernorm_gating_weight"]),
        ffn_limit=float(cfg["swiglu_limit"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        rms_eps=cfg["rms_norm_eps"], dtype=cfg["dtype"])


def build_unweighted(cfg: dict):
    """``HybridConvDecoder(cfg)`` in eval mode, its parameters constants of
    the served dtype on the HOST (``install`` puts the benchmark's in: the
    constructor's own must not lie beside them on the device)."""
    import jax
    from paddle_tpu import nn
    with jax.default_device(jax.devices("cpu")[0]):
        model = HybridConvDecoder(
            program_config(cfg),
            weight_attr=nn.ParamAttr(initializer=nn.initializer.Constant(0.0)))
    model.eval()
    return model


def build(cfg: dict, mapped: dict):
    model = build_unweighted(cfg)
    common.install(model, mapped)
    return model
