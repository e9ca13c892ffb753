"""The program's hybrid decoder built as the ``nemotron_h`` language model
(every layer one part: a Mamba-2 mixer, relu^2 experts with a shared
expert, or grouped-query attention without per-head norm and positions;
an untied head), through its public constructors, and given the
benchmark's weights.

The canonical weight tree is ``benchmark/reference/nemotron_h.py``'s (flat,
``l<i>.<leaf>``); this file is the one place that knows the program's
parameter names.
"""
from __future__ import annotations

from . import common
from paddle_tpu.text.models.hybrid_conv import (ATTN, MOE, NONE, SSM,
                                                HybridConvConfig,
                                                HybridConvDecoder)

TOP = {"embed": "embed.weight", "head": "lm_head", "norm_f": "norm.weight"}
# per kind of layer: the layer's one norm, then its leaves
LAYER = {
    "M": {"norm": "operator_norm.weight", "in_proj": "mixer.in_proj",
          "conv": "mixer.conv", "conv_b": "mixer.conv_bias",
          "dt_bias": "mixer.dt_bias", "A_log": "mixer.A_log", "D": "mixer.D",
          "norm_g": "mixer.norm", "out_proj": "mixer.out_proj"},
    "*": {"norm": "operator_norm.weight", "q": "mixer.q_proj",
          "k": "mixer.k_proj", "v": "mixer.v_proj", "o": "mixer.o_proj"},
    "E": {"norm": "ffn_norm.weight", "router": "ffn.router",
          "router_b": "ffn.router_bias", "exp_u": "ffn.w_up",
          "exp_d": "ffn.w_down", "sh_u": "ffn.shared.w_up",
          "sh_d": "ffn.shared.w_down"},
}
MIXER = {"M": SSM, "*": ATTN, "E": NONE}


def _ids(leaves) -> dict:
    """{program parameter name: canonical leaf}; a layer's kind is read
    off its leaves (only ``M`` has ``in_proj``, only ``E`` a ``router``)."""
    leaves = list(leaves)
    kinds = {}
    for leaf in leaves:
        layer, _, key = leaf.partition(".")
        if key in ("in_proj", "q", "router"):
            kinds[layer] = {"in_proj": "M", "q": "*", "router": "E"}[key]
    out = {}
    for leaf in leaves:
        if leaf in TOP:
            out[TOP[leaf]] = leaf
        else:
            layer, key = leaf.split(".", 1)
            out[f"layers.{layer[1:]}.{LAYER[kinds[layer]][key]}"] = leaf
    return out


def leaf_ids(cfg: dict) -> dict:
    """{program parameter name: canonical leaf id}; every leaf is a top
    leaf of the flat tree."""
    from benchmark.reference.nemotron_h import leaf_shapes
    return _ids(leaf_shapes(cfg))


def to_program(weights: dict) -> dict:
    """{program parameter name: array} from the canonical tree."""
    return {name: weights[leaf] for name, leaf in _ids(weights).items()}


def program_config(cfg: dict):
    from benchmark.reference.nemotron_h import held_experts, layer_kinds
    kinds = layer_kinds(cfg)
    return HybridConvConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=tuple(MIXER[k] for k in kinds),
        ffn_types=tuple(MOE if k == "E" else NONE for k in kinds),
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg.get("n_routed_experts_published",
                            cfg["n_routed_experts"]),
        held_experts=held_experts(cfg),
        shared_experts=cfg["n_shared_experts"]
        * cfg["moe_shared_expert_intermediate_size"]
        // cfg["moe_intermediate_size"],
        expert_activation=cfg["mlp_hidden_act"],
        experts_per_token=cfg["num_experts_per_tok"],
        routed_scaling=float(cfg["routed_scaling_factor"]),
        norm_topk=bool(cfg["norm_topk_prob"]), routing_norm_eps=1e-20,
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        qk_norm=False, rope_base=None,
        ssm_heads=cfg["mamba_num_heads"], ssm_head_dim=cfg["mamba_head_dim"],
        ssm_state=cfg["ssm_state_size"], ssm_groups=cfg["n_groups"],
        ssm_taps=cfg["conv_kernel"], ssm_chunk=cfg["chunk_size"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        rms_eps=cfg["norm_eps"], dtype=cfg["dtype"])


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
