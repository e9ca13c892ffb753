"""The program's short-convolution / grouped-query MoE decoder, built
through its public constructors and given the benchmark's weights.

The canonical weight tree is ``benchmark/reference/lfm2.py``'s (flat,
``l<i>.<leaf>``: the layers are unlike, so nothing is stacked); this file
is the one place that knows the program's parameter names.
"""
from __future__ import annotations

from . import common
# (at import, so that a checkout without the family fails before it makes
# any weight)
from paddle_tpu.text.models.hybrid_conv import (HybridConvConfig,
                                                HybridConvDecoder)

TOP = {"embed": "embed.weight", "norm_f": "norm.weight"}
LAYER = {
    "op_norm": "operator_norm.weight", "ffn_norm": "ffn_norm.weight",
    "in_proj": "mixer.in_proj", "conv": "mixer.conv",
    "out_proj": "mixer.out_proj",
    "q": "mixer.q_proj", "k": "mixer.k_proj", "v": "mixer.v_proj",
    "o": "mixer.o_proj", "q_norm": "mixer.q_norm.weight",
    "k_norm": "mixer.k_norm.weight",
    "ffn_g": "ffn.w_gate", "ffn_u": "ffn.w_up", "ffn_d": "ffn.w_down",
    "router": "ffn.router", "router_b": "ffn.router_bias",
    "exp_g": "ffn.w_gate", "exp_u": "ffn.w_up", "exp_d": "ffn.w_down",
}


def _ids(leaves) -> dict:
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
    from benchmark.reference.lfm2 import leaf_shapes
    return _ids(leaf_shapes(cfg))


def to_program(weights: dict) -> dict:
    """{program parameter name: array} from the canonical tree."""
    return {name: weights[leaf] for name, leaf in _ids(weights).items()}


def program_config(cfg: dict):
    from benchmark.reference.lfm2 import head_dim
    n = cfg["num_hidden_layers"]
    return HybridConvConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=tuple(cfg["layer_types"][:n]),
        dense_layers=cfg["num_dense_layers"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["num_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        routed_scaling=float(cfg["routed_scaling_factor"]),
        norm_topk=bool(cfg["norm_topk_prob"]),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=head_dim(cfg),
        rope_base=float(cfg["rope_theta"]), conv_taps=cfg["conv_L_cache"],
        rms_eps=cfg["norm_eps"], dtype=cfg["dtype"])


def build_unweighted(cfg: dict):
    """``HybridConvDecoder(cfg)`` in eval mode, its parameters constants of
    the served dtype on the HOST: the model is as large as the chip's
    memory allows, so the constructor's own weights must not lie beside
    the benchmark's on the device (``install`` puts those in)."""
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
