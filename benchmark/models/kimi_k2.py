"""The program's latent-attention MoE decoder built as Kimi-K2.5's language
model (``kimi_k2``: every layer a full latent layer without selector, gate
or latent rescale, YaRN positions), through its public constructors, and
given the benchmark's weights.

The canonical weight tree is ``benchmark/reference/kimi_k2.py``'s (flat,
``l<i>.<leaf>``); this file is the one place that knows the program's
parameter names.
"""
from __future__ import annotations

from . import common

TOP = {"embed": "embed.weight", "head": "head", "norm_f": "norm.weight"}
LAYER = {
    "in_norm": "input_norm.weight", "post_norm": "post_norm.weight",
    "q_a": "attn.q_a", "q_a_norm": "attn.q_a_norm.weight",
    "q_b": "attn.q_b", "kv_a": "attn.kv_a",
    "kv_a_norm": "attn.kv_a_norm.weight", "w_uk": "attn.w_uk",
    "w_uv": "attn.w_uv", "o": "attn.o_proj",
    "ffn_g": "ffn.w_gate", "ffn_u": "ffn.w_up", "ffn_d": "ffn.w_down",
    "router": "ffn.router", "router_b": "ffn.router_bias",
    "exp_g": "ffn.w_gate", "exp_u": "ffn.w_up", "exp_d": "ffn.w_down",
    "sh_g": "ffn.shared.w_gate", "sh_u": "ffn.shared.w_up",
    "sh_d": "ffn.shared.w_down",
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
    from benchmark.reference.kimi_k2 import leaf_shapes
    return _ids(leaf_shapes(cfg))


def to_program(weights: dict) -> dict:
    """{program parameter name: array} from the canonical tree."""
    return {name: weights[leaf] for name, leaf in _ids(weights).items()}


def program_config(cfg: dict):
    from paddle_tpu.text.models.latent_moe import FULL, LatentMoEConfig
    return LatentMoEConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=(FULL,) * cfg["num_hidden_layers"],
        dense_layers=cfg["first_k_dense_replace"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["n_routed_experts_published"],
        experts_held=tuple(cfg["experts_held"]),
        experts_per_token=cfg["num_experts_per_tok"],
        shared_experts=cfg["n_shared_experts"],
        routed_scaling=float(cfg["routed_scaling_factor"]),
        norm_topk=bool(cfg["norm_topk_prob"]),
        num_heads=cfg["num_attention_heads"],
        nope_dim=cfg["qk_nope_head_dim"], rope_dim=cfg["qk_rope_head_dim"],
        v_dim=cfg["v_head_dim"], q_rank=cfg["q_lora_rank"],
        kv_rank=cfg["kv_lora_rank"], rope_base=float(cfg["rope_theta"]),
        rope_scaling=cfg.get("rope_scaling"),
        # no selector (so no selector-key plane), no gate, no rescale
        index_heads=0, index_dim=0, index_topk=0,
        attention_gate=False, rescale_latents=False,
        rms_eps=cfg["rms_norm_eps"],
        cache_block=int(cfg["serve"]["prefill_chunk"]),
        attn_block=int(cfg["serve"].get("attn_block", 512)),
        dtype=cfg["dtype"])


def build_unweighted(cfg: dict):
    """``LatentMoEDecoder(cfg)`` in eval mode, its parameters constants of
    the served dtype on the HOST (``install`` puts the benchmark's in: the
    constructor's own must not lie beside them on the device)."""
    import jax
    from paddle_tpu import nn
    from paddle_tpu.text.models.latent_moe import LatentMoEDecoder
    with jax.default_device(jax.devices("cpu")[0]):
        model = LatentMoEDecoder(
            program_config(cfg),
            weight_attr=nn.ParamAttr(initializer=nn.initializer.Constant(0.0)))
    model.eval()
    return model


def build(cfg: dict, mapped: dict):
    model = build_unweighted(cfg)
    common.install(model, mapped)
    return model
