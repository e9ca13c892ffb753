"""The program's latent-attention MoE decoder built as GLM-5's language
model (``glm_moe_dsa``: every layer a full latent layer WITH the learned
column selector, values wider than the keys' content part, no gate, no
latent rescale, plain rotary), through its public constructors, and given
the benchmark's weights.

The canonical weight tree is ``benchmark/reference/glm_moe_dsa.py``'s
(flat, ``l<i>.<leaf>``); the program's parameter names are those of
``benchmark/models/dots3.py`` (the same decoder, the same leaves less the
gate).
"""
from __future__ import annotations

from . import common
from .dots3 import _ids


def leaf_ids(cfg: dict) -> dict:
    """{program parameter name: canonical leaf id}; every leaf is a top
    leaf of the flat tree."""
    from benchmark.reference.glm_moe_dsa import leaf_shapes
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
        kv_rank=cfg["kv_lora_rank"],
        rope_base=float(cfg["rope_parameters"]["rope_theta"]),
        # the selector on every layer; no gate, no rescale, no scaling of
        # the positions
        index_heads=cfg["index_n_heads"], index_dim=cfg["index_head_dim"],
        index_topk=cfg["index_topk"],
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
