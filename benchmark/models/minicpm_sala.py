"""The program's hybrid decoder built as the ``minicpm_sala`` language model
(lightning linear-attention layers beside block-sparse grouped-query
layers, each with a dense SwiGLU; the three muP scalings; an untied head),
through its public constructors, and given the benchmark's weights.

The canonical weight tree is ``benchmark/reference/minicpm_sala.py``'s
(flat, ``l<i>.<leaf>``); this file is the one place that knows the
program's parameter names.
"""
from __future__ import annotations

from . import common
from paddle_tpu.nn.functional.attention import BlockSparse
from paddle_tpu.text.models.hybrid_conv import (LINEAR, SPARSE,
                                                HybridConvConfig,
                                                HybridConvDecoder)

TOP = {"embed": "embed.weight", "head": "lm_head", "norm_f": "norm.weight"}
# a layer's leaves: the mixer's differ by kind only in ``o_norm`` (a linear
# layer's output norm), the rest of the names are shared
LAYER = {"op_norm": "operator_norm.weight", "ffn_norm": "ffn_norm.weight",
         "ffn_g": "ffn.w_gate", "ffn_u": "ffn.w_up", "ffn_d": "ffn.w_down",
         "q": "mixer.q_proj", "k": "mixer.k_proj", "v": "mixer.v_proj",
         "gate": "mixer.gate_proj", "o": "mixer.o_proj",
         "q_norm": "mixer.q_norm.weight", "k_norm": "mixer.k_norm.weight",
         "o_norm": "mixer.norm"}
MIXER = {"lightning-attn": LINEAR, "minicpm4": SPARSE}


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
    from benchmark.reference.minicpm_sala import leaf_shapes
    return _ids(leaf_shapes(cfg))


def to_program(weights: dict) -> dict:
    """{program parameter name: array} from the canonical tree."""
    return {name: weights[leaf] for name, leaf in _ids(weights).items()}


def program_config(cfg: dict):
    """The scalings and the layers' depths are derived HERE from the
    configuration's own keys, not taken from the reference's helpers: a
    formula wrong on one side only is what ``correct`` can see."""
    kinds = cfg["mixer_types"][:cfg["num_hidden_layers"]]
    sp = cfg["sparse_config"]
    # the stage's place in the published model: the residual scale keeps
    # the published depth under its root, a linear layer's decay its
    # published index over the published depth
    depth = int(cfg.get("num_hidden_layers_published",
                        cfg["num_hidden_layers"]))
    first = int(cfg.get("first_published_layer", 0))
    return HybridConvConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=tuple(MIXER[k] for k in kinds),
        dense_layers=len(kinds), intermediate_size=cfg["intermediate_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        qk_norm=bool(cfg["qk_norm"]),
        rope_base=float(cfg["rope_theta"]) if cfg["attn_use_rope"] else None,
        sparse_gate=bool(cfg["attn_use_output_gate"]),
        linear_heads=cfg["lightning_nh"],
        linear_head_dim=cfg["lightning_head_dim"],
        linear_rope_base=float(cfg["rope_theta"])
        if cfg["lightning_use_rope"] else None,
        linear_chunk=int(cfg.get("lightning_scan_chunk", 128)),
        linear_decay_depth=tuple((first + i) / (depth - 1)
                                 for i in range(len(kinds))),
        sparse=BlockSparse(
            kernel=sp["kernel_size"], stride=sp["kernel_stride"],
            block=sp["block_size"], top=sp["topk"],
            init_blocks=sp["init_blocks"], window=sp["window_size"],
            dense_len=sp["dense_len"]),
        embed_scale=float(cfg["scale_emb"]),
        residual_scale=cfg["scale_depth"] / depth ** 0.5,
        logit_divisor=cfg["hidden_size"] / cfg["dim_model_base"],
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
