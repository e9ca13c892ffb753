"""The program's GPT, built through its public constructors and given the
benchmark's weights.

The canonical weight tree is ``benchmark/reference/gpt.py``'s; this file is
the one place that knows the program's parameter names.
"""
from __future__ import annotations

from . import common

TOP = {
    "wte": "wte.weight",
    "wpe": "wpe.weight",
    "lnf_g": "encoder.norm.weight",
    "lnf_b": "encoder.norm.bias",
}
LAYER = {
    "q_w": "self_attn.q_proj.weight", "q_b": "self_attn.q_proj.bias",
    "k_w": "self_attn.k_proj.weight", "k_b": "self_attn.k_proj.bias",
    "v_w": "self_attn.v_proj.weight", "v_b": "self_attn.v_proj.bias",
    "o_w": "self_attn.out_proj.weight", "o_b": "self_attn.out_proj.bias",
    "ln1_g": "norm1.weight", "ln1_b": "norm1.bias",
    "f1_w": "linear1.weight", "f1_b": "linear1.bias",
    "f2_w": "linear2.weight", "f2_b": "linear2.bias",
    "ln2_g": "norm2.weight", "ln2_b": "norm2.bias",
}
LAYER_PREFIX = "encoder.layers.{i}."


def leaf_ids(cfg: dict) -> dict:
    """{program parameter name: canonical leaf id}."""
    return common.leaf_ids(TOP, LAYER, LAYER_PREFIX, cfg["n_layer"])


def to_program(weights: dict) -> dict:
    """{program parameter name: array} from the canonical tree."""
    n_layers = next(iter(weights["layers"].values())).shape[0]
    return common.to_program(weights, leaf_ids({"n_layer": n_layers}))


def program_config(cfg: dict):
    from paddle_tpu.text.models.gpt import GPTConfig
    return GPTConfig(vocab_size=cfg["vocab_size"], hidden_size=cfg["n_embd"],
                     num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
                     intermediate_size=cfg["n_inner"],
                     max_position_embeddings=cfg["n_positions"],
                     dropout=cfg["resid_pdrop"])


def build_unweighted(cfg: dict):
    """GPTModel(cfg) in eval mode, its weights cast to the served dtype by
    amp O2 as chip_smoke.py's ``_gpt`` does (float32, the tests' dtype, is
    the constructor's own); the weights are the constructor's."""
    import paddle_tpu as paddle
    from paddle_tpu.text.models.gpt import GPTModel
    model = GPTModel(program_config(cfg))
    model.eval()
    if cfg["dtype"] != "float32":
        paddle.amp.decorate(models=model, level="O2", dtype=cfg["dtype"])
    return model


def build(cfg: dict, mapped: dict):
    model = build_unweighted(cfg)
    common.install(model, mapped)
    return model
