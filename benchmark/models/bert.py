"""The program's BERT, built through its public constructors and given the
benchmark's weights.

The canonical weight tree is ``benchmark/reference/bert.py``'s; this file
is the one place that knows the program's parameter names.
"""
from __future__ import annotations

from . import common

# canonical leaf -> the program's parameter name ({i} = encoder layer)
TOP = {
    "word": "bert.embeddings.word_embeddings.weight",
    "pos": "bert.embeddings.position_embeddings.weight",
    "type": "bert.embeddings.token_type_embeddings.weight",
    "emb_ln_g": "bert.embeddings.layer_norm.weight",
    "emb_ln_b": "bert.embeddings.layer_norm.bias",
    "pool_w": "bert.pooler.dense.weight",
    "pool_b": "bert.pooler.dense.bias",
    "head_w": "cls.transform.weight",
    "head_b": "cls.transform.bias",
    "head_ln_g": "cls.layer_norm.weight",
    "head_ln_b": "cls.layer_norm.bias",
    "dec_b": "cls.decoder_bias",
    "nsp_w": "cls.seq_relationship.weight",
    "nsp_b": "cls.seq_relationship.bias",
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
LAYER_PREFIX = "bert.encoder.layers.{i}."


def leaf_ids(cfg: dict) -> dict:
    """{program parameter name: canonical leaf id}."""
    return common.leaf_ids(TOP, LAYER, LAYER_PREFIX, cfg["num_hidden_layers"])


def to_program(weights: dict) -> dict:
    """{program parameter name: array} from the canonical tree."""
    n_layers = next(iter(weights["layers"].values())).shape[0]
    return common.to_program(weights, leaf_ids({"num_hidden_layers": n_layers}))


def feed(batch):
    """The program's pretraining feed from one batch of the traffic, as
    chip_smoke.py feeds it: (ids, token types, attention mask, MLM labels,
    NSP label, masked positions)."""
    ids, positions, labels = batch
    return (ids, None, None, labels, None, positions)


def program_config(cfg: dict):
    from paddle_tpu.text.models.bert import BertConfig
    return BertConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        hidden_act=cfg["hidden_act"],
        hidden_dropout_prob=cfg["hidden_dropout_prob"],
        attention_probs_dropout_prob=cfg["attention_probs_dropout_prob"],
        max_position_embeddings=cfg["max_position_embeddings"],
        type_vocab_size=cfg["type_vocab_size"],
        initializer_range=cfg["initializer_range"])


def build(cfg: dict, mapped: dict):
    """BertForPretraining(cfg) carrying the benchmark's weights."""
    from paddle_tpu.text.models.bert import BertForPretraining
    model = BertForPretraining(program_config(cfg))
    common.install(model, mapped)
    return model
