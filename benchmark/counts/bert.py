"""Operations and least bytes of one BERT pretraining step, from shapes.

The algorithm's count, not the compiler's: recomputation, the one-hot
gather and padding are not counted, so a roofline share read against these
cannot pass 100% by construction.
"""
from __future__ import annotations


def encoder_matmul_params(cfg: dict) -> int:
    """Parameters of the encoder's matrix products (no embeddings, biases
    or LayerNorms): 4 h^2 + 2 h f per layer."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (4 * h * h + 2 * h * f)


def parameters(cfg: dict) -> int:
    h, f, L = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_hidden_layers"]
    V = cfg["vocab_size"]
    emb = (V + cfg["max_position_embeddings"] + cfg["type_vocab_size"]) * h + 2 * h
    layer = 4 * (h * h + h) + (h * f + f) + (f * h + h) + 4 * h
    head = (h * h + h) + 2 * h + V + (h * h + h) + (2 * h + 2)
    return emb + L * layer + head


def train_flops_per_step(cfg: dict) -> float:
    """Forward + backward of one step: 6 x (encoder matmul parameters) per
    token, 12 L s h per token for the attention scores and their use, and
    the MLM head (transform h^2, tied decoder h V) on the masked positions
    only."""
    tr = cfg["train"]
    B, s, P = tr["batch"], tr["seq"], tr["masked_per_seq"]
    h, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    per_token = 6 * encoder_matmul_params(cfg) + 12 * L * s * h
    per_masked = 6 * (h * h + h * V)
    return float(B * (s * per_token + P * per_masked))


def train_tokens_per_step(cfg: dict) -> int:
    return cfg["train"]["batch"] * cfg["train"]["seq"]


def train_min_bytes_per_step(cfg: dict) -> float:
    """The least a step must move: float32 master weights read and written
    (8 B), gradients written and read (8 B), Adam's two moments read and
    written (16 B) — 32 B a parameter less the 4 B of a gradient that a
    fused update need not write: 28 B a parameter.  Activations are not
    counted (a lower bound)."""
    return 28.0 * parameters(cfg)
