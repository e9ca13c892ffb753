"""Operations and least bytes of one decode step of a GPT-2 style decoder,
from shapes.  The algorithm's count: the bf16 weights once and the VALID
key/value columns of the live rows, logical bytes — not every column of
every plane, not lane padding — so a roofline share read against these
cannot pass 100% by construction."""
from __future__ import annotations

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def parameters(cfg: dict) -> int:
    h, f, L = cfg["n_embd"], cfg["n_inner"], cfg["n_layer"]
    emb = (cfg["vocab_size"] + cfg["n_positions"]) * h
    layer = 4 * (h * h + h) + (h * f + f) + (f * h + h) + 4 * h
    return emb + L * layer + 2 * h


def weight_bytes(cfg: dict) -> int:
    return parameters(cfg) * BYTES[cfg["dtype"]]


def kv_bytes_per_column(cfg: dict) -> int:
    """Keys and values of one token position of one row, all layers."""
    return 2 * cfg["n_layer"] * cfg["n_embd"] * BYTES[cfg["dtype"]]


def decode_step_min_bytes(cfg: dict, valid_columns: float) -> float:
    """One step: every weight once (the position table is not read whole,
    but is 0.1% of the model) and ``valid_columns`` key/value columns —
    the sum over live rows of their valid context."""
    return weight_bytes(cfg) + valid_columns * kv_bytes_per_column(cfg)


def decode_step_flops(cfg: dict, rows: float, valid_columns: float) -> float:
    """2 x matmul parameters per generated token (the tied head included)
    + 4 h per valid column for the attention scores and their use."""
    h, f, L = cfg["n_embd"], cfg["n_inner"], cfg["n_layer"]
    matmul = L * (4 * h * h + 2 * h * f) + cfg["vocab_size"] * h
    return 2.0 * matmul * rows + 4.0 * h * L * valid_columns
