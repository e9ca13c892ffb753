"""Operations and least bytes of the step and the prefill-chunk programs of
the short-convolution / grouped-query MoE decoder (``lfm2_moe``), from
shapes.

The algorithm's count, in logical bytes: every weight outside the experts
once (the tied table once: the head reads it whole, the embedding gathers
rows of it), the TOUCHED experts' weights once (the expected number of
distinct experts hit by the dispatch's assignments under even routing,
never more than there are), the VALID key/value columns of the live rows
in each attention layer, and the conv layers' states read and written.  No
lane padding, no column outside a row's context, no recomputation: a
roofline share read against these cannot pass 100% by construction.
"""
from __future__ import annotations

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
CONV = "conv"


def _kinds(cfg):
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def head_dim(cfg) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // \
        cfg["num_attention_heads"]


def mixer_parameters(cfg: dict, kind: str) -> int:
    h = cfg["hidden_size"]
    if kind == CONV:
        return h * 3 * h + h * cfg["conv_L_cache"] + h * h
    H, KV, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        head_dim(cfg)
    return h * H * d + 2 * h * KV * d + H * d * h + 2 * d


def expert_parameters(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def parameters(cfg: dict) -> dict:
    """Parameters held on this chip, by part."""
    h, E = cfg["hidden_size"], cfg["num_experts"]
    out = {"embedding": cfg["vocab_size"] * h, "mixers": 0, "norms": h,
           "dense_ffn": 0, "experts": 0, "router": 0}
    for i, kind in enumerate(_kinds(cfg)):
        out["mixers"] += mixer_parameters(cfg, kind)
        out["norms"] += 2 * h
        if i < cfg["num_dense_layers"]:
            out["dense_ffn"] += 3 * h * cfg["intermediate_size"]
        else:
            out["experts"] += E * expert_parameters(cfg)
            out["router"] += h * E + E
    return out


def weight_bytes(cfg: dict) -> int:
    return sum(parameters(cfg).values()) * BYTES[cfg["dtype"]]


def _moe_layers(cfg) -> int:
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def _attn_layers(cfg) -> int:
    return sum(1 for k in _kinds(cfg) if k != CONV)


def touched_experts(cfg: dict, assignments_per_layer: float) -> float:
    """Expected distinct experts hit by that many assignments spread evenly
    over the layer's experts."""
    n = cfg["num_experts"]
    return n * (1.0 - (1.0 - 1.0 / n) ** max(assignments_per_layer, 0.0))


def _weights_read(cfg, assignments) -> float:
    p = parameters(cfg)
    moe = max(_moe_layers(cfg), 1)
    fixed = sum(v for k, v in p.items() if k != "experts")
    touched = _moe_layers(cfg) * touched_experts(cfg, assignments / moe)
    return (fixed + touched * expert_parameters(cfg)) * BYTES[cfg["dtype"]]


def kv_bytes_per_column(cfg: dict) -> int:
    """Keys and values of one token position of one row, all the attention
    layers held here."""
    return 2 * _attn_layers(cfg) * cfg["num_key_value_heads"] \
        * head_dim(cfg) * BYTES[cfg["dtype"]]


def state_bytes_per_row(cfg: dict) -> int:
    """The conv layers' states of one row, read and written."""
    conv = len(_kinds(cfg)) - _attn_layers(cfg)
    return 2 * conv * (cfg["conv_L_cache"] - 1) * cfg["hidden_size"] \
        * BYTES[cfg["dtype"]]


def _flops(cfg, tokens, head_rows, assignments, pairs) -> float:
    """``tokens`` through every layer, ``head_rows`` of them through the
    head, ``assignments`` expert rows, ``pairs`` (query, column) pairs an
    attention layer."""
    h = cfg["hidden_size"]
    per_token = 0
    for i, kind in enumerate(_kinds(cfg)):
        per_token += 2 * mixer_parameters(cfg, kind)
        per_token += 6 * h * cfg["intermediate_size"] \
            if i < cfg["num_dense_layers"] else 2 * h * cfg["num_experts"]
    attn = 4.0 * cfg["num_attention_heads"] * head_dim(cfg) * _attn_layers(cfg)
    return (per_token * tokens + 2.0 * h * cfg["vocab_size"] * head_rows
            + 2.0 * expert_parameters(cfg) * assignments + attn * pairs)


def step(cfg: dict, rows: float, assignments: float, columns: float) -> dict:
    """One decode step over ``rows`` live rows whose valid contexts sum to
    ``columns`` (the slot loop's ``kv_columns_valid`` of the steps), with
    ``assignments`` expert rows over all MoE layers."""
    return {"bytes": _weights_read(cfg, assignments)
            + columns * kv_bytes_per_column(cfg)
            + rows * state_bytes_per_row(cfg),
            "flops": _flops(cfg, rows, rows, assignments, columns)}


def chunk(cfg: dict, tokens: float, assignments: float, pairs: float) -> dict:
    """One prefill chunk that appends ``tokens`` valid tokens of one row.
    Operations are per (token, column) pair (``chunk_kv_columns_valid``);
    bytes are per DISTINCT column, read once for all the chunk's queries:
    the chunk's context ends at the mean context of its tokens + half its
    tokens.  The head runs for the chunk's last token only, and reads the
    whole table for it."""
    end = pairs / max(tokens, 1.0) + tokens / 2.0
    return {"bytes": _weights_read(cfg, assignments)
            + end * kv_bytes_per_column(cfg) + state_bytes_per_row(cfg),
            "flops": _flops(cfg, tokens, 1.0, assignments, pairs)}
