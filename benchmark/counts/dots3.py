"""Operations and least bytes of the step and the prefill-chunk programs of
the latent-attention MoE decoder (``dots3_note``), from shapes.

The algorithm's count, in logical bytes: every weight outside the experts
once, the TOUCHED held experts' weights once (the expected number of
distinct experts hit by the dispatch's held assignments under even routing,
never more than are held), the latent rows of the SELECTED columns of the
full layers (``min(context, index_topk)``), the selector key of every valid
column, and the window layers' columns inside the window.  Operations are
those of the absorbed form over the selected columns only.  No lane
padding, no column outside a row's context, no recomputation: a roofline
share read against these cannot pass 100% by construction.
"""
from __future__ import annotations

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
FULL = "full_attention"


def _kinds(cfg):
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def _dims(cfg, kind):
    p = "" if kind == FULL else "swa_"
    return (cfg[p + "num_attention_heads"], cfg[p + "qk_nope_head_dim"],
            cfg[p + "qk_rope_head_dim"], cfg[p + "v_head_dim"],
            cfg[p + "q_lora_rank"], cfg[p + "kv_lora_rank"])


def attention_parameters(cfg: dict, kind: str) -> int:
    h = cfg["hidden_size"]
    H, dn, dr, dv, rq, rkv = _dims(cfg, kind)
    n = (h * rq + rq + rq * H * (dn + dr) + h * (rkv + dr) + rkv
         + H * rkv * (dn + dv) + h * H + H * dv * h)
    if kind == FULL:
        J, D = cfg["index_n_heads"], cfg["index_head_dim"]
        n += rq * J * D + h * D + 2 * D + h * J
    return n


def expert_parameters(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def parameters(cfg: dict) -> dict:
    """Parameters held on this chip, by part."""
    h, V = cfg["hidden_size"], cfg["vocab_size"]
    lo, hi = cfg["experts_held"]
    out = {"embedding": V * h, "head": V * h + h, "attention": 0,
           "norms": 0, "dense_ffn": 0, "experts": 0, "shared": 0,
           "router": 0}
    for i, kind in enumerate(_kinds(cfg)):
        out["attention"] += attention_parameters(cfg, kind)
        out["norms"] += 2 * h
        if i < cfg["first_k_dense_replace"]:
            out["dense_ffn"] += 3 * h * cfg["intermediate_size"]
        else:
            out["experts"] += (hi - lo) * expert_parameters(cfg)
            out["shared"] += cfg["n_shared_experts"] * expert_parameters(cfg)
            out["router"] += h * cfg["n_routed_experts_published"] \
                + cfg["n_routed_experts_published"]
    return out


def weight_bytes(cfg: dict) -> int:
    return sum(parameters(cfg).values()) * BYTES[cfg["dtype"]]


def _moe_layers(cfg):
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def touched_experts(cfg: dict, held_assignments_per_layer: float) -> float:
    """Expected distinct held experts hit by that many assignments spread
    evenly over the held experts."""
    n = cfg["experts_held"][1] - cfg["experts_held"][0]
    return n * (1.0 - (1.0 - 1.0 / n) ** max(held_assignments_per_layer, 0.0))


def _weights_read(cfg, held_assignments):
    """Bytes of weights one dispatch must read: all but the experts, and
    the touched experts; the embedding is gathered, not read whole."""
    p = parameters(cfg)
    moe = max(_moe_layers(cfg), 1)
    fixed = sum(v for k, v in p.items() if k not in ("experts", "embedding"))
    touched = _moe_layers(cfg) * touched_experts(cfg, held_assignments / moe)
    return (fixed + touched * expert_parameters(cfg)) * BYTES[cfg["dtype"]]


def _cache_bytes(cfg, selected, valid, window_cols):
    """``selected`` / ``valid``: selected and valid (token, column) pairs
    summed over the FULL layers; ``window_cols``: pairs inside the window
    summed over the window layers."""
    b = BYTES[cfg["dtype"]]
    _, _, dr, _, _, rkv = _dims(cfg, FULL)
    _, _, wdr, _, _, wrkv = _dims(cfg, "sliding_attention")
    return b * (selected * (rkv + dr) + valid * cfg["index_head_dim"]
                + window_cols * (wrkv + wdr))


def _flops(cfg, tokens, held_assignments, selected, valid, window_cols):
    h = cfg["hidden_size"]
    per_token = 2 * h * cfg["vocab_size"]             # the head, per logit row
    kinds = _kinds(cfg)
    for i, kind in enumerate(kinds):
        per_token += 2 * attention_parameters(cfg, kind)
        if i < cfg["first_k_dense_replace"]:
            per_token += 6 * h * cfg["intermediate_size"]
        else:
            per_token += 2 * cfg["n_shared_experts"] * expert_parameters(cfg) \
                + 2 * h * cfg["n_routed_experts_published"]
    H, _, dr, _, _, rkv = _dims(cfg, FULL)
    wH, _, wdr, _, _, wrkv = _dims(cfg, "sliding_attention")
    J, D = cfg["index_n_heads"], cfg["index_head_dim"]
    return (per_token * tokens
            + 2.0 * expert_parameters(cfg) * held_assignments
            + 2.0 * H * (2 * rkv + dr) * selected
            + (2.0 * J * D + 2 * J) * valid
            + 2.0 * wH * (2 * wrkv + wdr) * window_cols)


def _window_cols(cfg, contexts_sum, tokens):
    """(token, column) pairs inside the window, summed over the window
    layers, for ``tokens`` tokens whose contexts sum to ``contexts_sum``
    (each context at least the window in these cells; the smaller of the
    two otherwise)."""
    n = sum(1 for k in _kinds(cfg) if k != FULL)
    return n * min(contexts_sum, tokens * cfg["sliding_window_size"])


def step(cfg: dict, rows: float, held_assignments: float, selected: float,
         valid: float) -> dict:
    """One decode step over ``rows`` live rows: least bytes and operations.
    ``selected`` / ``valid`` are summed over the full layers (the slot
    loop's ``attn_columns_*`` of the steps)."""
    full = max(sum(1 for k in _kinds(cfg) if k == FULL), 1)
    win = _window_cols(cfg, valid / full, rows)
    return {"bytes": _weights_read(cfg, held_assignments)
            + _cache_bytes(cfg, selected, valid, win),
            # the head runs once a row in a step
            "flops": _flops(cfg, rows, held_assignments, selected, valid, win)}


def chunk(cfg: dict, tokens: float, held_assignments: float, selected: float,
          valid: float) -> dict:
    """One prefill chunk that appends ``tokens`` valid tokens of one row.
    Operations are per (token, column) pair; bytes are per DISTINCT column,
    read once for all the chunk's queries: with the chunk's context ending
    at ``end`` (the mean context of its tokens + half its tokens), a full
    layer needs at least ``min(end, index_topk)`` latent rows and ``end``
    selector keys, a window layer ``min(end, window + tokens - 1)`` rows.
    The head runs for the chunk's last token only."""
    kinds = _kinds(cfg)
    full = max(sum(1 for k in kinds if k == FULL), 1)
    end = valid / full / max(tokens, 1.0) + tokens / 2.0
    win_pairs = _window_cols(cfg, valid / full, tokens)
    win_cols = (len(kinds) - full) * min(
        end, cfg["sliding_window_size"] + tokens - 1)
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    return {"bytes": _weights_read(cfg, held_assignments)
            + _cache_bytes(cfg, full * min(end, cfg["index_topk"]),
                           full * end, win_cols),
            "flops": _flops(cfg, tokens, held_assignments, selected, valid,
                            win_pairs) - head * (tokens - 1)}
