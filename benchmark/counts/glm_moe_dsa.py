"""Operations and least bytes of the step and the prefill-chunk programs of
GLM-5's language model (``glm_moe_dsa``: latent attention over one latent
plane and one selector-key plane a layer, a learned column selector on
every layer), from shapes.

The algorithm's count, in logical bytes, by the rule ``counts/dots3.py``
states: every weight outside the experts once (the embedding is gathered,
not read), the TOUCHED held experts' weights once (the expected number of
distinct experts hit by the dispatch's held assignments under even routing,
never more than are held), the latent rows of the SELECTED columns
(``min(context, index_topk)``; a chunk reads each distinct column once for
all its queries) and the selector key of every VALID column.  Operations:
the selector's scores over every valid (token, column) pair; the attention
over the SELECTED pairs only, a step's in the absorbed form (the cached
form), a chunk's in the cheaper of the absorbed and the per-head form
(per-head keys and values expanded once a distinct selected column).  A
token served out of the prefix cache passes through no chunk and is counted
nowhere.  No lane padding, no column outside a row's context or outside the
selection, no recomputation: a roofline share read against these cannot
pass 100% by construction, and reads low while the layer masks the
unselected columns and reads every valid one.

``step`` and ``chunk`` take what ``benchmark/layer_metrics/
latent_step_roofline_pct.py`` hands them: the dispatch's tokens, its held
assignments, and the (token, column) pairs selected and valid summed over
the layers.
"""
from __future__ import annotations

from .dots3 import BYTES, expert_parameters, touched_experts


def _dims(cfg):
    return (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["q_lora_rank"],
            cfg["kv_lora_rank"])


def attention_parameters(cfg: dict) -> int:
    """One layer's latent attention without its selector."""
    h = cfg["hidden_size"]
    H, dn, dr, dv, rq, rkv = _dims(cfg)
    return (h * rq + rq + rq * H * (dn + dr) + h * (rkv + dr) + rkv
            + H * rkv * (dn + dv) + H * dv * h)


def selector_parameters(cfg: dict) -> int:
    """One layer's selector: its queries from the query latent, its key
    with the LayerNorm's gain and bias, its head weights."""
    h, rq = cfg["hidden_size"], cfg["q_lora_rank"]
    J, D = cfg["index_n_heads"], cfg["index_head_dim"]
    return rq * J * D + h * D + 2 * D + h * J


def _moe_layers(cfg):
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def parameters(cfg: dict) -> dict:
    """Parameters held on this chip, by part."""
    h, V, n = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    lo, hi = cfg["experts_held"]
    E, moe = cfg["n_routed_experts_published"], _moe_layers(cfg)
    return {"embedding": V * h, "head": V * h + h,
            "attention": n * attention_parameters(cfg),
            "selector": n * selector_parameters(cfg), "norms": n * 2 * h,
            "dense_ffn": cfg["first_k_dense_replace"] * 3 * h
            * cfg["intermediate_size"],
            "experts": moe * (hi - lo) * expert_parameters(cfg),
            "shared": moe * cfg["n_shared_experts"] * expert_parameters(cfg),
            "router": moe * (h * E + E)}


def params(cfg: dict) -> int:
    return sum(parameters(cfg).values())


def weight_bytes(cfg: dict) -> int:
    return params(cfg) * BYTES[cfg["dtype"]]


def cache_bytes_per_token(cfg: dict) -> int:
    """One token's logical cache: a latent row and a selector key a layer."""
    _, _, dr, _, _, rkv = _dims(cfg)
    return cfg["num_hidden_layers"] * (rkv + dr + cfg["index_head_dim"]) \
        * BYTES[cfg["dtype"]]


def _weights_read(cfg, held_assignments):
    p = parameters(cfg)
    moe = max(_moe_layers(cfg), 1)
    fixed = sum(v for k, v in p.items() if k not in ("experts", "embedding"))
    touched = _moe_layers(cfg) * touched_experts(cfg, held_assignments / moe)
    return (fixed + touched * expert_parameters(cfg)) * BYTES[cfg["dtype"]]


def _dense_flops(cfg, tokens, held_assignments, heads_for, valid):
    """The products outside the attention's scores: per token every matrix
    of every layer (the selector's too; the router over its published
    width), the held experts for their assignments, the head for
    ``heads_for`` tokens; and the selector's score of every valid (token,
    column) pair, ``J`` dot products of ``D`` and their weighted sum."""
    h = cfg["hidden_size"]
    J, D = cfg["index_n_heads"], cfg["index_head_dim"]
    per_token = cfg["num_hidden_layers"] * 2 * (
        attention_parameters(cfg) + selector_parameters(cfg)) \
        + cfg["first_k_dense_replace"] * 6 * h * cfg["intermediate_size"] \
        + _moe_layers(cfg) * (
            2 * cfg["n_shared_experts"] * expert_parameters(cfg)
            + 2 * h * cfg["n_routed_experts_published"])
    return (per_token * tokens + 2.0 * expert_parameters(cfg) * held_assignments
            + 2.0 * h * cfg["vocab_size"] * heads_for
            + (2.0 * J * D + 2 * J) * valid)


def _cache_bytes(cfg, latent_rows, keys):
    _, _, dr, _, _, rkv = _dims(cfg)
    return BYTES[cfg["dtype"]] * (latent_rows * (rkv + dr)
                                  + keys * cfg["index_head_dim"])


def step(cfg: dict, rows: float, held_assignments: float, selected: float,
         valid: float) -> dict:
    """One decode step over ``rows`` live rows; ``selected`` / ``valid`` =
    the rows' selected and causal columns summed over the layers
    (``attn_columns_*``).  Absorbed form over the selected pairs: ``2 H (2
    r_kv + d_r)`` a pair."""
    H, _, dr, _, _, rkv = _dims(cfg)
    return {"bytes": _weights_read(cfg, held_assignments)
            + _cache_bytes(cfg, selected, valid),
            "flops": _dense_flops(cfg, rows, held_assignments, rows, valid)
            + 2.0 * H * (2 * rkv + dr) * selected}


def chunk(cfg: dict, tokens: float, held_assignments: float, selected: float,
          valid: float) -> dict:
    """One prefill chunk that appends ``tokens`` valid tokens of one row.
    With the chunk's context ending at ``end`` (the mean context of its
    tokens + half its tokens) a layer reads ``end`` selector keys and at
    least ``min(end, index_topk)`` distinct latent rows (what its last
    query selects).  Operations of the attention: the cheaper of the
    absorbed form (``2 H (2 r_kv + d_r)`` a selected pair) and the per-head
    form (``2 H (d_n + d_r + d_v)`` a selected pair, and the keys and
    values of the distinct selected columns before the chunk's own
    expanded once, ``2 H r_kv (d_n + d_v)`` a column, in place of the
    per-token absorption of the same size).  The head runs for the chunk's
    last token only."""
    H, dn, dr, dv, _, rkv = _dims(cfg)
    n = cfg["num_hidden_layers"]
    end = valid / n / max(tokens, 1.0) + tokens / 2.0
    rows = min(end, cfg["index_topk"])
    absorbed = 2.0 * H * (2 * rkv + dr) * selected
    per_head = 2.0 * H * (dn + dr + dv) * selected \
        + 2.0 * H * rkv * (dn + dv) * n * max(rows - tokens, 0.0)
    return {"bytes": _weights_read(cfg, held_assignments)
            + _cache_bytes(cfg, n * rows, n * end),
            "flops": _dense_flops(cfg, tokens, held_assignments, 1.0, valid)
            + min(absorbed, per_head)}
