"""Operations and least bytes of the step and the prefill-chunk programs of
Kimi-K2.5's language model (``kimi_k2``: latent attention over one plane a
layer, no selector), from shapes.

The algorithm's count, in logical bytes: every weight outside the experts
once (the embedding is gathered, not read), the TOUCHED held experts'
weights once (the expected number of distinct experts hit by the dispatch's
held assignments under even routing, never more than are held), and the
VALID latent columns read (``kv_lora_rank + qk_rope_head_dim`` numbers a
column; a chunk reads each distinct column once for all its queries).
Operations: a step's attention in the absorbed form (the cached form); a
chunk's in the cheaper of the absorbed and the per-head form (per-head
keys and values expanded once a distinct column).  A token served out of
the prefix cache passes through no chunk and is counted nowhere.  No lane
padding, no column outside a row's context, no recomputation: a roofline
share read against these cannot pass 100% by construction.

``step`` and ``chunk`` take what ``benchmark/layer_metrics/
latent_step_roofline_pct.py`` hands them: the dispatch's tokens, its held
assignments, and the (token, column) pairs selected and valid summed over
the layers (equal here: every valid column is read).
"""
from __future__ import annotations

from .dots3 import BYTES, expert_parameters, touched_experts


def _dims(cfg):
    return (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["q_lora_rank"],
            cfg["kv_lora_rank"])


def attention_parameters(cfg: dict) -> int:
    h = cfg["hidden_size"]
    H, dn, dr, dv, rq, rkv = _dims(cfg)
    return (h * rq + rq + rq * H * (dn + dr) + h * (rkv + dr) + rkv
            + H * rkv * (dn + dv) + H * dv * h)


def _moe_layers(cfg):
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def parameters(cfg: dict) -> dict:
    """Parameters held on this chip, by part."""
    h, V, n = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    lo, hi = cfg["experts_held"]
    E, moe = cfg["n_routed_experts_published"], _moe_layers(cfg)
    return {"embedding": V * h, "head": V * h + h,
            "attention": n * attention_parameters(cfg), "norms": n * 2 * h,
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
    """One token's logical cache: a latent row a layer."""
    _, _, dr, _, _, rkv = _dims(cfg)
    return cfg["num_hidden_layers"] * (rkv + dr) * BYTES[cfg["dtype"]]


def _weights_read(cfg, held_assignments):
    p = parameters(cfg)
    moe = max(_moe_layers(cfg), 1)
    fixed = sum(v for k, v in p.items() if k not in ("experts", "embedding"))
    touched = _moe_layers(cfg) * touched_experts(cfg, held_assignments / moe)
    return (fixed + touched * expert_parameters(cfg)) * BYTES[cfg["dtype"]]


def _dense_flops(cfg, tokens, held_assignments, heads_for):
    """The products outside the attention's scores: per token every matrix
    of every layer (the router over its published width), the held
    experts for their assignments, the head for ``heads_for`` tokens."""
    h = cfg["hidden_size"]
    per_token = cfg["num_hidden_layers"] * 2 * attention_parameters(cfg) \
        + cfg["first_k_dense_replace"] * 6 * h * cfg["intermediate_size"] \
        + _moe_layers(cfg) * (
            2 * cfg["n_shared_experts"] * expert_parameters(cfg)
            + 2 * h * cfg["n_routed_experts_published"])
    return (per_token * tokens + 2.0 * expert_parameters(cfg) * held_assignments
            + 2.0 * h * cfg["vocab_size"] * heads_for)


def step(cfg: dict, rows: float, held_assignments: float, selected: float,
         valid: float) -> dict:
    """One decode step over ``rows`` live rows; ``valid`` = the rows'
    contexts summed over the layers (``attn_columns_valid``).  Absorbed
    form: a (token, column) pair costs ``2 H (2 r_kv + d_r)``."""
    H, _, dr, _, _, rkv = _dims(cfg)
    return {"bytes": _weights_read(cfg, held_assignments)
            + valid * (rkv + dr) * BYTES[cfg["dtype"]],
            "flops": _dense_flops(cfg, rows, held_assignments, rows)
            + 2.0 * H * (2 * rkv + dr) * valid}


def chunk(cfg: dict, tokens: float, held_assignments: float, selected: float,
          valid: float) -> dict:
    """One prefill chunk that appends ``tokens`` valid tokens of one row.
    With the chunk's context ending at ``end`` (the mean context of its
    tokens + half its tokens) a layer reads ``end`` distinct latent rows.
    Operations: the cheaper of the absorbed form (``2 H (2 r_kv + d_r)`` a
    pair) and the per-head form (``2 H (d_n + d_r + d_v)`` a pair, the
    keys and values expanded once a distinct column, ``2 H r_kv (d_n +
    d_v)``, in place of the per-token absorption of the same size).  The
    head runs for the chunk's last token only."""
    H, dn, dr, dv, _, rkv = _dims(cfg)
    n = cfg["num_hidden_layers"]
    end = valid / n / max(tokens, 1.0) + tokens / 2.0
    absorbed = 2.0 * H * (2 * rkv + dr) * valid
    per_head = 2.0 * H * (dn + dr + dv) * valid \
        + 2.0 * H * rkv * (dn + dv) * n * (end - tokens)
    return {"bytes": _weights_read(cfg, held_assignments)
            + n * end * (rkv + dr) * BYTES[cfg["dtype"]],
            "flops": _dense_flops(cfg, tokens, held_assignments, 1.0)
            + min(absorbed, per_head)}
