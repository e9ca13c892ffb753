"""Operations and least bytes of the step and the prefill-chunk programs of
the hybrid decoder built as ``gigachat3_5`` (gated delta-rule linear layers
beside gated latent attention; a dense SwiGLU or a share of sigmoid-routed
experts), and of the delta rule's two forms alone, from shapes.

The algorithm's count, in logical bytes: every weight outside the routed
experts once (the untied head's table once: the head reads it whole; the
embedding gathers one row a token), the TOUCHED held experts' weights once
(the expected number of distinct held experts hit when the dispatch's
assignments are spread evenly over the PUBLISHED experts, never more than
are held), the VALID latent columns of the live rows in each full layer
(``kv_lora_rank + qk_rope_head_dim`` numbers a column; a chunk reads each
distinct column once for all its queries), and each live row's float32
matrix state and convolution inputs read and written once a linear layer.
Operations: the products by their parameters; a step's attention in the
absorbed form (the cached form), a chunk's in the cheaper of the absorbed
and the per-head form (``kimi_k2``'s count); the delta rule by its own
equations.  A step's, token by token: ``7 x value heads x value_dim x
key_dim`` a token a layer (the decay, ``S k``, the correction's outer
product and its add, ``S q``).  A chunk's, in the chunked form as its
equations stand, a scan chunk of ``L`` tokens a value head: the triangular
inverse ``2/3 L^3``, its two right-hand sides ``L^2 (P + N)`` (a triangular
factor: half a product), the scores ``K K^T`` and ``Q K^T`` ``2 L^2 N``
each a KEY head, the pseudo-values' weighted sum ``L^2 P``, and the two
products with the carried state and the state's own add, ``2 L N P`` each.
No lane padding, no column outside a row's context, no recomputation, none
of the implementation's float32 passes: a roofline share read against
these cannot pass 100% by construction.

``step`` and ``chunk`` take what the accepted ``hybrid_*_roofline_pct``
readers hand over: the dispatch's tokens, its expert assignments (over the
published experts, held here or not) and its valid columns.
"""
from __future__ import annotations

# the share's arithmetic (experts held of those published, the expected
# distinct held experts a dispatch touches) is the same configuration keys
from .nemotron_h import (BYTES, experts_per_token, held_assignments,  # noqa: F401,E501
                         held_experts, published_experts, touched_experts)

LINEAR, FULL, EXPERTS = "linear_attention", "full_attention", "E"


def _kinds(cfg) -> list:
    full = set(cfg["full_attention_layers"])
    return [FULL if i in full else LINEAR
            for i in range(cfg["num_hidden_layers"])]


def layers(cfg: dict, kind: str) -> int:
    """Layers of ``kind``: a mixer's (``linear_attention``,
    ``full_attention``) or ``"E"``, the layers whose FFN is the experts."""
    if kind == EXPERTS:
        return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return _kinds(cfg).count(kind)


def _delta(cfg):
    G, H = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    N, P = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return G, H, N, P, 2 * G * N + H * P


def linear_parameters(cfg: dict) -> int:
    """``W_qkv``, ``W_z``, ``W_b``, ``W_a``, the convolution's taps,
    ``W_out``; ``A_log``, ``dt_bias`` and the output norm's vector."""
    h = cfg["hidden_size"]
    _G, H, _N, P, conv_dim = _delta(cfg)
    return (h * conv_dim + h * H * P + 2 * h * H
            + conv_dim * cfg["linear_conv_kernel_dim"] + H * P * h
            + 2 * H + P)


def full_parameters(cfg: dict) -> int:
    """``W_qa``, ``W_qb``, ``W_kva``, ``W_uk``, ``W_uv``, the gate a
    feature, ``W_o``; the two latents' norms."""
    h, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return (h * rq + rq + rq * H * (dn + dr) + h * (rkv + dr) + rkv
            + H * rkv * (dn + dv) + 2 * h * H * dv)


def expert_parameters(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_parameters(cfg: dict) -> int:
    E = published_experts(cfg)
    return cfg["hidden_size"] * E + E


def parameters(cfg: dict) -> dict:
    """Parameters held on this chip, by part."""
    h, n = cfg["hidden_size"], cfg["num_hidden_layers"]
    nE = layers(cfg, EXPERTS)
    return {"embedding": cfg["vocab_size"] * h, "head": cfg["vocab_size"] * h,
            "norms": h * (4 * n + 1),
            "linear": layers(cfg, LINEAR) * linear_parameters(cfg),
            "full": layers(cfg, FULL) * full_parameters(cfg),
            "dense_ffn": cfg["first_k_dense_replace"] * 3 * h
            * cfg["intermediate_size"],
            "router": nE * router_parameters(cfg),
            "shared": nE * cfg["n_shared_experts"] * expert_parameters(cfg),
            "experts": nE * held_experts(cfg) * expert_parameters(cfg)}


def params(cfg: dict) -> int:
    return sum(parameters(cfg).values())


def weight_bytes(cfg: dict) -> int:
    """(``router_b``, ``A_log`` and ``dt_bias`` are float32: 1.5 KB over
    this.)"""
    return params(cfg) * BYTES[cfg["dtype"]]


def _weights_read(cfg, tokens, assignments) -> float:
    p = parameters(cfg)
    nE = max(layers(cfg, EXPERTS), 1)
    fixed = sum(v for k, v in p.items() if k not in ("experts", "embedding"))
    touched = layers(cfg, EXPERTS) * touched_experts(cfg, assignments / nE)
    return (fixed + tokens * cfg["hidden_size"]
            + touched * expert_parameters(cfg)) * BYTES[cfg["dtype"]]


def latent_bytes_per_column(cfg: dict) -> int:
    """One token's logical latent row, all the full layers."""
    return layers(cfg, FULL) * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) \
        * BYTES[cfg["dtype"]]


def state_bytes_per_row_layer(cfg: dict) -> int:
    """What ONE linear layer keeps of one row as its matrix state."""
    _G, H, N, P, _c = _delta(cfg)
    return 4 * H * P * N


def conv_bytes_per_row_layer(cfg: dict) -> int:
    """... and as the last inputs of its convolution."""
    return BYTES[cfg["dtype"]] * (cfg["linear_conv_kernel_dim"] - 1) \
        * _delta(cfg)[4]


def linear_state_plane(cfg: dict) -> str:
    """One linear layer's state of every slot, as the compiled program's
    text spells its shape (what ``_sala_scope.plane_copy_ms`` looks for in
    the names of a capture's copies)."""
    _G, H, N, P, _c = _delta(cfg)
    return "f32[%d,%d,%d,%d]" % (cfg["serve"]["slots"], H, P, N)


def update_flops_per_token_layer(cfg: dict) -> int:
    """The delta rule as its equations stand, a token a layer: the decay of
    the state, ``S k``, the correction's outer product and its add, ``S
    q``."""
    _G, H, N, P, _c = _delta(cfg)
    return 7 * H * P * N


def scan_flops_per_token_layer(cfg: dict) -> float:
    """The chunked form as its equations stand (module docstring), a token
    a layer, at the configuration's scan chunk."""
    G, H, N, P, _c = _delta(cfg)
    L = int(cfg.get("linear_scan_chunk", 64))
    per_value_head = (2.0 / 3.0) * L ** 3 + L * L * (P + N) + L * L * P \
        + 3 * 2 * L * N * P
    per_key_head = 2 * 2 * L * L * N
    return (H * per_value_head + G * per_key_head) / L


def linear_update(cfg: dict, rows: float) -> dict:
    """The one-token updates of one step over ``rows`` live rows, all the
    linear layers: each row's state read and written once a layer."""
    nL = layers(cfg, LINEAR)
    return {"bytes": rows * nL * 2 * state_bytes_per_row_layer(cfg),
            "flops": rows * nL * update_flops_per_token_layer(cfg)}


def linear_scan(cfg: dict, tokens: float) -> dict:
    """The scans of one prefill chunk over ``tokens`` valid tokens of one
    row, all the linear layers: the row's state read and written once a
    layer, the chunked form's operations a token."""
    nL = layers(cfg, LINEAR)
    return {"bytes": nL * 2 * state_bytes_per_row_layer(cfg),
            "flops": tokens * nL * scan_flops_per_token_layer(cfg)}


def _product_flops(cfg, tokens, head_rows, assignments) -> float:
    """``tokens`` through every matrix of every layer (the router over its
    published width), ``head_rows`` of them through the head, the held
    experts for their share of ``assignments``."""
    h = cfg["hidden_size"]
    nE = layers(cfg, EXPERTS)
    per_token = layers(cfg, LINEAR) * 2 * linear_parameters(cfg) \
        + layers(cfg, FULL) * 2 * full_parameters(cfg) \
        + cfg["first_k_dense_replace"] * 6 * h * cfg["intermediate_size"] \
        + nE * 2 * (router_parameters(cfg)
                    + cfg["n_shared_experts"] * expert_parameters(cfg))
    return (per_token * tokens + 2.0 * h * cfg["vocab_size"] * head_rows
            + 2.0 * expert_parameters(cfg)
            * held_assignments(cfg, assignments))


def step(cfg: dict, rows: float, assignments: float, columns: float) -> dict:
    """One decode step over ``rows`` live rows whose valid contexts sum to
    ``columns`` (the slot loop's ``kv_columns_valid`` of the steps), with
    ``assignments`` made over all expert layers.  Absorbed form: a (token,
    column) pair costs ``2 H (2 r_kv + d_r)`` a full layer."""
    H, rkv, dr = (cfg["num_attention_heads"], cfg["kv_lora_rank"],
                  cfg["qk_rope_head_dim"])
    nL, upd = layers(cfg, LINEAR), linear_update(cfg, rows)
    return {"bytes": _weights_read(cfg, rows, assignments)
            + columns * latent_bytes_per_column(cfg) + upd["bytes"]
            + rows * nL * 2 * conv_bytes_per_row_layer(cfg),
            "flops": _product_flops(cfg, rows, rows, assignments)
            + upd["flops"]
            + layers(cfg, FULL) * 2.0 * H * (2 * rkv + dr) * columns}


def chunk(cfg: dict, tokens: float, assignments: float, pairs: float) -> dict:
    """One prefill chunk that appends ``tokens`` valid tokens of one row.
    Operations are per (token, column) pair (``chunk_kv_columns_valid``),
    in the cheaper of the absorbed and the per-head form (the keys and
    values expanded once a distinct column); bytes are per DISTINCT column,
    read once for all the chunk's queries: the chunk's context ends at the
    mean context of its tokens + half its tokens.  The head runs for the
    chunk's last token only, and reads the whole table for it."""
    H, rkv, dr = (cfg["num_attention_heads"], cfg["kv_lora_rank"],
                  cfg["qk_rope_head_dim"])
    dn, dv = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    nF, nL = layers(cfg, FULL), layers(cfg, LINEAR)
    end = pairs / max(tokens, 1.0) + tokens / 2.0
    absorbed = 2.0 * H * (2 * rkv + dr) * pairs
    per_head = 2.0 * H * (dn + dr + dv) * pairs \
        + 2.0 * H * rkv * (dn + dv) * max(end - tokens, 0.0)
    scan = linear_scan(cfg, tokens)
    return {"bytes": _weights_read(cfg, tokens, assignments)
            + end * latent_bytes_per_column(cfg) + scan["bytes"]
            + nL * 2 * conv_bytes_per_row_layer(cfg),
            "flops": _product_flops(cfg, tokens, 1.0, assignments)
            + scan["flops"] + nF * min(absorbed, per_head)}
