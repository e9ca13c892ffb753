"""Operations and least bytes of the step and the prefill-chunk programs of
the hybrid decoder built as ``nemotron_h`` (Mamba-2 state-space layers,
relu^2 experts of which this chip holds a share, grouped-query attention),
and of the state-space layers' two forms alone, from shapes.

The algorithm's count, in logical bytes: every weight outside the routed
experts once (the untied head's table once: the head reads it whole; the
embedding gathers one row a token), the TOUCHED held experts' weights once
(the expected number of distinct held experts hit when the dispatch's
assignments are spread evenly over the PUBLISHED experts, never more than
are held), the VALID key/value columns of the live rows in each attention
layer, and each live row's state-space state and convolution inputs read
and written once a layer.  Operations: the products by their parameters,
the attention by its (query, column) pairs, the recurrence by its own
equations token by token (``5 x heads x head_dim x state`` a token a
layer: the decay, the outer product and its add, the read through ``C``);
the chunked form's extra products are the implementation's, not the
mathematics'.  No lane padding, no column outside a row's context, no
recomputation: a roofline share read against these cannot pass 100% by
construction.
"""
from __future__ import annotations

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


def _kinds(cfg) -> str:
    return cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]


def layers(cfg: dict, kind: str) -> int:
    return _kinds(cfg).count(kind)


def _ssm(cfg):
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N = cfg["n_groups"], cfg["ssm_state_size"]
    return H, P, G, N, H * P, H * P + 2 * G * N


def published_experts(cfg: dict) -> int:
    return cfg.get("n_routed_experts_published", cfg["n_routed_experts"])


def held_experts(cfg: dict) -> int:
    lo, hi = cfg.get("experts_held") or (0, cfg["n_routed_experts"])
    return hi - lo


def experts_per_token(cfg: dict) -> int:
    return cfg["num_experts_per_tok"]


def mamba_parameters(cfg: dict) -> int:
    h = cfg["hidden_size"]
    H, _P, _G, _N, inner, conv_dim = _ssm(cfg)
    return (h * (inner + conv_dim + H)            # in_proj: z | xBC | dt
            + conv_dim * cfg["conv_kernel"] + conv_dim        # taps, bias
            + 3 * H + inner                       # dt_bias, A_log, D; norm
            + inner * h)                                      # out_proj


def attention_parameters(cfg: dict) -> int:
    h, d = cfg["hidden_size"], cfg["head_dim"]
    return h * d * (2 * cfg["num_attention_heads"]
                    + 2 * cfg["num_key_value_heads"])


def expert_parameters(cfg: dict) -> int:
    return 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_parameters(cfg: dict) -> int:
    return 2 * cfg["hidden_size"] * cfg["n_shared_experts"] \
        * cfg["moe_shared_expert_intermediate_size"]


def router_parameters(cfg: dict) -> int:
    E = published_experts(cfg)
    return cfg["hidden_size"] * E + E


def parameters(cfg: dict) -> dict:
    """Parameters held on this chip, by part."""
    h = cfg["hidden_size"]
    nM, nE, nA = (layers(cfg, k) for k in (MAMBA, EXPERTS, ATTENTION))
    return {"embedding": cfg["vocab_size"] * h, "head": cfg["vocab_size"] * h,
            "norms": h * (nM + nE + nA + 1),
            "mamba": nM * mamba_parameters(cfg),
            "attention": nA * attention_parameters(cfg),
            "router": nE * router_parameters(cfg),
            "shared": nE * shared_parameters(cfg),
            "experts": nE * held_experts(cfg) * expert_parameters(cfg)}


def weight_bytes(cfg: dict) -> int:
    return sum(parameters(cfg).values()) * BYTES[cfg["dtype"]]


def touched_experts(cfg: dict, assignments_per_layer: float) -> float:
    """Expected distinct HELD experts hit by that many assignments spread
    evenly over the layer's published experts."""
    E = published_experts(cfg)
    return held_experts(cfg) * (
        1.0 - (1.0 - 1.0 / E) ** max(assignments_per_layer, 0.0))


def held_assignments(cfg: dict, assignments: float) -> float:
    """Of ``assignments`` made over the published experts, those that an
    even router sends to the experts held here."""
    return assignments * held_experts(cfg) / published_experts(cfg)


def _weights_read(cfg, tokens, assignments) -> float:
    p = parameters(cfg)
    nE = max(layers(cfg, EXPERTS), 1)
    fixed = sum(v for k, v in p.items() if k not in ("experts", "embedding"))
    touched = layers(cfg, EXPERTS) * touched_experts(cfg, assignments / nE)
    return (fixed + tokens * cfg["hidden_size"]
            + touched * expert_parameters(cfg)) * BYTES[cfg["dtype"]]


def kv_bytes_per_column(cfg: dict) -> int:
    """Keys and values of one token position of one row, all the attention
    layers."""
    return 2 * layers(cfg, ATTENTION) * cfg["num_key_value_heads"] \
        * cfg["head_dim"] * BYTES[cfg["dtype"]]


def state_bytes_per_row_layer(cfg: dict) -> int:
    """What ONE state-space layer keeps of one row: the float32 state and
    the convolution's last ``conv_kernel - 1`` inputs in the served dtype."""
    H, P, _G, N, _inner, conv_dim = _ssm(cfg)
    return 4 * H * P * N + BYTES[cfg["dtype"]] * (cfg["conv_kernel"] - 1) \
        * conv_dim


def recurrence_flops_per_token_layer(cfg: dict) -> int:
    """The recurrence as its equations stand, a token a layer: the decay
    of the state, the outer product and its add, the read through ``C``."""
    H, P, _G, N, _inner, _conv = _ssm(cfg)
    return 5 * H * P * N


def state_update(cfg: dict, rows: float) -> dict:
    """The one-token updates of one step over ``rows`` live rows, all the
    state-space layers: each row's state and convolution inputs read and
    written once a layer."""
    nM = layers(cfg, MAMBA)
    return {"bytes": rows * nM * 2 * state_bytes_per_row_layer(cfg),
            "flops": rows * nM * recurrence_flops_per_token_layer(cfg)}


def scan(cfg: dict, tokens: float) -> dict:
    """The scans of one prefill chunk over ``tokens`` valid tokens of one
    row, all the state-space layers: the row's state and convolution
    inputs read and written once a layer, the recurrence a token."""
    nM = layers(cfg, MAMBA)
    return {"bytes": nM * 2 * state_bytes_per_row_layer(cfg),
            "flops": tokens * nM * recurrence_flops_per_token_layer(cfg)}


def _flops(cfg, tokens, head_rows, assignments, pairs) -> float:
    """``tokens`` through every layer, ``head_rows`` of them through the
    head, ``assignments`` made over the published experts, ``pairs``
    (query, column) pairs an attention layer."""
    h = cfg["hidden_size"]
    nM, nE, nA = (layers(cfg, k) for k in (MAMBA, EXPERTS, ATTENTION))
    per_token = nM * (2 * mamba_parameters(cfg)
                      + recurrence_flops_per_token_layer(cfg)) \
        + nA * 2 * attention_parameters(cfg) \
        + nE * 2 * (router_parameters(cfg) + shared_parameters(cfg))
    attn = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * nA
    return (per_token * tokens + 2.0 * h * cfg["vocab_size"] * head_rows
            + 2.0 * expert_parameters(cfg) * held_assignments(cfg, assignments)
            + attn * pairs)


def step(cfg: dict, rows: float, assignments: float, columns: float) -> dict:
    """One decode step over ``rows`` live rows whose valid contexts sum to
    ``columns`` (the slot loop's ``kv_columns_valid`` of the steps), with
    ``assignments`` made over all expert layers (``moe_assignments``: over
    the published experts, held here or not)."""
    return {"bytes": _weights_read(cfg, rows, assignments)
            + columns * kv_bytes_per_column(cfg)
            + state_update(cfg, rows)["bytes"],
            "flops": _flops(cfg, rows, rows, assignments, columns)}


def chunk(cfg: dict, tokens: float, assignments: float, pairs: float) -> dict:
    """One prefill chunk that appends ``tokens`` valid tokens of one row.
    Operations are per (token, column) pair (``chunk_kv_columns_valid``);
    bytes are per DISTINCT column, read once for all the chunk's queries:
    the chunk's context ends at the mean context of its tokens + half its
    tokens.  The head runs for the chunk's last token only, and reads the
    whole table for it."""
    end = pairs / max(tokens, 1.0) + tokens / 2.0
    return {"bytes": _weights_read(cfg, tokens, assignments)
            + end * kv_bytes_per_column(cfg) + scan(cfg, tokens)["bytes"],
            "flops": _flops(cfg, tokens, 1.0, assignments, pairs)}
