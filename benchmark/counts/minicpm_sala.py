"""Operations and least bytes of the step and the prefill-chunk programs of
the hybrid decoder built as ``minicpm_sala`` (lightning linear-attention
layers beside block-sparse grouped-query layers, dense SwiGLU in both), and
of the two mechanisms alone, from shapes.

The algorithm's count, in logical bytes: every weight once (the untied
head's table once: the head reads it whole; the embedding gathers one row
a token), each live row's float32 matrix state read and written once a
linear layer (2 x 2,097,152 B a row a layer), and, a sparse layer, the
CHOSEN blocks' keys and values and the pooled entries the row scores.
Operations: the products by their parameters, the recurrence by its own
equations token by token (``5 x heads x head_dim^2`` a token a layer: the
decay, the outer product and its add, the read through ``q``), the
attention by the (query, column) pairs of the chosen blocks and the
(query, entry) pairs of the pooled scores; the chunked form's extra
products and the chunk's scores over columns it then masks are the
implementation's, not the mathematics'.  No lane padding, no recomputation.

``step`` and ``chunk`` take what the accepted ``hybrid_*_roofline_pct``
readers hand over: the dispatch's tokens, its expert assignments (none
here) and its valid K/V columns, of which only the MEAN context a token can
be read.  A token reads no more than ``topk x block_size`` columns whatever
its context; below ``dense_len`` the model reads all of its context, which
may be more, and which this count leaves out because the mean does not say
how many tokens lay below: it is a lower bound, exact for a step of this
cell (every row is past ``dense_len``) and for the mean of its 24 chunk
positions (16 dense ones average 4,096 columns a token too).  A roofline
share read against these cannot pass 100% by construction.
"""
from __future__ import annotations

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
LINEAR, SPARSE = "lightning-attn", "minicpm4"


def _kinds(cfg) -> list:
    return list(cfg["mixer_types"][:cfg["num_hidden_layers"]])


def layers(cfg: dict, kind: str) -> int:
    return _kinds(cfg).count(kind)


def mlp_parameters(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def linear_parameters(cfg: dict) -> int:
    """q, k, v, the output gate and o; the per-head norms of q and k and
    the output norm."""
    h = cfg["hidden_size"]
    inner = cfg["lightning_nh"] * cfg["lightning_head_dim"]
    return 5 * h * inner + 2 * cfg["lightning_head_dim"] + inner


def sparse_parameters(cfg: dict) -> int:
    """q, the output gate and o; k and v over the cached heads; the
    per-head norms of q and k."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    return h * d * (3 * cfg["num_attention_heads"]
                    + 2 * cfg["num_key_value_heads"]) + 2 * d


def parameters(cfg: dict) -> dict:
    """Parameters held on this chip, by part."""
    h = cfg["hidden_size"]
    nL, nS = layers(cfg, LINEAR), layers(cfg, SPARSE)
    return {"embedding": cfg["vocab_size"] * h, "head": cfg["vocab_size"] * h,
            "norms": h * (2 * (nL + nS) + 1),
            "linear": nL * linear_parameters(cfg),
            "sparse": nS * sparse_parameters(cfg),
            "mlp": (nL + nS) * mlp_parameters(cfg)}


def weight_bytes(cfg: dict) -> int:
    return sum(parameters(cfg).values()) * BYTES[cfg["dtype"]]


def _weights_read(cfg, tokens) -> float:
    p = parameters(cfg)
    fixed = sum(v for k, v in p.items() if k != "embedding")
    return (fixed + tokens * cfg["hidden_size"]) * BYTES[cfg["dtype"]]


def kv_bytes_per_column(cfg: dict) -> int:
    """Keys and values of one token position of one row, all the sparse
    layers."""
    return 2 * layers(cfg, SPARSE) * cfg["num_key_value_heads"] \
        * cfg["head_dim"] * BYTES[cfg["dtype"]]


def pooled_bytes_per_entry(cfg: dict) -> int:
    """One pooled key of every cached head of ONE sparse layer."""
    return cfg["num_key_value_heads"] * cfg["head_dim"] * BYTES[cfg["dtype"]]


def state_bytes_per_row_layer(cfg: dict) -> int:
    """What ONE linear layer keeps of one row: the float32 matrix state."""
    return 4 * cfg["lightning_nh"] * cfg["lightning_head_dim"] ** 2


def linear_state_plane(cfg: dict) -> str:
    """One linear layer's state of every slot, as the compiled program's
    text spells its shape (what ``_sala_scope.plane_copy_ms`` looks for in
    the names of a capture's copies)."""
    return "f32[%d,%d,%d,%d]" % (
        cfg["serve"]["slots"], cfg["lightning_nh"],
        cfg["lightning_head_dim"], cfg["lightning_head_dim"])


def recurrence_flops_per_token_layer(cfg: dict) -> int:
    """The recurrence as its equations stand, a token a layer: the decay
    of the state, the outer product and its add, the read through ``q``."""
    return 5 * cfg["lightning_nh"] * cfg["lightning_head_dim"] ** 2


def linear_update(cfg: dict, rows: float) -> dict:
    """The one-token updates of one step over ``rows`` live rows, all the
    linear layers: each row's state read and written once a layer."""
    nL = layers(cfg, LINEAR)
    return {"bytes": rows * nL * 2 * state_bytes_per_row_layer(cfg),
            "flops": rows * nL * recurrence_flops_per_token_layer(cfg)}


def linear_scan(cfg: dict, tokens: float) -> dict:
    """The scans of one prefill chunk over ``tokens`` valid tokens of one
    row, all the linear layers: the row's state read and written once a
    layer, the recurrence a token."""
    nL = layers(cfg, LINEAR)
    return {"bytes": nL * 2 * state_bytes_per_row_layer(cfg),
            "flops": tokens * nL * recurrence_flops_per_token_layer(cfg)}


def _attention_flops_per_pair(cfg: dict) -> float:
    """Scores and weighted values of one (query, column) pair, ONE layer."""
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]


def sparse_read(cfg: dict, blocks: float, entries: float) -> dict:
    """What the sparse layers of one step read and compute to score,
    choose and attend: ``blocks`` chosen blocks (a row a layer, as the
    slot loop's ``sparse_blocks_selected`` counts them: every cached head
    reads as many) and ``entries`` pooled entries scored (a row a layer,
    ``pooled_entries_scored``).  The same whichever form reads."""
    sp = cfg["sparse_config"]
    cols = blocks * sp["block_size"]
    per_layer_column = kv_bytes_per_column(cfg) / max(layers(cfg, SPARSE), 1)
    return {"bytes": cols * per_layer_column
            + entries * pooled_bytes_per_entry(cfg),
            "flops": cols * _attention_flops_per_pair(cfg)
            + entries * _attention_flops_per_pair(cfg) / 2.0}


def read_columns(cfg: dict, context: float) -> float:
    """Columns a token with ``context`` tokens of context reads at the
    least (the module docstring's lower bound)."""
    sp = cfg["sparse_config"]
    return min(max(context, 0.0), float(sp["topk"] * sp["block_size"]))


def scored_entries(cfg: dict, context: float) -> float:
    sp = cfg["sparse_config"]
    if context <= sp["dense_len"]:
        return 0.0
    return max((context - sp["kernel_size"]) // sp["kernel_stride"] + 1, 0.0)


def _flops(cfg, tokens, head_rows, pairs, entries) -> float:
    """``tokens`` through every layer, ``head_rows`` of them through the
    head, ``pairs`` (query, column) pairs and ``entries`` (query, pooled
    entry) pairs a sparse layer."""
    nL, nS = layers(cfg, LINEAR), layers(cfg, SPARSE)
    per_token = nL * (2 * linear_parameters(cfg)
                      + recurrence_flops_per_token_layer(cfg)) \
        + nS * 2 * sparse_parameters(cfg) \
        + (nL + nS) * 2 * mlp_parameters(cfg)
    pair = _attention_flops_per_pair(cfg) * nS
    return (per_token * tokens
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * head_rows
            + pair * pairs + pair / 2.0 * entries)


def step(cfg: dict, rows: float, assignments: float, columns: float) -> dict:
    """One decode step over ``rows`` live rows whose valid contexts sum to
    ``columns`` (the slot loop's ``kv_columns_valid`` of the steps);
    ``assignments`` is the hybrid family's argument and counts nothing
    here (no experts)."""
    context = columns / max(rows, 1.0)
    read, scored = read_columns(cfg, context), scored_entries(cfg, context)
    nS = layers(cfg, SPARSE)
    return {"bytes": _weights_read(cfg, rows)
            + rows * (read * kv_bytes_per_column(cfg)
                      + scored * nS * pooled_bytes_per_entry(cfg))
            + linear_update(cfg, rows)["bytes"],
            "flops": _flops(cfg, rows, rows, rows * read, rows * scored)}


def chunk(cfg: dict, tokens: float, assignments: float, pairs: float) -> dict:
    """One prefill chunk that appends ``tokens`` valid tokens of one row.
    Operations are per (token, column) pair, no more than ``topk x
    block_size`` of them a token; bytes are per DISTINCT column, read once
    for all the chunk's queries (between them they choose nearly every
    block): the chunk's context ends at the mean context of its tokens +
    half its tokens, and the pooled entries up to there are read once.
    The head runs for the chunk's last token only, and reads the whole
    table for it."""
    context = pairs / max(tokens, 1.0)
    end = context + tokens / 2.0
    nS = layers(cfg, SPARSE)
    return {"bytes": _weights_read(cfg, tokens)
            + end * kv_bytes_per_column(cfg)
            + scored_entries(cfg, end) * nS * pooled_bytes_per_entry(cfg)
            + linear_scan(cfg, tokens)["bytes"],
            "flops": _flops(cfg, tokens, 1.0,
                            tokens * read_columns(cfg, context),
                            tokens * scored_entries(cfg, context))}
