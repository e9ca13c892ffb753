"""Expert-parallel Mixture-of-Experts layers (ISSUE 14).

The fourth workload pillar on the all-to-all routing layer: compute
scales with the expert count while per-token FLOPs stay constant — the
sparse-scaling play the reference's heterogeneous CTR stack (PAPER.md
``distributed/`` + HeterPS seat) chased with parameter servers, done
TPU-style on the mesh.  ``ops/routing.py`` built the movers for
embedding rows (PR 10); here the SAME static-cap owner bucketing routes
*token vectors*, with owner = expert shard:

  * **top-k softmax gating** (k ∈ {1, 2}) over a replicated gate
    projection; k = 2 gates renormalize over the chosen pair;
  * **capacity-factor dispatch** — each routing-axis group may park at
    most ``cap = ceil(capacity_factor · tokens · k / E)`` assignments on
    one expert (``pack_by_owner`` with ``rps = 1``); overflow
    assignments DROP (the token keeps its residual) and are counted;
  * **expert FFNs as ONE stacked parameter** per plane —
    ``experts.w1 [E, D, H]`` etc., sharded ``P(ep, None, None)`` so each
    shard owns ``E / n`` experts (autoshard: the ``expert`` rules
    table);
  * **two all_to_alls per layer** — tokens expert-ward, results
    token-ward (``ops.routing.all_to_all_experts``), wire bytes ∝
    capacity, never vocab;
  * **aux load-balance loss** — ``E · Σ_e mean-gate_e ×
    fraction-routed_e`` per group, surfaced through the model loss
    (``total_aux_loss``).

Correctness contract: ``dispatch="dense"`` runs the GShard-style
dense-dispatch control — every token einsum-multiplied against every
``(expert, capacity)`` slot through a one-hot mask built from the SAME
:func:`~...ops.routing.expert_dispatch_plan` — producing expert input
buffers bit-identical to the routed path's, so forward AND backward
bit-match on a real mesh (the 8-device gate in tests/test_moe.py).

Observability: per-forward drop count and per-expert load ratios land
in the ``_moe_dropped`` / ``_moe_load`` buffers (in-graph, donated with
the rest of the state); :func:`publish_moe_metrics` flushes them into
the typed registry (``moe_tokens_dropped_total{model}`` counter +
``moe_expert_load_ratio`` histogram).
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from ...framework import flags as _flags
from ...framework.enforce import InvalidArgumentError
from ...framework.tensor import Tensor, unwrap
from ...ops import routing as _routing
from ...profiler.metrics import default_registry as _registry
from .common import Dropout, Linear
from .layers import Layer
from .norm import LayerNorm
from .transformer import MultiHeadAttention

__all__ = [
    "MoELayer", "MoEEncoderLayer", "ExpertFFN", "DroplessMoE", "SwiGLU",
    "top_k_gating",
    "load_balance_loss", "moe_layers", "total_aux_loss",
    "publish_moe_metrics", "moe_axis", "moe_top_k", "moe_capacity_factor",
]

MOE_DROPPED = _registry().counter(
    "moe_tokens_dropped_total",
    "Token→expert assignments dropped past the per-expert capacity "
    "(the routed token keeps its residual); flushed from the layers' "
    "in-graph counters by nn.layer.moe.publish_moe_metrics.",
    labels=("model",))
MOE_LOAD = _registry().histogram(
    "moe_expert_load_ratio",
    "Per-expert routed load as a multiple of the balanced share "
    "(1.0 = perfectly balanced; >capacity_factor implies drops); one "
    "observation per expert per publish_moe_metrics flush.",
    labels=("model",),
    buckets=(0.1, 0.25, 0.5, 0.75, 1.0, 1.25, 1.5, 2.0, 3.0, 5.0))


# ---------------------------------------------------------------------------
# exactness primitives
#
# The bit-match contract (routed == dense control, forward AND backward)
# needs two things XLA does not guarantee by default:
#
#   * a GELU whose VJP is an explicit elementwise formula — jax.nn.gelu's
#     autodiff backward gets reassociated differently by the fusion
#     compiler depending on the surrounding batch shape (measured 1-ulp
#     grad skew between the [eps, ...] shard body and the [E, ...] dense
#     stack);
#   * an optimization barrier around the control's expert stack so the
#     combine einsum's backward cannot fuse into the expert reductions —
#     the same isolation the shard_map boundary gives the routed path.
# ---------------------------------------------------------------------------

_SQRT_HALF = np.float32(0.7071067811865476)
_INV_SQRT_2PI = np.float32(0.3989422804014327)


@jax.custom_vjp
def _exact_gelu(x):
    """Exact (erf) GELU with a hand-written elementwise VJP: the
    derivative ``Φ(x) + x·φ(x)`` is one fused elementwise expression in
    BOTH the routed and dense programs, so gradients stay bitwise
    shape-independent."""
    return x * (0.5 * (1.0 + jax.lax.erf(x * _SQRT_HALF)))


def _exact_gelu_fwd(x):
    return _exact_gelu(x), x


def _exact_gelu_bwd(x, g):
    phi = 0.5 * (1.0 + jax.lax.erf(x * _SQRT_HALF))
    dens = jnp.exp(-0.5 * x * x) * _INV_SQRT_2PI
    return (g * (phi + x * dens),)


_exact_gelu.defvjp(_exact_gelu_fwd, _exact_gelu_bwd)


@jax.custom_vjp
def _isolate(x):
    """Identity that blocks XLA fusion across it, in both directions
    (``optimization_barrier`` has no autodiff rule in jax 0.4, hence
    the custom_vjp wrapper)."""
    return jax.lax.optimization_barrier(x)


def _isolate_fwd(x):
    return _isolate(x), None


def _isolate_bwd(_, g):
    return (jax.lax.optimization_barrier(g),)


_isolate.defvjp(_isolate_fwd, _isolate_bwd)


def moe_axis() -> str:
    return str(_flags.flag("moe_axis"))


def moe_top_k() -> int:
    return int(_flags.flag("moe_top_k"))


def moe_capacity_factor() -> float:
    return float(_flags.flag("moe_capacity_factor"))


# ---------------------------------------------------------------------------
# gating + aux loss (shared VERBATIM by the routed path and the dense
# control — bitwise identity of the two starts here)
# ---------------------------------------------------------------------------

def gate_from_logits(logits, k: int):
    """Softmax + top-k over precomputed gate logits ``[U, E]``.

    Returns ``(probs [U, E] f32, expert_ids [U, k] int32, gates
    [U, k] f32)``; k = 2 gates renormalize over the chosen pair (the
    GShard top-2 rule), k = 1 keeps the raw top-1 probability (Switch).
    Deterministic: ties break toward the lower expert index.
    """
    if int(k) not in (1, 2):
        raise InvalidArgumentError(
            f"top-k gating supports k in {{1, 2}}, got {k} "
            "(FLAGS_moe_top_k)")
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    vals, eids = jax.lax.top_k(probs, int(k))
    gates = vals / jnp.sum(vals, axis=-1, keepdims=True) if int(k) > 1 \
        else vals
    return probs, eids.astype(jnp.int32), gates


def top_k_gating(x2d, gate_w, k: int, mesh=None):
    """Softmax gating over ``E`` experts for ``[U, D]`` token rows —
    :func:`gate_from_logits` over the gate projection.  With ``mesh``,
    the projection's forward and backward contractions are pinned
    replicated (see :func:`_pinned_gate_project`) so the gate weight's
    gradient keeps one association whatever the rest of the program
    partitions."""
    logits = _pinned_gate_project(x2d, gate_w, mesh)
    return gate_from_logits(logits, k)


def _pinned_gate_project(x2d, gate_w, mesh=None):
    """``x @ W_gate`` whose VJP contractions are pinned to replicated
    full shapes on ``mesh``.

    Left free, GSPMD back-propagates the dispatch's ``P(axis)`` specs
    into the gating chain and computes the weight gradient as
    per-device partial dots + all-reduce — a different summation
    association than an unpartitioned program's single contraction
    (1-ulp skew that breaks the routed == dense-control bit-match).
    Constraints on every operand and result of the custom VJP leave the
    partitioner no freedom here; token-row math elsewhere is row-wise
    exact under any partitioning, so this one dot is the only pin the
    contract needs."""
    x32 = jnp.asarray(x2d, jnp.float32)
    w32 = jnp.asarray(gate_w, jnp.float32)
    if mesh is None:
        return jnp.matmul(x32, w32)
    from jax.sharding import NamedSharding, PartitionSpec as _P
    rep = NamedSharding(mesh, _P())

    def pin(v):
        return jax.lax.with_sharding_constraint(v, rep)

    @jax.custom_vjp
    def proj(x, w):
        return pin(jnp.matmul(pin(x), pin(w)))

    def fwd(x, w):
        return proj(x, w), (x, w)

    def bwd(res, g):
        x, w = res
        g = pin(g)
        dw = pin(jnp.einsum("ud,ue->de", pin(x), g))
        dx = pin(jnp.einsum("ue,de->ud", g, pin(w)))
        return dx, dw

    proj.defvjp(fwd, bwd)
    return proj(x32, w32)


def load_balance_loss(probs, expert_ids, n_groups: int):
    """The standard auxiliary load-balance loss, per routing group:
    ``E · mean_g Σ_e fraction-routed_{g,e} × mean-gate_{g,e}`` — minimal
    (1.0) at a perfectly uniform assignment, so the gate learns to
    spread tokens instead of collapsing onto one expert.  Pre-capacity
    fractions: the loss shapes the gate, the capacity enforces the
    budget."""
    U, E = probs.shape
    k = expert_ids.shape[-1]
    G = int(n_groups)
    pg = probs.reshape(G, U // G, E)
    mean_gate = jnp.mean(pg.astype(jnp.float32), axis=1)          # [G, E]
    onehot = jax.nn.one_hot(expert_ids.reshape(G, -1), E,
                            dtype=jnp.float32)                    # [G, uk, E]
    frac = jnp.mean(onehot, axis=1)                               # [G, E]
    return jnp.float32(E) * jnp.mean(jnp.sum(frac * mean_gate, axis=-1))


class ExpertFFN(Layer):
    """The expert bank: one two-layer FFN per expert, stored as stacked
    leading-``E``-axis parameters (``w1 [E, D, H]``, ``b1 [E, H]``,
    ``w2 [E, H, D]``, ``b2 [E, D]``) so a ``P(ep, None, None)``
    annotation shards WHOLE experts — every shard runs a dense
    ``[eps, m, D]`` batch through its slice, no ragged compute."""

    def __init__(self, num_experts: int, d_model: int, d_hidden: int,
                 activation: str = "gelu"):
        super().__init__()
        if activation not in ("gelu", "relu"):
            raise InvalidArgumentError(
                f"unsupported MoE expert activation {activation!r}")
        self.num_experts = int(num_experts)
        self.d_model = int(d_model)
        self.d_hidden = int(d_hidden)
        self.activation = activation
        E, D, H = self.num_experts, self.d_model, self.d_hidden
        self.w1 = self.create_parameter([E, D, H])
        self.b1 = self.create_parameter([E, H], is_bias=True)
        self.w2 = self.create_parameter([E, H, D])
        self.b2 = self.create_parameter([E, D], is_bias=True)

    def stack_fn(self):
        """The pure stacked-expert apply ``(rows [e, m, D], w1, b1, w2,
        b2) -> [e, m, D]`` handed to the routing movers: expert- and
        row-independent, so the routed per-shard slice and the dense
        full-stack control compute bit-identical rows."""
        act = _exact_gelu if self.activation == "gelu" else jax.nn.relu

        def fn(rows, w1, b1, w2, b2):
            h = act(jnp.einsum("emd,edh->emh", rows, w1)
                    + b1[:, None, :].astype(rows.dtype))
            return (jnp.einsum("emh,ehd->emd", h, w2)
                    + b2[:, None, :].astype(rows.dtype))
        return fn

    def raw_params(self):
        return (self.w1._value, self.b1._value, self.w2._value,
                self.b2._value)


class MoELayer(Layer):
    """Top-k gated, capacity-dispatched, expert-parallel FFN.

    ``forward(x [.., D]) -> [.., D]``: gate each token, bucket
    assignments by owning expert under the static capacity, move token
    rows to the expert shards (two all_to_alls over ``axis``), run the
    local expert slice, move results back, combine under the gate
    weights.  Dropped assignments contribute zero — the surrounding
    residual connection is the passthrough.  ``dispatch``:

      ``routed``  the production mover (shard_map all_to_all) when the
                  mesh carries the expert axis; falls back to the
                  meshless local scatter/gather when it does not;
      ``dense``   the GShard einsum dense-dispatch control — every
                  token against every (expert, slot) through a one-hot
                  mask from the same plan; the bit-match reference.
    """

    def __init__(self, d_model: int, d_hidden: Optional[int] = None,
                 num_experts: int = 8, top_k: Optional[int] = None,
                 capacity_factor: Optional[float] = None, mesh=None,
                 axis: Optional[str] = None, activation: str = "gelu",
                 dispatch: str = "routed", annotate: bool = True,
                 gate_attr=None):
        super().__init__()
        if dispatch not in ("routed", "dense"):
            raise InvalidArgumentError(
                f"MoELayer dispatch must be 'routed' or 'dense', "
                f"got {dispatch!r}")
        self.d_model = int(d_model)
        self.d_hidden = int(d_hidden if d_hidden is not None
                            else 4 * d_model)
        self.num_experts = int(num_experts)
        self.top_k = int(top_k if top_k is not None else moe_top_k())
        self.capacity_factor = float(
            capacity_factor if capacity_factor is not None
            else moe_capacity_factor())
        if self.top_k not in (1, 2):
            raise InvalidArgumentError(
                f"MoE top_k must be 1 or 2, got {self.top_k}")
        if self.capacity_factor <= 0:
            raise InvalidArgumentError(
                f"MoE capacity_factor must be > 0, "
                f"got {self.capacity_factor}")
        if self.num_experts < 1:
            raise InvalidArgumentError("num_experts must be >= 1")
        self.dispatch = dispatch
        self.axis = axis or moe_axis()
        self.mesh = mesh
        if self.mesh is None:
            from ...parallel.mesh import get_mesh, has_mesh
            if has_mesh():
                self.mesh = get_mesh()
        n = 1
        if self.mesh is not None:
            n = int(dict(self.mesh.shape).get(self.axis, 1))
        if n > 1 and self.num_experts % n:
            raise InvalidArgumentError(
                f"num_experts ({self.num_experts}) must divide by the "
                f"{self.axis!r} axis size ({n}) — each shard owns a "
                "whole number of experts")
        self.n_shards = n
        self.gate = Linear(self.d_model, self.num_experts,
                           weight_attr=gate_attr, bias_attr=False)
        self.experts = ExpertFFN(self.num_experts, self.d_model,
                                 self.d_hidden, activation)
        self._aux = None
        self._aux_in = None
        self.register_buffer("_moe_dropped",
                             Tensor(jnp.zeros((), jnp.float32)))
        self.register_buffer("_moe_load",
                             Tensor(jnp.zeros((self.num_experts,),
                                              jnp.float32)))
        if annotate and self.n_shards > 1 and dispatch == "routed":
            from jax.sharding import PartitionSpec as P
            from ...parallel.api import shard_parameter
            ax = self.axis
            shard_parameter(self.experts.w1, P(ax, None, None))
            shard_parameter(self.experts.b1, P(ax, None))
            shard_parameter(self.experts.w2, P(ax, None, None))
            shard_parameter(self.experts.b2, P(ax, None))

    def capacity_for(self, n_tokens: int) -> int:
        """Static per-(group, expert) slot count for a ``n_tokens``
        forward (a compile-time constant per input shape)."""
        return _routing.moe_capacity(n_tokens // self.n_shards,
                                     self.top_k, self.num_experts,
                                     self.capacity_factor)

    def _dense_rows(self, x_dup, pos, cap):
        """Dense-dispatch control: one-hot every assignment against the
        full ``[E * cap]`` slot range and einsum tokens in and out —
        gather-all-tokens-to-all-experts, mask, combine.  Slot buffers
        (and therefore expert inputs, outputs and every gradient) are
        bit-identical to the routed mover's: each slot holds at most
        one token, and ``x·1 + Σ 0`` is exact in any float width."""
        E, G = self.num_experts, self.n_shards
        D = x_dup.shape[-1]
        slots = E * cap
        xg = x_dup.reshape(G, -1, D)
        onehot = (pos[..., None] ==
                  jnp.arange(slots, dtype=jnp.int32)[None, None, :]
                  ).astype(x_dup.dtype)                  # [G, S, slots]
        buf = jnp.einsum("gts,gtd->gsd", onehot, xg)     # [G, slots, D]
        ebuf = buf.reshape(G, E, cap, D).transpose(1, 0, 2, 3) \
            .reshape(E, G * cap, D)
        # _isolate = the control's stand-in for the routed path's
        # shard_map boundary: without it the combine einsum's backward
        # fuses into the expert reductions and reassociates them
        y = self.experts.stack_fn()(_isolate(ebuf),
                                    *self.experts.raw_params())
        ybuf = _isolate(y).reshape(E, G, cap, D).transpose(1, 0, 2, 3) \
            .reshape(G, slots, D)
        out = jnp.einsum("gts,gsd->gtd", onehot, ybuf)   # [G, S, D]
        return out.reshape(-1, D)

    def forward(self, x):
        xv = unwrap(x)
        D = xv.shape[-1]
        if D != self.d_model:
            raise InvalidArgumentError(
                f"MoELayer(d_model={self.d_model}) got inputs of "
                f"width {D}")
        lead = xv.shape[:-1]
        x2 = xv.reshape(-1, D)
        U = x2.shape[0]
        n, k, E = self.n_shards, self.top_k, self.num_experts
        if self.mesh is not None and n > 1:
            # hard boundary for GSPMD propagation: without it the
            # shard_map's P(axis) input specs walk upstream through
            # repeat/reshape into the residual stream, and every
            # attention/embedding weight gradient above this layer
            # becomes a token-sharded partial contraction + all-reduce
            from jax.sharding import NamedSharding, PartitionSpec as _P
            x2 = jax.lax.with_sharding_constraint(
                x2, NamedSharding(self.mesh, _P()))
        if U % n:
            raise InvalidArgumentError(
                f"MoE routing over axis {self.axis!r} (size {n}) needs "
                f"the token count ({U}) divisible by the axis size — "
                "pad the batch to a multiple")
        probs, eids, gates = top_k_gating(
            x2, self.gate.weight._value, k,
            mesh=self.mesh if n > 1 else None)
        if self.mesh is not None and n > 1:
            # pin the gating region replicated: the shard_map's P(axis)
            # input specs otherwise back-propagate through the dispatch
            # plan into top-k/softmax/the gate projection, which then
            # compute per-device token slices — and the gate weight's
            # gradient becomes partial-dot + all-reduce, a different
            # summation association than the dense control's full-shape
            # contraction (1-ulp skew, visible in the compiled HLO).
            # Integer plan math is exact under any partitioning; only
            # the float gating outputs need pinning.
            from jax.sharding import NamedSharding, PartitionSpec as _P
            rep = NamedSharding(self.mesh, _P())
            probs = jax.lax.with_sharding_constraint(probs, rep)
            gates = jax.lax.with_sharding_constraint(gates, rep)
            eids = jax.lax.with_sharding_constraint(eids, rep)
        # barrier the float gating outputs as well: the gate projection
        # and softmax then live in a fusion region whose contents are
        # identical whatever dispatch runs next door, so the gate
        # weight's gradient contraction never reassociates.  probs only
        # feeds the aux loss, which is deferred to aux_loss() below —
        # its barrier defers with it
        gates = _isolate(gates)
        cap = self.capacity_for(U)
        plan = _routing.expert_dispatch_plan(
            eids.reshape(n, (U // n) * k), n_experts=E, cap=cap)
        x_dup = jnp.repeat(x2, k, axis=0)                # [U*k, D]
        fn = self.experts.stack_fn()
        params = self.experts.raw_params()
        # the dispatch core runs between fusion barriers in EVERY mode,
        # so the (identical) gating/combine code around it compiles into
        # identical kernels whichever mover runs inside — the fusion
        # half of the bit-match contract (the other half is the
        # elementwise-VJP gelu above)
        x_dup = _isolate(x_dup)
        if self.dispatch == "dense":
            rows = self._dense_rows(x_dup, plan.pos, cap)
        elif n > 1:
            rows = _routing.all_to_all_experts(
                x_dup, plan.pos, params, fn, mesh=self.mesh,
                axis=self.axis, n_experts=E, cap=cap)
            # pin the result rows back to replicated at the shard_map
            # boundary (one all-gather): every op outside the dispatch
            # then reduces at full shape — shared-parameter gradients
            # (gate, attention, embeddings, the loss itself) keep the
            # exact association of the dense control instead of
            # ep-partial sums + all-reduce
            from jax.sharding import NamedSharding, PartitionSpec as _P
            rows = jax.lax.with_sharding_constraint(
                rows, NamedSharding(self.mesh, _P()))
        else:
            rows = _routing.local_experts(
                x_dup, plan.pos, params, fn, n_experts=E, cap=cap)
        rows = _isolate(rows)
        out = jnp.sum(rows.reshape(U, k, D)
                      * gates[..., None].astype(rows.dtype), axis=1)
        # aux-loss ingredients + in-graph stats: pre-capacity fractions
        # shape the gate; dropped/load land in buffers the step donates
        # like any other state (publish_moe_metrics flushes them
        # host-side).  The loss itself is computed lazily in aux_loss()
        # — a forward whose caller never sums it (every inference step)
        # must not trace it as dead compute (graph-lint dead-fetch)
        self._aux_in = (probs, eids, n)
        self._aux = None
        self._moe_dropped.set_value(
            Tensor(jnp.sum(plan.dropped).astype(jnp.float32)))
        self._moe_load.set_value(Tensor(
            jnp.sum(plan.counts, axis=0).astype(jnp.float32)
            * jnp.float32(E) / jnp.float32(U * k)))
        return Tensor(out.reshape(lead + (D,)).astype(xv.dtype)) \
            if isinstance(x, Tensor) else out.reshape(lead + (D,))

    def aux_loss(self):
        """The load-balance loss of the LAST forward (a traced value
        inside the same trace; the model sums these into its loss).
        Emitted on first call from that forward's stored gating outputs
        — identical value, but never traced when nothing consumes it."""
        if self._aux is None and self._aux_in is not None:
            probs, eids, n = self._aux_in
            self._aux = load_balance_loss(_isolate(probs), eids, n)
        return self._aux

    def wire_bytes(self, n_tokens: int, itemsize: int = 4) -> int:
        """Ring-model per-device bytes of this layer's two all_to_alls
        for one ``n_tokens`` forward."""
        return _routing.moe_a2a_wire_bytes(
            self.num_experts, self.capacity_for(n_tokens), self.d_model,
            self.n_shards, itemsize)

    def extra_repr(self):
        return (f"d_model={self.d_model}, d_hidden={self.d_hidden}, "
                f"experts={self.num_experts}, top_k={self.top_k}, "
                f"capacity_factor={self.capacity_factor}, "
                f"axis={self.axis!r}, shards={self.n_shards}, "
                f"dispatch={self.dispatch!r}")


class MoEEncoderLayer(Layer):
    """TransformerEncoderLayer with the dense FFN replaced by a
    :class:`MoELayer` — same attention/norm/cache contract (ring-cache
    decode included), so GPT-style stacks swap blocks freely."""

    def __init__(self, d_model, nhead, dim_feedforward, num_experts,
                 dropout=0.1, activation="gelu", attn_dropout=None,
                 act_dropout=None, normalize_before=True, top_k=None,
                 capacity_factor=None, mesh=None, axis=None,
                 dispatch="routed", annotate=True):
        super().__init__()
        attn_dropout = dropout if attn_dropout is None else attn_dropout
        self.normalize_before = normalize_before
        self.self_attn = MultiHeadAttention(d_model, nhead, attn_dropout)
        self.moe = MoELayer(d_model, dim_feedforward, num_experts,
                            top_k=top_k, capacity_factor=capacity_factor,
                            mesh=mesh, axis=axis, activation=activation,
                            dispatch=dispatch, annotate=annotate)
        self.norm1 = LayerNorm(d_model)
        self.norm2 = LayerNorm(d_model)
        self.dropout1 = Dropout(dropout)
        self.dropout2 = Dropout(dropout)

    def forward(self, src, src_mask=None, cache=None, cache_position=None,
                decode_window=None, row=None):
        residual = src
        if self.normalize_before:
            src = self.norm1(src)
        if cache is None:
            src = self.self_attn(src, src, src, src_mask)
        else:
            src, cache = self.self_attn(src, src, src, src_mask, cache,
                                        cache_position=cache_position,
                                        decode_window=decode_window,
                                        row=row)
        src = residual + self.dropout1(src)
        if not self.normalize_before:
            src = self.norm1(src)
        residual = src
        if self.normalize_before:
            src = self.norm2(src)
        # dropped assignments return zero rows: the residual add below
        # IS the capacity-overflow passthrough
        src = residual + self.dropout2(self.moe(src))
        if not self.normalize_before:
            src = self.norm2(src)
        return src if cache is None else (src, cache)

    def gen_cache(self, src):
        return self.self_attn.gen_cache(src)

    def gen_ring_cache(self, batch, max_len, dtype="float32"):
        return self.self_attn.gen_ring_cache(batch, max_len, dtype)

    def ring_cache_spec(self, max_len):
        return self.self_attn.ring_cache_spec(max_len)


# ---------------------------------------------------------------------------
# model-level plumbing
# ---------------------------------------------------------------------------

def moe_layers(layer) -> Sequence[MoELayer]:
    """Every MoELayer in a model, in traversal order."""
    return [m for _, m in layer.named_sublayers(include_self=True)
            if isinstance(m, MoELayer)]


def total_aux_loss(layer):
    """Sum of the per-MoE-layer load-balance losses of the LAST forward
    (call right after the forward that produced them; 0.0 when the
    model has no MoE layers or none has run)."""
    terms = [m.aux_loss() for m in moe_layers(layer)
             if m.aux_loss() is not None]
    if not terms:
        return jnp.float32(0.0)
    total = terms[0]
    for t in terms[1:]:
        total = total + t
    return total


def publish_moe_metrics(layer, model: str = "moe"):
    """Flush the layers' in-graph drop/load buffers into the typed
    registry: ``moe_tokens_dropped_total{model}`` grows by the summed
    drop counters, ``moe_expert_load_ratio{model}`` gets one
    observation per expert.  Returns ``(dropped_total, load_ratios)``.
    """
    dropped = 0.0
    loads = []
    for m in moe_layers(layer):
        dropped += float(np.asarray(unwrap(m._moe_dropped)))
        loads.extend(np.asarray(unwrap(m._moe_load)).tolist())
    if dropped:
        MOE_DROPPED.labels(model=model).inc(dropped)
    h = MOE_LOAD.labels(model=model)
    for v in loads:
        h.observe(float(v))
    return dropped, loads


# ---------------------------------------------------------------------------
# dropless, sigmoid-routed experts of which this chip holds a share
# ---------------------------------------------------------------------------

def clamped(g, v, limit):
    """A SwiGLU's two halves held to ``limit``: the gate's input from above
    (``min(g, limit)``: below, the SiLU flattens by itself), the linear
    half from both sides; ``limit`` None leaves them as they are."""
    if limit is None:
        return g, v
    return jnp.minimum(g, limit), jnp.clip(v, -limit, limit)


class SwiGLU(Layer):
    """``W_down(silu(W_gate u) * W_up u)``, no biases; with ``limit``,
    ``W_down(silu(min(g, limit)) * clip(v, -limit, limit))``."""

    def __init__(self, hidden, width, weight_attr=None, dtype=None,
                 limit=None):
        super().__init__()
        from .. import initializer as I
        self.limit = None if limit is None else float(limit)

        def mat(*shape):
            return self.create_parameter(
                list(shape), attr=weight_attr, dtype=dtype,
                default_initializer=I.Normal(0.0, 0.02))
        self.w_gate = mat(hidden, width)
        self.w_up = mat(hidden, width)
        self.w_down = mat(width, hidden)

    def forward(self, u):
        raw = unwrap(u)
        f32 = jnp.float32
        with jax.named_scope("mlp"):
            g = jnp.einsum("...h,hf->...f", raw, unwrap(self.w_gate),
                           preferred_element_type=f32)
            v = jnp.einsum("...h,hf->...f", raw, unwrap(self.w_up),
                           preferred_element_type=f32)
            g, v = clamped(g, v, self.limit)
            y = jnp.einsum("...f,fh->...h",
                           (jax.nn.silu(g) * v).astype(raw.dtype),
                           unwrap(self.w_down), preferred_element_type=f32)
            y = y.astype(raw.dtype)
        return Tensor(y) if isinstance(u, Tensor) else y


class ReluSquared(Layer):
    """``W_down relu(W_up u)^2``: two matrices, no gate, no biases."""

    def __init__(self, hidden, width, weight_attr=None, dtype=None):
        super().__init__()
        from .. import initializer as I

        def mat(*shape):
            return self.create_parameter(
                list(shape), attr=weight_attr, dtype=dtype,
                default_initializer=I.Normal(0.0, 0.02))
        self.w_up = mat(hidden, width)
        self.w_down = mat(width, hidden)

    def forward(self, u):
        raw = unwrap(u)
        f32 = jnp.float32
        with jax.named_scope("mlp"):
            v = jnp.einsum("...h,hf->...f", raw, unwrap(self.w_up),
                           preferred_element_type=f32)
            y = jnp.einsum("...f,fh->...h",
                           jnp.square(jax.nn.relu(v)).astype(raw.dtype),
                           unwrap(self.w_down), preferred_element_type=f32)
            y = y.astype(raw.dtype)
        return Tensor(y) if isinstance(u, Tensor) else y


# an expert's form -> the dense layer of that form (the shared expert is one)
EXPERT_FORMS = {"swiglu": SwiGLU, "relu2": ReluSquared}


class DroplessMoE(Layer):
    """Sigmoid-routed experts without capacity and without drops, told
    WHICH experts it holds.

    The router scores all ``num_experts`` (its published width) with a
    sigmoid, chooses the ``top_k`` of ``score + bias`` (the bias corrects
    the load and is used to choose only), and weighs the chosen by their
    scores, renormalised over the chosen (``norm_topk``) and times
    ``scaling``.  This layer holds experts ``held = (lo, hi)`` and
    computes THEIR part of ``sum_chosen w_i E_i(u)``: the assignments are
    sorted by expert and the expert's products run over the rows of each
    held expert, however many rows that is.  ``activation`` is the
    experts' form: ``"swiglu"`` (three matrices, ``W_down(silu(W_gate u) *
    W_up u)``) or ``"relu2"`` (two, ``W_down relu(W_up u)^2``: such a layer
    has no ``w_gate``); ``limit`` clamps a SwiGLU's two halves
    (:func:`clamped`), in the routed experts and the shared one alike.  An
    assignment to an expert
    held elsewhere adds nothing here: on a mesh the other shares' parts
    arrive by the exchange that this layer does not do (the one-chip
    share of an expert-parallel deployment); ``held=None`` holds every
    expert.  ``shared`` experts (one dense layer of the
    experts' own form, ``shared x width`` wide) see every token and are
    counted once.  ``norm_eps`` is what the
    renormalisation adds to the sum of the chosen scores (``w_i = s_i /
    (sum + norm_eps)``, the ``lfm2_moe`` form); ``None`` divides by
    ``max(sum, 1e-20)`` (the ``dots3_note`` form).

    **Which products a dispatch runs** is decided by its size, never by a
    model's name.  Each held expert's rows are padded to ``cap`` (four
    times the even share ``A / num_experts`` of the dispatch's ``A``
    assignments, but at most 64 rows over it, at least 8) and run as ONE
    batched product, which streams the experts' weights once at 92% of
    the chip's bandwidth, as long as ``cap <= PADDED_ROWS_MAX``: up to
    there the padded rows cost less than the weights they are multiplied
    with.  A dispatch in which the router sent one expert more than
    ``cap`` rows takes a second, WIDE tier of the same padded products
    (PR 40): a token sends an expert one row at most, so ``min(tokens,
    PADDED_ROWS_MAX)`` rows hold any skew of a dispatch of up to
    ``PADDED_ROWS_MAX`` tokens (every decode step), and a 512-token chunk
    overflows it only when one expert gets more than half its tokens.
    Beyond that, and in a dispatch whose ``cap`` passes
    ``PADDED_ROWS_MAX``, the products run grouped as the rows lie
    (``lax.ragged_dot``, 45% of the bandwidth whatever the rows: PERF.md
    section 6, PR 27): nothing is ever dropped.  The benchmark's cells
    enter: ``dots3-ep8-rag4k-saturated`` the first tier in its 64-row
    step (cap 8) and its 512-token chunk (cap 64);
    ``lfm2-pp2-reason-saturated`` (all 32 experts held, top 4) in its
    128-row step (cap 64) and its chunk (cap 128);
    ``nemotron3-ep8-reason1k-saturated`` (16 of 128 held, top 6) the first
    tier in its 48-row step (cap 16) and, in most of its chunks (cap 88:
    one request's 512 tokens send an expert up to 171 rows), the wide one
    (256).

    After a forward, ``last_counts`` holds (assignments made, assignments
    that fell on held experts, the largest per-expert row count), over
    the tokens marked live, as int32 scalars of the same trace.
    """

    # rows an expert may be padded to: past ~240 rows (v5e: 197 TFLOP/s
    # over 819 GB/s) an expert's products cost more than its weights
    PADDED_ROWS_MAX = 256

    def __init__(self, hidden, width, num_experts, top_k, *, held=None,
                 shared=0, scaling=1.0, norm_topk=True, norm_eps=None,
                 activation="swiglu", limit=None, weight_attr=None,
                 dtype=None):
        super().__init__()
        from .. import initializer as I
        if activation not in EXPERT_FORMS:
            raise InvalidArgumentError(
                f"experts of form {activation!r}; have "
                f"{sorted(EXPERT_FORMS)}")
        if limit is not None and activation != "swiglu":
            raise InvalidArgumentError(
                f"a clamp ({limit}) on experts of form {activation!r}")
        self.activation = activation
        self.limit = None if limit is None else float(limit)
        lo, hi = (0, num_experts) if held is None else map(int, held)
        if not 0 <= lo < hi <= num_experts:
            raise InvalidArgumentError(
                f"held experts [{lo}, {hi}) are not inside the router's "
                f"{num_experts}")
        if not 1 <= top_k <= num_experts:
            raise InvalidArgumentError(
                f"top_k {top_k} of {num_experts} experts")
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.lo, self.hi = lo, hi
        self.scaling, self.norm_topk = float(scaling), bool(norm_topk)
        self.norm_eps = None if norm_eps is None else float(norm_eps)

        def mat(*shape):
            return self.create_parameter(
                list(shape), attr=weight_attr, dtype=dtype,
                default_initializer=I.Normal(0.0, 0.02))
        n = hi - lo
        self.router = mat(hidden, num_experts)
        self.router_bias = self.create_parameter(
            [num_experts], dtype="float32", is_bias=True)
        if activation == "swiglu":
            self.w_gate = mat(n, hidden, width)
        self.w_up = mat(n, hidden, width)
        self.w_down = mat(n, width, hidden)
        # (only a SwiGLU takes a clamp, and only it is ever given one)
        extra = {"limit": limit} if activation == "swiglu" else {}
        self.shared = EXPERT_FORMS[activation](
            hidden, shared * width, weight_attr, dtype, **extra) \
            if shared else None
        self.last_counts = None

    def route(self, u2d):
        """(expert ids ``[N, k]``, weights ``[N, k]`` float32) of the
        tokens ``u2d [N, hidden]``."""
        s = jax.nn.sigmoid(jnp.einsum(
            "nh,he->ne", u2d, unwrap(self.router),
            preferred_element_type=jnp.float32))
        _, ids = jax.lax.top_k(s + unwrap(self.router_bias), self.top_k)
        w = jnp.take_along_axis(s, ids, axis=1)
        if self.norm_topk:
            total = w.sum(-1, keepdims=True)
            w = w / (jnp.maximum(total, 1e-20) if self.norm_eps is None
                     else total + self.norm_eps)
        return ids.astype(jnp.int32), w * self.scaling

    def forward(self, u, live=None):
        """``u [..., hidden]``; ``live [...]`` marks the tokens that are
        routed and that count in ``last_counts`` (all of them when None);
        the others get the shared expert's part only."""
        raw = unwrap(u)
        shape, dt = raw.shape, raw.dtype
        x = raw.reshape(-1, shape[-1])
        N, k, n = x.shape[0], self.top_k, self.hi - self.lo
        with jax.named_scope("experts"):
            with jax.named_scope("router"):
                ids, w = self.route(x)
            alive = jnp.ones((N,), bool) if live is None \
                else unwrap(live).reshape(-1)
            # a dead token (left padding, a dead slot row) is computed by
            # no expert: padding is one token many times over, and would
            # land on one expert all at once
            held = (ids >= self.lo) & (ids < self.hi) & alive[:, None]
            # sort the N*k assignments by held expert, absent ones last
            key = jnp.where(held, ids - self.lo, n).reshape(-1)
            order = jnp.argsort(key, stable=True)
            sizes = jnp.bincount(key, length=n + 1)[:n].astype(jnp.int32)
            tok = order // k                    # sorted row -> its token
            place = jnp.argsort(order)          # assignment -> sorted row
            f32, A = jnp.float32, N * k

            def inner(into):
                """An expert's hidden activation from ``into(w)``, the
                rows' product with an up-going matrix of each expert."""
                if self.activation == "relu2":
                    return jnp.square(
                        jax.nn.relu(into(unwrap(self.w_up)))).astype(dt)
                g, v = clamped(into(unwrap(self.w_gate)),
                               into(unwrap(self.w_up)), self.limit)
                return (jax.nn.silu(g) * v).astype(dt)

            def ragged():
                """Grouped products over the sorted rows, as they lie:
                any number of rows an expert."""
                xs = x[tok]
                y = jax.lax.ragged_dot(
                    inner(lambda w: jax.lax.ragged_dot(
                        xs, w, sizes, preferred_element_type=f32)),
                    unwrap(self.w_down), sizes, preferred_element_type=f32)
                return y[place]                            # [A, h], unsorted

            def padded(cap):
                """The same products with each expert's rows padded to
                ``cap``: one batched product over the held experts, which
                streams their weights once (the grouped product's custom
                call reaches 45% of the chip's bandwidth, and the weights
                are the cost: PERF.md section 6, PR 27)."""
                first = jnp.cumsum(sizes) - sizes
                at = jnp.minimum(first[:, None] + jnp.arange(cap)[None],
                                 A - 1)                        # [n, cap]
                xs = x[tok[at]]                                # [n, cap, h]
                y = jnp.einsum(
                    "ecf,efh->ech",
                    inner(lambda w: jnp.einsum(
                        "ech,ehf->ecf", xs, w, preferred_element_type=f32)),
                    unwrap(self.w_down), preferred_element_type=f32)
                # assignment a lies at sorted row p, in its expert's padded
                # row p - first[expert]
                e = jnp.minimum(key, n - 1)
                src = e * cap + jnp.clip(place - first[e], 0, cap - 1)
                return y.reshape(n * cap, -1)[src]             # [A, h]

            # even routing gives an expert A / E rows; pad to four times
            # that, at most 64 rows over it (64 for a 512-token chunk's
            # 4,096 assignments over 256 experts, the floor of 8 for a
            # 64-row step's 512; 64 for a 128-row step's 512 over 32 and
            # 128 for a chunk's 2,048).  An expert with more goes to a
            # second, wide tier of padded products: a token sends an expert
            # one row at most, so ``N`` rows (up to PADDED_ROWS_MAX) hold
            # whatever the router does; only a dispatch of more tokens than
            # that can overflow it, into the grouped products: nothing is
            # ever dropped
            even = -(-A // self.num_experts)
            cap = max(8, -(-min(4 * even, even + 64) // 8) * 8)
            if cap <= self.PADDED_ROWS_MAX:
                wide = min(self.PADDED_ROWS_MAX, -(-N // 8) * 8)
                caps = [cap] + ([wide] if wide > cap else [])
                branches = [functools.partial(padded, c) for c in caps]
                if caps[-1] < N:
                    branches.append(ragged)
                most = jnp.max(sizes)
                tier = sum((most > c).astype(jnp.int32) for c in caps)
                y = jax.lax.switch(jnp.minimum(tier, len(branches) - 1),
                                   branches)
            else:
                y = ragged()
            y = jnp.where(held.reshape(-1, 1), y * w.reshape(-1, 1), 0.0)
            out = y.reshape(N, k, shape[-1]).sum(1).astype(dt)
            self.last_counts = (
                alive.sum().astype(jnp.int32) * k,
                held.sum().astype(jnp.int32), jnp.max(sizes))
            if self.shared is not None:
                out = out + self.shared(x)
        out = out.reshape(shape)
        return Tensor(out) if isinstance(u, Tensor) else out
