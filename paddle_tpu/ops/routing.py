"""Mesh routing primitives: static-cap owner bucketing + all-to-all row moves.

Reference parity: the HeterPS sparse-table shards
(framework/fleet/heter_ps/hashtable.h — per-GPU hash shards, ids routed to
the owning card before the gather) and the PS shard rule
(distributed/ps — ``id % shard_num`` picks the server).  TPU-first: there
is no RPC hop; the table is ONE array row-partitioned over a mesh axis
(``P(axis, None)``) and the id routing is a ``lax.all_to_all`` inside
``shard_map``, entirely inside the jitted step — steady state moves only
ICI bytes, zero host bytes.

Layout contract (every helper here shares it):

  * a table of ``vocab`` logical rows over ``n`` shards stores
    ``rps = ceil(vocab / n)`` real rows **plus one scratch row** per shard
    — global shape ``[(rps + 1) * n, dim]``, sharded ``P(axis, None)``.
    The scratch row (local index ``rps``) absorbs every padded/sentinel
    request, so masked routing never needs a select against a real row
    (duplicate-index scatter hazards collapse onto a row nobody reads).
  * logical id ``i`` lives on shard ``i // rps`` at local row ``i % rps``;
    :func:`storage_index` maps logical ids to rows of the global array.
  * request vectors carry sentinel ``-1`` for padding; their length must
    divide by ``n`` (each shard owns a ``U / n`` slice of the requests).

Bucketing is STATIC-shape: each shard packs its requests into an
``[n, cap]`` send buffer grouped by owner shard.  ``cap`` defaults to the
whole per-shard slice (overflow impossible); a smaller cap shrinks the
routed buffers and the pack reports ``overflow`` so callers can re-run an
octave up (the device-dedup protocol of ``rec.wide_deep``).
"""
from __future__ import annotations

import functools
from typing import List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map as _shard_map

__all__ = [
    "PackPlan", "rows_per_shard", "storage_table_rows", "storage_index",
    "pad_requests", "pack_by_owner", "all_to_all_gather", "all_to_all_set",
    "all_to_all_apply_rule", "a2a_wire_bytes",
    "ExpertPlan", "moe_capacity", "expert_dispatch_plan",
    "all_to_all_experts", "local_experts", "moe_a2a_wire_bytes",
]


def rows_per_shard(vocab: int, n_shards: int) -> int:
    """Real rows each shard owns for a ``vocab``-row table."""
    return max(1, -(-int(vocab) // int(n_shards)))


def storage_table_rows(vocab: int, n_shards: int) -> int:
    """Global row count of the storage array (incl. per-shard scratch)."""
    return (rows_per_shard(vocab, n_shards) + 1) * int(n_shards)


def storage_index(ids, rps: int):
    """Logical id -> row of the ``[(rps+1)*n, D]`` storage array (works on
    numpy and jnp arrays; ids must be >= 0)."""
    owner = ids // rps
    return owner * (rps + 1) + (ids - owner * rps)


def pad_requests(n: int, n_shards: int, pad) -> int:
    """Octave-pad a request count AND round up to a shard multiple, so the
    padded vector splits evenly over the routing axis.  ``pad`` is the
    octave function (``pad_adaptive``-style); compile count stays bounded
    by the octave ladder."""
    base = max(int(n_shards), int(pad(max(1, n))))
    return -(-base // n_shards) * n_shards


class PackPlan(NamedTuple):
    """One shard's static-shape owner bucketing of its request slice."""

    send_ids: jnp.ndarray    # [n*cap] int32, grouped by owner, -1 padding
    pos: jnp.ndarray         # [u] int32 slot of each request (-1 = dropped)
    counts: jnp.ndarray      # [n] int32 per-owner request counts
    overflow: jnp.ndarray    # bool: some owner's count exceeded cap


def pack_by_owner(ids, *, n_shards: int, rps: int, cap: int,
                  with_send: bool = True) -> PackPlan:
    """Group a request slice by owner shard into a ``[n*cap]`` send buffer.

    ``ids`` is ``[u]`` int (sentinel ``< 0`` entries are excluded and never
    consume cap).  Pure jnp — usable outside any mesh for tests, and
    traced inside shard_map bodies for the real thing.  Callers that only
    need the slot/count bookkeeping (expert_dispatch_plan) pass
    ``with_send=False`` and get ``send_ids=None``/``overflow=None`` —
    the send-buffer scatter and the overflow reduction would otherwise
    be built and thrown away every step (such callers count drops from
    ``pos`` directly).
    """
    u = ids.shape[0]
    ids = ids.astype(jnp.int32)
    valid = ids >= 0
    # sentinels sort AFTER every real owner so the grouped prefix is dense
    owner = jnp.where(valid, ids // rps, n_shards)
    order = jnp.argsort(owner)
    so = owner[order]
    rank = jnp.arange(u, dtype=jnp.int32) - jnp.searchsorted(
        so, so, side="left").astype(jnp.int32)
    ok = (so < n_shards) & (rank < cap)
    # the +1 tail slot absorbs every dropped write (OOB-free scatter)
    slot = jnp.where(ok, so.astype(jnp.int32) * cap + rank, n_shards * cap)
    send = None
    if with_send:
        send = jnp.full((n_shards * cap + 1,), -1, jnp.int32).at[slot].set(
            ids[order])[:-1]
    pos = jnp.full((u,), -1, jnp.int32).at[order].set(
        jnp.where(ok, slot, -1).astype(jnp.int32))
    counts = jax.ops.segment_sum(valid.astype(jnp.int32),
                                 jnp.clip(owner, 0, n_shards - 1),
                                 num_segments=n_shards)
    overflow = (jnp.max(counts) > cap) if with_send else None
    return PackPlan(send, pos, counts, overflow)


def _scatter_to_slots(values, pos, n_slots):
    """Place per-request rows at their send-buffer slots (pos -1 dropped)."""
    width = values.shape[1:]
    buf = jnp.zeros((n_slots + 1,) + width, values.dtype)
    slot = jnp.where(pos >= 0, pos, n_slots)
    return buf.at[slot].set(values)[:-1]


def _local_rows(req, rps: int, axis: str):
    """Received request ids -> local row indices (scratch for sentinels)."""
    me = lax.axis_index(axis)
    return jnp.where(req >= 0, req - me * rps, rps)


# ---------------------------------------------------------------------------
# shard_map bodies
# ---------------------------------------------------------------------------

def _gather_body(ids_loc, *arrs_loc, axis, n, rps, cap):
    plan = pack_by_owner(ids_loc, n_shards=n, rps=rps, cap=cap)
    req = lax.all_to_all(plan.send_ids.reshape(n, cap), axis, 0, 0,
                         tiled=True)                  # [n, cap] asks for MY rows
    local = _local_rows(req, rps, axis)
    outs = []
    for a in arrs_loc:                                # each [rps+1, D]
        rows = a[local]                               # [n, cap, D]
        back = lax.all_to_all(rows, axis, 0, 0, tiled=True)
        flat = back.reshape((n * cap,) + back.shape[2:])
        got = flat[jnp.clip(plan.pos, 0, n * cap - 1)]
        outs.append(jnp.where((plan.pos >= 0).reshape(
            (-1,) + (1,) * (got.ndim - 1)), got, 0))
    ovf = lax.pmax(plan.overflow.astype(jnp.int32), axis)
    return (ovf,) + tuple(outs)


def _set_body(ids_loc, rows_and_tables, axis, n, rps, cap, n_arrays):
    rows_loc = rows_and_tables[:n_arrays]
    arrs_loc = rows_and_tables[n_arrays:]
    plan = pack_by_owner(ids_loc, n_shards=n, rps=rps, cap=cap)
    req = lax.all_to_all(plan.send_ids.reshape(n, cap), axis, 0, 0,
                         tiled=True)
    local = _local_rows(req, rps, axis)
    outs = []
    for a, r in zip(arrs_loc, rows_loc):
        buf = _scatter_to_slots(r, plan.pos, n * cap)
        recv = lax.all_to_all(buf.reshape((n, cap) + buf.shape[1:]),
                              axis, 0, 0, tiled=True)
        outs.append(a.at[local].set(recv))
    ovf = lax.pmax(plan.overflow.astype(jnp.int32), axis)
    return (ovf,) + tuple(outs)


def _apply_body(ids_loc, grads_loc, table_loc, *state_loc, axis, n, rps,
                cap, opt, hyper, state_names):
    plan = pack_by_owner(ids_loc, n_shards=n, rps=rps, cap=cap)
    req = lax.all_to_all(plan.send_ids.reshape(n, cap), axis, 0, 0,
                         tiled=True)
    local = _local_rows(req, rps, axis)
    gbuf = _scatter_to_slots(grads_loc, plan.pos, n * cap)
    grecv = lax.all_to_all(gbuf.reshape((n, cap) + gbuf.shape[1:]),
                           axis, 0, 0, tiled=True)
    flat_local = local.reshape(-1)
    rows = table_loc[flat_local]
    st = {k: s[flat_local] for k, s in zip(state_names, state_loc)}
    from ..distributed.ps.device_cache import apply_rule_device
    new_rows, new_st = apply_rule_device(
        opt, rows, st, grecv.reshape((n * cap,) + grecv.shape[2:]), **hyper)
    # scratch entries carry zero grads: the rule is a no-op there, and
    # duplicate scratch writes all land the same (irrelevant) value
    new_table = table_loc.at[flat_local].set(new_rows)
    new_state = tuple(state_loc[i].at[flat_local].set(new_st[k])
                      for i, k in enumerate(state_names))
    ovf = lax.pmax(plan.overflow.astype(jnp.int32), axis)
    return (ovf, new_table) + new_state


# ---------------------------------------------------------------------------
# public wrappers
# ---------------------------------------------------------------------------

def _route_params(mesh, axis: str, n_ids: int, cap: Optional[int]):
    n = int(dict(mesh.shape)[axis])
    if n_ids % n:
        raise ValueError(
            f"routing over axis {axis!r} (size {n}) needs the request "
            f"vector length ({n_ids}) divisible by the axis size — pad "
            f"with sentinel -1 (ops.routing.pad_requests)")
    u = n_ids // n
    cap = u if not cap else min(int(cap), u)
    return n, cap


def all_to_all_gather(arrays: Sequence, ids, *, mesh, axis: str, rps: int,
                      cap: Optional[int] = None):
    """Routed multi-array row lookup.

    ``arrays``: sharded ``[(rps+1)*n, D_i]`` storage arrays (rows +
    optimizer-state planes travel in ONE routed exchange of ids).
    ``ids``: ``[U]`` logical ids (sentinel -1), ``U % n == 0``.
    Returns ``(rows_list, overflow)`` — each ``[U, D_i]`` aligned with
    ``ids`` (zeros at sentinel slots), overflow an int32 scalar (>0 when
    some shard's per-owner count exceeded ``cap``).
    """
    n, cap = _route_params(mesh, axis, ids.shape[0], cap)
    body = functools.partial(_gather_body, axis=axis, n=n, rps=rps, cap=cap)
    fn = _shard_map(
        body, mesh=mesh,
        in_specs=(P(axis),) + (P(axis, None),) * len(arrays),
        out_specs=(P(),) + (P(axis, None),) * len(arrays),
        check_vma=False)
    out = fn(ids, *arrays)
    return list(out[1:]), out[0]


def all_to_all_set(arrays: Sequence, ids, rows: Sequence, *, mesh,
                   axis: str, rps: int, cap: Optional[int] = None):
    """Routed row import: write ``rows[i]`` (``[U, D_i]``, aligned with
    ``ids``) into each storage array at the owner shards.  Sentinel ids
    land on the owner's scratch row.  Returns ``(new_arrays, overflow)``.
    """
    n, cap = _route_params(mesh, axis, ids.shape[0], cap)

    def wrapped(ids_loc, *packed):
        return _set_body(ids_loc, packed, axis=axis, n=n, rps=rps, cap=cap,
                         n_arrays=len(arrays))

    fn = _shard_map(
        wrapped, mesh=mesh,
        in_specs=(P(axis),) + (P(axis, None),) * len(arrays)
        + (P(axis, None),) * len(arrays),
        out_specs=(P(),) + (P(axis, None),) * len(arrays),
        check_vma=False)
    out = fn(ids, *rows, *arrays)
    return list(out[1:]), out[0]


def all_to_all_apply_rule(table, state: dict, ids, grads, *, opt: str,
                          hyper: dict, mesh, axis: str, rps: int,
                          cap: Optional[int] = None):
    """Routed sparse-optimizer update: route ``(id, grad)`` pairs to the
    owner shards, apply the on-chip rule (``device_cache.DEVICE_RULES``)
    to the local rows + state, scatter in place.  The backward leg of the
    all-to-all lookup: updates touch ONLY the owning shard's slice.
    Returns ``(new_table, new_state, overflow)``."""
    n, cap = _route_params(mesh, axis, ids.shape[0], cap)
    names = tuple(sorted(state))
    body = functools.partial(_apply_body, axis=axis, n=n, rps=rps, cap=cap,
                             opt=opt, hyper=dict(hyper), state_names=names)
    fn = _shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P(axis, None)) + (P(axis, None),) * (1 + len(names)),
        out_specs=(P(),) + (P(axis, None),) * (1 + len(names)),
        check_vma=False)
    out = fn(ids, grads, table, *[state[k] for k in names])
    new_state = {k: out[2 + i] for i, k in enumerate(names)}
    return out[1], new_state, out[0]


# ---------------------------------------------------------------------------
# expert-parallel token routing (Mixture-of-Experts, ISSUE 14)
#
# The embedding movers above route *ids* to the shard that OWNS a table
# row; MoE routes *token vectors* to the shard that owns an expert,
# computes there, and routes the results back — the same static-cap
# owner bucketing with owner = expert, ``rps = 1`` (each "row" of the
# virtual table is one expert), and TWO all_to_alls per layer: tokens
# expert-ward, results token-ward.  Buffers are ``[E, cap]`` slots per
# source shard, so wire bytes scale with capacity, never with vocab or
# d_model beyond the row width.
# ---------------------------------------------------------------------------


def moe_capacity(tokens_per_group: int, top_k: int, n_experts: int,
                 capacity_factor: float) -> int:
    """Static per-(source shard, expert) slot count: each of the ``G``
    token groups (one per shard of the routing axis) may park at most
    ``cap`` of its ``tokens * k`` assignments on any one expert; the
    rest drop (residual passthrough).  ``capacity_factor`` 1.0 is the
    exactly-balanced budget; 1.25 is the usual head-room."""
    t = int(tokens_per_group) * int(top_k)
    return max(1, -(-int(t * float(capacity_factor)) // int(n_experts)))


class ExpertPlan(NamedTuple):
    """Per-group static dispatch plan (pure function of the expert ids,
    shared verbatim by the routed mover and the dense-dispatch
    control so both drop the same assignments)."""

    pos: jnp.ndarray      # [G, S] slot in the per-group [E*cap] buffer
    counts: jnp.ndarray   # [G, E] pre-drop per-expert demand
    dropped: jnp.ndarray  # [G] int32 assignments past capacity (dropped)


def expert_dispatch_plan(expert_ids, *, n_experts: int,
                         cap: int) -> ExpertPlan:
    """Owner-bucket each group's assignment slice ``expert_ids [G, S]``
    (entries in ``[0, E)``; sentinel ``< 0`` never consumes cap) into
    per-group ``[E * cap]`` send buffers — :func:`pack_by_owner` with
    owner = expert (``rps = 1``), vmapped over the group axis."""
    eids = jnp.asarray(expert_ids, jnp.int32)
    plan = jax.vmap(functools.partial(
        pack_by_owner, n_shards=int(n_experts), rps=1, cap=int(cap),
        with_send=False))(eids)
    kept = jnp.sum((plan.pos >= 0).astype(jnp.int32), axis=1)
    valid = jnp.sum((eids >= 0).astype(jnp.int32), axis=1)
    return ExpertPlan(plan.pos, plan.counts, valid - kept)


def _expert_body(x_loc, pos_loc, *w_loc, axis, n, n_experts, cap, expert_fn):
    """Per-shard leg of the routed expert exchange: scatter my ``S``
    token rows into the ``[E, cap]`` dispatch buffer, all_to_all so each
    shard receives every group's slots for ITS experts, run the local
    expert stack, all_to_all the results back, gather rows to token
    order (dropped slots read zero)."""
    E, eps = n_experts, n_experts // n
    tail = x_loc.shape[1:]                                # feature dims (D,)
    perm = (1, 0, 2) + tuple(range(3, 3 + len(tail)))
    pos = pos_loc.reshape(-1)
    buf = _scatter_to_slots(x_loc, pos, E * cap)          # [E*cap, D]
    buf = buf.reshape((n, eps * cap) + tail)
    recv = lax.all_to_all(buf, axis, 0, 0, tiled=True)    # [n, eps*cap, D]
    rows = recv.reshape((n, eps, cap) + tail).transpose(perm)
    rows = rows.reshape((eps, n * cap) + tail)            # [eps, n*cap, D]
    out = expert_fn(rows, *w_loc)                         # [eps, n*cap, D]
    out = out.reshape((eps, n, cap) + tail).transpose(perm)
    out = out.reshape((n, eps * cap) + tail)
    back = lax.all_to_all(out, axis, 0, 0, tiled=True)
    flat = back.reshape((E * cap,) + tail)
    got = flat[jnp.clip(pos, 0, E * cap - 1)]
    return jnp.where((pos >= 0).reshape((-1,) + (1,) * (got.ndim - 1)),
                     got, 0)


def all_to_all_experts(x_dup, pos, expert_params: Sequence, expert_fn, *,
                       mesh, axis: str, n_experts: int, cap: int):
    """Routed expert application: move token rows to the shard owning
    their expert, apply the expert stack there, move results back.

    ``x_dup``: ``[G*S, D]`` token rows (one row per (token, top-k slot)
    assignment; ``G`` = routing-axis size, each shard owns a contiguous
    ``S`` slice).  ``pos``: ``[G, S]`` dispatch plan from
    :func:`expert_dispatch_plan`.  ``expert_params``: stacked
    ``[E, ...]`` arrays sharded ``P(axis, ...)`` — each shard holds its
    ``E / n`` experts.  ``expert_fn(rows [e, m, D], *params_local)``
    must be expert-row-independent (a stacked FFN).  Returns
    ``[G*S, D]`` result rows aligned with ``x_dup`` (zeros at dropped
    slots).  Exactly TWO all_to_alls.
    """
    n = int(dict(mesh.shape)[axis])
    if n_experts % n:
        raise ValueError(
            f"expert routing over axis {axis!r} (size {n}) needs the "
            f"expert count ({n_experts}) divisible by the axis size")
    body = functools.partial(_expert_body, axis=axis, n=n,
                             n_experts=int(n_experts), cap=int(cap),
                             expert_fn=expert_fn)
    specs = tuple(P(axis, *([None] * (w.ndim - 1))) for w in expert_params)
    fn = _shard_map(body, mesh=mesh,
                    in_specs=(P(axis), P(axis, None)) + specs,
                    out_specs=P(axis), check_vma=False)
    return fn(x_dup, pos, *expert_params)


def local_experts(x_dup, pos, expert_params: Sequence, expert_fn, *,
                  n_experts: int, cap: int):
    """Meshless (single-shard) expert application — the same scatter →
    stacked-expert compute → gather as :func:`all_to_all_experts` with
    the two all_to_alls elided (``G = n = 1``); the decode/serving path
    when no expert axis is live."""
    E = int(n_experts)
    p = jnp.asarray(pos).reshape(-1)
    buf = _scatter_to_slots(x_dup, p, E * cap)
    rows = buf.reshape((E, cap) + buf.shape[1:])
    out = expert_fn(rows, *expert_params)
    flat = out.reshape((E * cap,) + out.shape[2:])
    got = flat[jnp.clip(p, 0, E * cap - 1)]
    return jnp.where((p >= 0).reshape((-1,) + (1,) * (got.ndim - 1)),
                     got, 0)


def moe_a2a_wire_bytes(n_experts: int, cap: int, dim: int, n_shards: int,
                       itemsize: int = 4) -> int:
    """Ring-model per-device interconnect bytes of one MoE layer's two
    all_to_alls (tokens out + results back): each leg moves the
    ``[E, cap, D]`` dispatch buffer, of which ``(n-1)/n`` crosses the
    wire.  Wire bytes scale with capacity (∝ tokens routed), never with
    vocab."""
    n = int(n_shards)
    if n <= 1:
        return 0
    leg = int(n_experts) * int(cap) * int(dim) * int(itemsize)
    return int(2 * leg * (n - 1) / n)


def a2a_wire_bytes(n_requests: int, dim: int, n_shards: int, cap: int,
                   itemsize: int = 4, n_planes: int = 1) -> int:
    """Ring-model per-device interconnect bytes of one routed gather:
    ids out + ids' worth of row planes back (and the same shape again for
    a set/update leg).  ``(n-1)/n`` of an all-to-all buffer actually
    crosses the wire."""
    n = int(n_shards)
    if n <= 1:
        return 0
    buf_ids = n * cap * 4
    buf_rows = n * cap * dim * itemsize * n_planes
    return int((buf_ids + buf_rows) * (n - 1) / n)
