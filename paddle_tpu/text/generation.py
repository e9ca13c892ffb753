"""Autoregressive decoding as a first-class workload: static-shape
KV-cache ``generate()`` compiled as exactly TWO executables.

The reference stack decodes through the contrib beam-search DSL
(incubate/decoder.py) — a host loop that re-dispatches per token and, on
a shape-keyed compiler, would recompile per token as the sequence grows.
The TPU-idiomatic form fixes every shape at compile time:

  * **ring KV cache** — per attention layer a ``(B, ceil(N/g), C, g*H)``
    buffer (``g`` heads side by side on the minor dim so that a token's
    K/V is contiguous on the device; ``g = 1`` for head_dim >= 128)
    written in place with ``lax.dynamic_update_slice`` at an explicit
    ``cache_position`` (nn/layer/transformer.py ``RingCache``); batch and
    cache length ``C`` are compile-time constants, validity is a mask;
  * **left-padded prompts** — prompts pad LEFT up to a prefill bucket
    ``P`` (FLAGS_decode_buckets), so every row's valid cache window is
    the contiguous ``[P - len_b, pos)`` and the last prefill column is
    the last prompt token for every row (no per-row gather);
  * **one prefill executable** per (batch, P, C): embeds the prompt,
    fills the cache, returns next-token logits;
  * **one decode executable** per (batch, C, steps, beam): the whole
    token loop is a single jitted ``lax.scan`` over the step body —
    greedy argmax, or beam search via ops.decode's ``beam_search_step`` +
    ``beam_parent_gather`` (the incubate BeamSearchDecoder reorder
    semantics) + ``gather_tree`` backtrace.

Every compile is recorded in the recompile ledger (site
``generate:<model>``, kinds ``generate_prefill`` / ``generate_decode``);
repeat calls at the same buckets are ledgered cache hits — the
zero-per-token-compile proof the tests and the serving engine assert.

Model contract: ``layer.init_cache(batch, max_len, dtype)`` and
``layer.forward_cached(input_ids, cache, cache_position,
start_positions)`` (text.models.gpt implements it over the ring-cache
transformer stack).
"""
from __future__ import annotations

import time
import weakref
from typing import Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Format, Layout

from ..framework import core
from ..framework import flags as _flags
from ..framework.enforce import (InvalidArgumentError, OutOfRangeError,
                                 PreconditionNotMetError)
from ..framework.functional import _bound_state, layer_state
from ..framework.tensor import Tensor, unwrap
from ..ops.decode import (_beam_search_step_fn, _gather_tree_fn,
                          beam_parent_gather)
from ..profiler import ledger as _ledger
from ..profiler import tracing as _tracing
from ..serving.bucketing import BucketLadder

__all__ = ["Generator", "generate", "agree_layouts"]

# the key suffix of a slot program compiled with the weights' layouts left
# to the compiler (``Generator.slot_execs``)
_FREE_WEIGHTS = (("arg:weights", "auto"),)


def _known(**facts):
    """The facts that are known: a ledger event's ``extra`` names only
    what the model has."""
    return {k: v for k, v in facts.items() if v is not None}


def _aval(a, fmt=None):
    """``a``'s abstract value; with ``fmt`` a program lowered from it takes
    that argument in that device format and no other."""
    return jax.ShapeDtypeStruct(tuple(a.shape), a.dtype, sharding=fmt)


def _default_layout(a, device=None):
    """The device layout a freshly made array of ``a``'s shape and dtype
    has on ``device`` (``a``'s own where none is given): on the TPU it
    depends on the shape."""
    d = device if device is not None else next(iter(a.devices()))
    return Layout.from_pjrt_layout(
        d.client.get_default_layout(a.dtype, tuple(a.shape), d))


def _own_format(a):
    """``a``'s device format where it does not lie in its shape's default
    layout (a weight that some Generator has relaid), else None."""
    fmt = a.format
    return None if fmt.layout == _default_layout(a) else fmt


def _as_placed(x):
    return x


def _relay(a, fmt):
    """``a`` in the device format ``fmt`` (the same bits, laid otherwise),
    landed.  ``jax.device_put(a, fmt)`` is a jitted identity with an
    output layout, and an executable that JAX's persistent compilation
    cache hands back labels its results with their shapes' DEFAULT
    layouts whatever it was compiled for (seen on the chip, PR 30: the
    bytes relaid, ``format`` saying they are not, every later call
    refused or, worse, fed transposed bytes).  So this program is never
    written there (three shapes a model, compiled in a moment), and a
    result that does not say what was asked for is refused here."""
    floor = "jax_persistent_cache_min_compile_time_secs"
    prev = getattr(jax.config, floor)
    jax.config.update(floor, 1e30)
    try:
        new = jax.block_until_ready(
            jax.jit(_as_placed, out_shardings=fmt)(a))
    finally:
        jax.config.update(floor, prev)
    if new.format.layout != fmt.layout:
        raise PreconditionNotMetError(
            f"a weight of shape {tuple(a.shape)} asked for in layout "
            f"{fmt.layout} came back as {new.format.layout}: the runtime "
            "does not keep the layouts of this program's results")
    return new


def agree_layouts(have, *chosen):
    """The rule by which programs that share ONE copy of the weights
    settle where each lies.  ``have``: the layout each array has;
    ``chosen``: per program, the layout its compile chose with the
    weights' layouts left free (None where the program does not read the
    weight: no wish).  A weight is relaid only where every program that
    reads it chose the same layout and the array has another; where they
    disagree it stays as it is (each program is then compiled for the
    layout the array has).  Returns ``(relaid, disagreed)``:
    ``{key: layout}`` and the keys left alone for disagreement."""
    relaid, disagreed = {}, []
    for k, h in have.items():
        wishes = {c[k] for c in chosen if c[k] is not None}
        if len(wishes) > 1:
            disagreed.append(k)
        elif wishes and wishes != {h}:
            relaid[k] = wishes.pop()
    return relaid, disagreed


def _rebuild_ring(layer, cache):
    """Raw plane tuples -> the per-layer cache namedtuples that
    ``layer.init_cache`` builds (RingCache, QuantRingCache, LatentCache,
    ...): each layer's cache is of the class the MODEL says it is, asked
    at a one-column size, so unlike layers may keep unlike planes."""
    types = [type(c) for c in layer.init_cache(1, 1)]
    return [cls(*(Tensor(p) for p in c)) for cls, c in zip(types, cache)]


def _apply_layer(layer, params, buffers, ids, cache, pos, start, rows=None,
                 row=None):
    """Raw-array incremental forward of ONE model: bind the state
    snapshot into the live layer and run its forward_cached under
    no-grad (the @to_static pure-fn pattern, jit/__init__.py).  Shared
    by the Generator (target) and the speculative draft.  ``rows`` (a
    slot step's live rows) reaches only a model that asks for it
    (``cached_forward_takes_rows``: one of its planes wraps inside a
    session, so a dead row must not write).  ``row`` (a chunk's joining
    row, a traced scalar) goes to a model that writes a batch-1 block
    into one row of the full planes (``cached_forward_takes_row``); the
    caller asks for it only of such a model."""
    ring = _rebuild_ring(layer, cache)
    kw = {} if row is None else {"row": row}
    if rows is not None and getattr(layer, "cached_forward_takes_rows",
                                    False):
        kw["write_rows"] = Tensor(rows)
    with core.no_grad_guard(), _bound_state(layer, params, buffers):
        logits, new_cache = layer.forward_cached(
            Tensor(ids), ring, pos, Tensor(start), **kw)
    return unwrap(logits), [tuple(unwrap(p) for p in c) for c in new_cache]


def _refuse_planes(kinds, who):
    raise InvalidArgumentError(
        f"{who} handles uniform K/V ring planes only; this model "
        f"keeps planes of kind {', '.join(map(repr, kinds))}, which it "
        "cannot cut (serve the model with that feature off)")


def require_kv_planes(kinds, who):
    """Refuse plane kinds that ``who`` cannot cut: the session store and
    the KV handoff park and ship UNIFORM K/V ring planes (kinds ``kv``,
    ``kv_int8``: every plane as long as the session, a column a token).
    A latent plane, a window plane shorter than the session, or a state
    without columns (``conv_state``, ``ssm_state``) is not theirs yet: a
    summed state has no column blocks to cut, only snapshots.  Nor is a
    K/V plane with another plane beside it (``kv+pooled_key``: the pooled
    keys are an entry every 16 columns, not a column a token)."""
    bad = sorted(k for k in set(kinds)
                 if not str(k).startswith("kv") or "+" in str(k))
    if bad:
        _refuse_planes(bad, who)


def require_prefix_planes(spec, columns, who):
    """Refuse a model whose planes ``who`` (the prefix cache) cannot cut
    into chunk-wide column blocks and restore into another row at another
    ``start``.  Decided from what the model says of its planes (``spec``
    = ``Generator.cache_spec(columns)``), not from their names: every
    layer must keep column planes as long as the session (``columns``),
    a column a token, written once (``wraps`` false), and no state beside
    them.  Such a column's content is a function of the token prefix and
    of ``column - start`` only: uniform K/V ring planes, a latent plane
    on its own, and a latent plane WITH its selector-key plane (a block
    then carries both; that the layer selects among the restored columns
    is the reader's business, not the block's: it scores the keys it
    finds there as it scores those a chunk wrote).  A window plane
    shorter than the session, a state without columns and a plane that
    keeps an entry every ``pooled_stride`` columns (pooled keys: a block
    of it is no function of the block's own tokens, its windows reach
    back over the block's edge) go on being refused, with
    :func:`require_kv_planes`'s message."""
    bad = sorted({str(s["kind"]) for s in spec
                  if int(s["columns"]) != int(columns) or s.get("wraps")
                  or int(s.get("pooled_stride") or 1) != 1})
    if bad:
        _refuse_planes(bad, who)


def _slice_row(cache, rowidx):
    """Row ``rowidx`` of every cache plane as a batch-1 cache view (a
    traced ``dynamic_slice`` — the row index is a runtime scalar)."""
    with jax.named_scope("cache_write/row_slice"):
        return [tuple(lax.dynamic_slice(p, (rowidx,) + (0,) * (p.ndim - 1),
                                        (1,) + p.shape[1:]) for p in c)
                for c in cache]


def _splice_row(cache, sub, rowidx):
    """Write a batch-1 cache back into row ``rowidx`` of the full
    planes — the single-row inverse of :func:`_slice_row`."""
    with jax.named_scope("cache_write/row_splice"):
        return [tuple(lax.dynamic_update_slice(
                          p, ps, (rowidx,) + (0,) * (p.ndim - 1))
                      for p, ps in zip(c, cs))
                for c, cs in zip(cache, sub)]




class Generator:
    """Compiled incremental decoding for one model.

    Owns the model's functional state snapshot and a cache of AOT
    executables keyed on (phase, batch, prompt-bucket, cache-bucket,
    steps, beam) — the warm-up set the serving engine enumerates.  All
    compiles are ledgered at ``site``; hits at warmed keys are ledgered
    cache hits (the zero-steady-state-compile invariant).
    """

    def __init__(self, layer, site: Optional[str] = None,
                 seq_buckets: Optional[Sequence[int]] = None,
                 max_len: Optional[int] = None, mesh=None,
                 param_specs=None):
        if not hasattr(layer, "forward_cached") \
                or not hasattr(layer, "init_cache"):
            raise InvalidArgumentError(
                f"{type(layer).__name__} does not implement the "
                "incremental-decoding contract (init_cache + "
                "forward_cached) — see text.models.GPTModel")
        if mesh is not None and type(self) is not Generator:
            raise InvalidArgumentError(
                "sharded decoding (mesh=) supports the plain Generator "
                f"only; {type(self).__name__} must run per-replica "
                "unsharded")
        layer.eval()
        self._layer = layer
        # sharded serving (serving/cluster): params placed per the
        # autoshard-derived specs, KV planes pinned to the cluster-wide
        # layout rule, all avals carrying shardings so the AOT programs
        # compile SPMD over the mesh.  mesh=None (the default) is the
        # single-device path, byte-identical to before.
        self._mesh = mesh
        self._param_specs = dict(param_specs or {})
        self._site = site or f"generate:{type(layer).__name__.lower()}"
        self._max_len = int(max_len if max_len is not None
                            else _flags.flag("decode_max_len"))
        spec = seq_buckets if seq_buckets is not None \
            else _flags.flag("decode_buckets")
        ladder = BucketLadder.from_flag(spec)
        # cache lengths cap at max_len; max_len itself is the top bucket
        self._seq_buckets = sorted(
            {b for b in ladder.buckets if b <= self._max_len}
            | {self._max_len})
        self._execs = {}
        # the device formats of the weights that do NOT lie in their
        # shape's default layout, keyed (position among the state
        # arguments, parameter name); every other weight, every buffer
        # and everything under a mesh is in the default one.  Settled
        # once: by the first program compiled against the state, or by
        # slot_execs(), which asks the compiler first.
        self._formats = None
        self._settled = False
        self.weights_layout = {"weights_relaid": 0, "weights_relaid_mb": 0.0,
                               "weights_layout_disagreed": 0}
        self.refresh_state()

    @property
    def site(self):
        return self._site

    @property
    def seq_buckets(self):
        return list(self._seq_buckets)

    def _models(self):
        """The models whose (params, buffers) pairs lead every generate
        program's arguments, in that order — the speculative subclass
        appends its draft."""
        return (self._layer,)

    @property
    def _params(self):
        return self._state[0]

    @property
    def _buffers(self):
        return self._state[1]

    def refresh_state(self):
        """Re-snapshot params/buffers from the live layer (after training
        or loading).  Shapes are unchanged, so no recompile — the fresh
        arrays just flow through the existing executables, each placed
        in the device format those were compiled for."""
        old = getattr(self, "_state", ())
        self._state = tuple(t for m in self._models()
                            for t in layer_state(m))
        if self._mesh is not None:
            self._state = (
                {n: jax.device_put(
                    v, self._sharding(self._param_specs.get(n)))
                 for n, v in self._state[0].items()},
                {n: jax.device_put(v, self._sharding())
                 for n, v in self._state[1].items()})
            return
        first = self._formats is None
        if first:
            self._formats = {}
        todo = {}
        for i, tree in enumerate(self._state):
            if i % 2:
                continue                    # buffers keep their layouts
            for n, a in tree.items():
                if i < len(old) and old[i].get(n) is a:
                    continue                # placed with the last snapshot
                own = _own_format(a)
                if first:
                    if own is not None:     # relaid by another Generator
                        self._formats[i, n] = own
                    continue
                want = self._formats.get((i, n))
                if (own and own.layout) != (want and want.layout):
                    todo[i, n] = want or Format(_default_layout(a),
                                                a.sharding)
        self._place(todo)

    def _place(self, formats):
        """Place the weights ``{(i, name): format}`` of the snapshot one
        at a time; the snapshot and the bound layer drop each original as
        they go and a copy has landed before the next begins, so the
        transient is one weight, not the model.  Returns the bytes
        placed."""
        if not formats:
            return 0
        bound = [dict(m.named_parameters()) for m in self._models()]
        nbytes = 0
        for (i, n), fmt in formats.items():
            new = _relay(self._state[i][n], fmt)
            self._state[i][n] = bound[i // 2][n]._value = new
            nbytes += int(new.nbytes)
        return nbytes

    # -- sharded-serving layout (serving/cluster/sharding.py) ----------------
    def _sharding(self, spec=None):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self._mesh,
                             spec if spec is not None else P())

    def kv_plane_sharding(self, shape):
        """The pinned ring-plane sharding at this generator's mesh (None
        on the single-device path) — handoff ingest and the decode
        avals both consult it, so cross-pool layouts always agree."""
        if self._mesh is None:
            return None
        from ..serving.cluster.sharding import kv_plane_spec
        return self._sharding(kv_plane_spec(shape, self._mesh))

    # -- bucketing -----------------------------------------------------------
    def prefill_bucket(self, length: int) -> int:
        """Smallest sequence bucket holding ``length`` prompt tokens."""
        for b in self._seq_buckets:
            if length <= b:
                return b
        raise OutOfRangeError(
            f"prompt length {length} exceeds the largest decode bucket "
            f"{self._seq_buckets[-1]} (FLAGS_decode_buckets / "
            "FLAGS_decode_max_len)")

    def cache_bucket(self, prefill: int, steps: int) -> int:
        """Smallest sequence bucket holding prefill + generated tokens."""
        need = int(prefill) + int(steps)
        for b in self._seq_buckets:
            if need <= b:
                return b
        raise OutOfRangeError(
            f"prompt bucket {prefill} + {steps} new tokens = {need} "
            f"exceeds FLAGS_decode_max_len={self._max_len}")

    # -- the two pure programs ----------------------------------------------
    def _apply_cached(self, params, buffers, ids, cache, pos, start,
                      rows=None, row=None):
        return _apply_layer(self._layer, params, buffers, ids, cache, pos,
                            start, rows, row)

    def _init_cache_raw(self, B, C):
        ring = self._layer.init_cache(B, C)
        return [tuple(unwrap(p) for p in c) for c in ring]

    def cache_spec(self, C):
        """Per layer, what the model says its cache planes are at cache
        length ``C``: ``kind`` (the cache class's), ``heads_per_lane_row``
        (``g`` of ``gen_ring_cache``), ``columns`` of its planes (0: a
        per-row state that a feed overwrites in place),
        whether they ``wrap`` inside a session, its ``window``,
        ``select_top`` and, for a layer that selects, the widths its
        search may take (``select_widths``).  The model's ``cache_spec``;
        a model that has none is described from the classes its
        ``init_cache`` builds."""
        fn = getattr(self._layer, "cache_spec", None)
        if fn is not None:
            return list(fn(int(C)))
        return [{"kind": getattr(type(c), "kind", type(c).__name__),
                 "heads_per_lane_row": 1, "columns": int(C), "wraps": False,
                 "window": None, "select_top": None}
                for c in self._layer.init_cache(1, 1)]

    def plane_kinds(self):
        return sorted({str(s["kind"]) for s in self.cache_spec(1)})

    def kv_heads_per_lane_row(self):
        """How many heads share a row of the minor dimension of this
        model's ring planes (``g`` of ``gen_ring_cache``; 1 = unpacked
        planes: head_dim >= 128, the int8 cache, a latent plane), as the
        model's first layer that keeps columns describes its cache, so
        the ledger's ``generate_step`` / ``generate_chunk`` events and
        ``SlotLoop.stats()`` say which layout a run used."""
        spec = [s for s in self.cache_spec(1) if s["columns"]]
        return int(spec[0]["heads_per_lane_row"]) if spec else 1

    def chunk_row(self):
        """How the prefill chunk reaches its joining row: ``"in_place"``
        for a model whose cached forward takes the row
        (``cached_forward_takes_row``: the block is written into the full
        donated planes at ``(row, 0, pos, 0)`` and attends that row
        there), ``"sliced"`` for every other (the row is cut out of every
        plane, run at batch 1 and spliced back).  Decided when the
        program is traced: a fact of the program, in the ledger's
        ``generate_chunk`` event and in ``SlotLoop.stats()``."""
        return "in_place" if getattr(
            self._layer, "cached_forward_takes_row", False) else "sliced"

    def step_read(self, C):
        """The form the step's one-query read of the model's K/V ring
        planes takes at cache length ``C``
        (``nn.functional.attention.decode_read_form``, from the backend,
        the mesh and the planes as they would be traced): ``"per_row"``,
        one kernel over each generating row's own column blocks, or
        ``"span"``, the XLA loops over the union span; ``"mixed"`` where
        the layers' planes decide differently; None for a model whose
        column planes are not all plain ``kv`` planes (an int8 cache, a
        latent plane, a layer that passes a mask of chosen blocks).  A
        fact of the program, in the ledger's ``generate_step`` event and
        in ``SlotLoop.stats()``."""
        from ..nn.functional.attention import decode_block, decode_read_form
        layers = [(s, c) for s, c in zip(self.cache_spec(C),
                                         self._slot_cache_avals(1, C))
                  if int(s["columns"])]
        if not layers or any(s["kind"] != "kv" for s, _ in layers):
            return None
        forms = {decode_read_form(c[0].shape, c[0].dtype, decode_block(C))
                 for _, c in layers}
        return forms.pop() if len(forms) == 1 else "mixed"

    def latent_form(self, T):
        """The form a model's cached attention over latent planes takes
        for the width ``T`` of the block a program is traced for
        (``"absorbed"`` / ``"per_head"``, the model's ``latent_form``),
        None for a model that keeps no latent plane: a fact of the
        program, in the ledger's ``generate_step`` / ``generate_chunk``
        events and, per program, in ``SlotLoop.stats()``."""
        fn = getattr(self._layer, "latent_form", None)
        return None if fn is None else fn(int(T))

    def selector_widths(self, C):
        """The widths the search of the model's column selector may take
        at cache length ``C`` (its selecting layers' ``select_widths``;
        the search goes over the narrowest that holds the dispatch's live
        span; a list per distinct rule where the layers differ), None for
        a model in which no layer selects: a fact of the programs, in the
        ledger's ``generate_step`` / ``generate_chunk`` events."""
        rules = sorted({tuple(s["select_widths"]) for s in self.cache_spec(C)
                        if s.get("select_widths") is not None})
        if len(rules) == 1:
            return list(rules[0])
        return [list(r) for r in rules] or None

    def decode_count_names(self):
        """Names of the int32 counts the model's cached forward leaves
        behind (``decode_counts``), which the slot programs hand back
        with the step's tokens and beside the chunk's logits; ``()`` for
        a model that counts nothing."""
        return tuple(getattr(self._layer, "decode_count_names", ()))

    def _decode_counts(self):
        fn = getattr(self._layer, "decode_counts", None)
        return None if fn is None else fn()

    def _build_prefill(self, B, P, C):
        def prefill(params, buffers, ids, start):
            cache0 = self._init_cache_raw(B, C)
            logits, cache = self._apply_cached(
                params, buffers, ids, cache0, jnp.int32(0), start)
            # left-padding: the last column is the last prompt token for
            # EVERY row — one static slice, no per-row gather
            return cache, logits[:, -1, :].astype(jnp.float32)
        return prefill

    def _build_decode(self, B, C, steps, beam, end):
        # end == -1 encodes "no eos": argmax tokens are always >= 0, so
        # the finished mask never trips and the one program serves both
        apply = self._apply_cached

        def greedy(params, buffers, cache, logits0, start, pos0):
            def step(carry, _):
                cache, logits, pos, finished = carry
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                tok = jnp.where(finished, jnp.int32(end), tok)
                finished = finished | (tok == end)
                nlogits, ncache = apply(params, buffers, tok[:, None],
                                        cache, pos, start)
                return (ncache, nlogits[:, 0].astype(jnp.float32),
                        pos + 1, finished), tok

            init = (cache, logits0, pos0, jnp.zeros((B,), bool))
            _, toks = lax.scan(step, init, None, length=steps)
            return jnp.transpose(toks)                    # [B, steps]

        def beam_decode(params, buffers, cache, logits0, start, pos0):
            K = beam
            cache = [tuple(jnp.repeat(p, K, axis=0) for p in c)
                     for c in cache]
            start_k = jnp.repeat(start, K, axis=0)
            logp0 = jax.nn.log_softmax(logits0.astype(jnp.float32), axis=-1)
            V = logp0.shape[-1]
            # only beam 0 live at t=0 (the incubate BeamSearchDecoder
            # -inf init), so step 1 expands ONE beam
            scores0 = jnp.broadcast_to(
                jnp.where(jnp.arange(K) > 0, -1e9, 0.0), (B, K)
            ).astype(jnp.float32)
            logp0 = jnp.broadcast_to(logp0[:, None, :], (B, K, V))
            pre0 = jnp.full((B, K), end - 1, jnp.int32)   # != end: all live

            def step(carry, _):
                cache, pre_ids, scores, logp, pos = carry
                ids_t, scores_t, parents_t = _beam_search_step_fn(
                    pre_ids, scores, logp, beam_size=K, end_id=end,
                    is_accumulated=True)
                # reorder beam-parallel cache rows by the selected
                # parents — the incubate BeamSearchDecoder gather
                cache = [tuple(beam_parent_gather(p, parents_t) for p in c)
                         for c in cache]
                tok = ids_t.reshape(B * K)[:, None]
                nlogits, ncache = apply(params, buffers, tok, cache, pos,
                                        start_k)
                nlogp = jax.nn.log_softmax(
                    nlogits[:, 0].astype(jnp.float32), axis=-1
                ).reshape(B, K, V)
                return (ncache, ids_t, scores_t, nlogp, pos + 1), \
                    (ids_t, parents_t)

            init = (cache, pre0, scores0, logp0, pos0)
            (_, _, scores, _, _), (all_ids, all_parents) = lax.scan(
                step, init, None, length=steps)
            paths = _gather_tree_fn(all_ids, all_parents)  # [steps, B, K]
            return jnp.transpose(paths, (1, 2, 0)), scores

        return greedy if beam == 1 else beam_decode

    # -- slot-loop programs (serving/slots.py) -------------------------------
    def _build_step(self, S, C, end):
        """ONE greedy token step over ``S`` slot rows — the body of the
        run-to-completion scan, hoisted so the HOST owns the loop:
        requests retire/join between dispatches with no recompile and no
        cache copy.  Inactive rows' logits pass through unchanged so a
        freshly activated row is never clobbered; their CACHE write is
        deliberately NOT masked — the cache argument is donated and a
        per-row blend would force XLA to preserve the donated planes
        (a full-plane copy every step, measured ~4x the step cost on
        CPU).  Instead the host guarantees every column a step writes
        for an inactive row is dead: it lies inside the row's pending
        chunk window [act-Pb, act) and the slot loop dispatches chunk k
        only after it has DISPATCHED the step at position act-n+k (see
        slots._dispatch_chunks; the device runs its programs in the
        order they were dispatched), so the chunk rewrite always lands
        after the last garbage write.  Emitted tokens for active rows
        are bit-identical to the scanned decode's per-row stream (row
        independence + the PR-7 batch/bucket invariance).

        ``finished`` never visits the host: the loop hands each step the
        array the step before it returned, while that step may still be
        running (the loop keeps one step in flight).  What the host knows
        and the device does not, it says in two masks: ``active`` (the
        rows it counts as generating; every other row reads as finished)
        and ``joined`` (of those, the rows that activated since the last
        step: their flag is cleared).  What the device knows first, the
        step acts on itself: a row that took the end token at the step
        before is still ``active`` to a host that has not read that
        token yet, and takes no part — its token is the end token, its
        logits pass through, a state without columns is kept, and its
        write lands in a dead column like any inactive row's."""
        apply = self._apply_cached

        def step(params, buffers, cache, logits, start, finished, active,
                 joined, pos):
            with jax.named_scope("head/sample"):
                finished = (finished | ~active) & ~joined
                live = active & ~finished
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                tok = jnp.where(finished, jnp.int32(end), tok)
                finished = finished | (tok == end)
                # rows that are not live may carry garbage argmax (end ==
                # -1 included) — clamp their fed token; their write lands
                # in a dead column
                fed = jnp.where(live, tok, jnp.int32(0))
            nlogits, ncache = apply(params, buffers, fed[:, None], cache,
                                    pos, start, live)
            with jax.named_scope("head/sample"):
                nlog = jnp.where(live[:, None],
                                 nlogits[:, 0].astype(jnp.float32), logits)
            counts = self._decode_counts()
            if counts is not None:
                # the model's counts ride the token read-back: [S + n]
                tok = jnp.concatenate([tok, counts.astype(jnp.int32)])
            return ncache, nlog, finished, tok

        return step

    def _build_chunk(self, S, T, C):
        """One Sarathi-style prefill chunk: forward ``T`` prompt tokens
        of ONE joining row at the block position ``pos``, writing its
        K/V block without touching any other slot's row.  The forward
        runs at batch 1 — rows are independent in forward_cached, so the
        batch-1 compute is bit-identical to that row's lane in a batched
        dispatch, and a chunk costs the row's own FLOPs instead of
        ``S``× them.  How it reaches the row is decided HERE, when the
        program is traced, from what the model declares
        (:meth:`chunk_row`): a model whose cached forward takes the row
        is handed the full donated planes and ``rowidx``, writes its
        ``[1, G, T, L]`` block at ``(rowidx, 0, pos, 0)`` in place and
        reads that row's columns from the plane; for every other model
        the row is cut out of every plane (``_slice_row``), run as a
        batch-1 cache and spliced back whole (``_splice_row``).  Both
        write exactly the block's columns of that row, so the ordering
        rule of :meth:`_build_step` holds for either.  Returns the
        chunk's last-column logits — the final chunk's are the
        activation logits (= the prefill executable's ``logits[:, -1]``
        for the same prompt)."""
        apply = self._apply_cached
        in_place = self.chunk_row() == "in_place"

        def chunk(params, buffers, cache, ids, start, rowidx, pos):
            if in_place:
                logits, ncache = apply(params, buffers, ids, cache, pos,
                                       start, row=rowidx)
            else:
                sub = _slice_row(cache, rowidx)
                logits, nsub = apply(params, buffers, ids, sub, pos, start)
                ncache = _splice_row(cache, nsub, rowidx)
            with jax.named_scope("head"):
                out = (ncache, logits[0, -1, :].astype(jnp.float32))
            counts = self._decode_counts()
            return out if counts is None else out + (counts,)

        return chunk

    def _require_unsharded_slots(self):
        if self._mesh is not None:
            raise InvalidArgumentError(
                "slot decode (FLAGS_decode_slots) runs per-replica "
                "unsharded — drop the mesh or the slot loop")

    def _step_program(self, S, C, eos_token_id=None):
        """What ``_compile_slot`` needs for the slot step over ``S`` slots
        at cache bucket ``C``: ``(key, ledger kind, program, non-state
        avals, extra, donated arguments)``.  A plain tuple, so that the
        AST lint's resolver (analysis/ast_lint.py) follows the program
        from its builder to ``jax.jit``."""
        self._require_unsharded_slots()
        end = -1 if eos_token_id is None else int(eos_token_id)
        return (self._key("step3", S, None, C, 1, 1, end), "generate_step",
                self._build_step(S, C, end), self.step_avals(S, C),
                {"slots": S, "cache": C, "eos": end,
                 "kv_heads_per_lane_row": self.kv_heads_per_lane_row(),
                 **_known(latent_form=self.latent_form(1),
                          selector_widths=self.selector_widths(C),
                          step_read=self.step_read(C))},
                (2,))

    def _chunk_program(self, S, T, C):
        """The same for the prefill chunk of width ``T`` (ledger kind
        ``generate_chunk``)."""
        self._require_unsharded_slots()
        return (self._key("chunk2", S, T, C, None, None), "generate_chunk",
                self._build_chunk(S, T, C), self.chunk_avals(S, T, C),
                {"slots": S, "chunk": T, "cache": C,
                 "kv_heads_per_lane_row": self.kv_heads_per_lane_row(),
                 "chunk_row": self.chunk_row(),
                 **_known(latent_form=self.latent_form(T),
                          selector_widths=self.selector_widths(C))},
                (2,))

    def step_exec(self, S, C, eos_token_id=None):
        """AOT single-step decode executable over ``S`` slots at cache
        bucket ``C`` — the slot loop's hot dispatch, for the weights as
        they lie (``slot_execs`` is the pair that settles where)."""
        return self._compile_slot(self._step_program(S, C, eos_token_id))

    def chunk_exec(self, S, T, C):
        """AOT prefill-chunk executable over ``S`` slots at chunk width
        ``T`` and cache bucket ``C``, for the weights as they lie."""
        return self._compile_slot(self._chunk_program(S, T, C))

    def _compile_slot(self, prog, free=False, events=None):
        """Compile a slot program: for the weights as they lie, or with
        their layouts left ``free``; its ledger event says how many of
        them ``slot_execs`` relaid."""
        key, kind, fn, avals, extra, donate = prog
        return self._compile(key + _FREE_WEIGHTS if free else key, kind, fn,
                             avals, {**extra, **self.weights_layout},
                             donate_argnums=donate, free=free, events=events)

    def slot_execs(self, S, T, C, eos_token_id=None):
        """The slot loop's ``(step, chunk)`` pair, with every weight
        lying the way both programs contract over it.

        The two run thousands of times a second over ONE copy of the
        weights, so a weight that a program wants in another layout than
        it has is transposed again in every run.  Where nothing is
        compiled against the state yet, both are first compiled with the
        layouts of the parameters left to the compiler (buffers, cache
        planes and every other argument keep theirs) and
        :func:`agree_layouts` decides: a weight both want alike, and
        otherwise than it lies, is relaid once, here; the rest stay, and
        a program that wanted one of those otherwise is compiled again
        for the weights as they lie.  A program whose every wish was
        granted IS the final one: no further compile, and a warm start
        reads the same decision off the loaded executables' formats.
        Every program compiled afterwards takes the state as placed
        (``_state_avals``)."""
        progs = (self._step_program(S, C, eos_token_id),
                 self._chunk_program(S, T, C))
        if self._settled:
            return tuple(self._compile_slot(p) for p in progs)
        events = []
        free = [self._compile_slot(p, free=True, events=events)
                for p in progs]
        n = len(self._state)
        chosen = [{(i, name): f.layout
                   for i, tree in enumerate(ex.input_formats[0][:n])
                   if not i % 2 for name, f in tree.items()} for ex in free]
        have = self._held_layouts()
        relaid, disagreed = agree_layouts(have, *chosen)
        placed = {(i, name): Format(lay, self._state[i][name].sharding)
                  for (i, name), lay in relaid.items()}
        nbytes = self._place(placed)
        # from here on the state's formats are fixed for this Generator's life
        self._formats.update(placed)
        self._settled = True
        self.weights_layout = {
            "weights_relaid": len(relaid),
            "weights_relaid_mb": round(nbytes / 1e6, 3),
            "weights_layout_disagreed": len(disagreed)}
        for ev in events:
            ev.update(self.weights_layout)
        out = []
        for p, ex, wish in zip(progs, free, chosen):
            if all(wish[k] in (None, relaid.get(k, have[k])) for k in have):
                self._execs[p[0]] = ex
            else:
                ex = self._compile_slot(p)
            out.append(ex)
        return tuple(out)

    def _held_layouts(self):
        """``{(i, name): layout}`` of every weight of the snapshot as it
        lies on the device."""
        return {(i, name): a.format.layout
                for i, tree in enumerate(self._state) if not i % 2
                for name, a in tree.items()}

    def step_avals(self, S, C):
        """Non-state avals of the slot step program (cache, logits,
        start, finished, active, joined, pos) — shared by the AOT compile
        and the serving graph-lint admission gate."""
        vocab = self._vocab_size()
        return (self._slot_cache_avals(S, C),
                jax.ShapeDtypeStruct((S, vocab), jnp.float32),
                jax.ShapeDtypeStruct((S,), jnp.int32),
                jax.ShapeDtypeStruct((S,), jnp.bool_),
                jax.ShapeDtypeStruct((S,), jnp.bool_),
                jax.ShapeDtypeStruct((S,), jnp.bool_),
                jax.ShapeDtypeStruct((), jnp.int32))

    def chunk_avals(self, S, T, C):
        """Non-state avals of the single-row prefill-chunk program
        (cache, ids [1, T], start [1], row index, block position)."""
        return (self._slot_cache_avals(S, C),
                jax.ShapeDtypeStruct((1, T), jnp.int32),
                jax.ShapeDtypeStruct((1,), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32))

    def _slot_cache_avals(self, S, C):
        raw = jax.eval_shape(lambda: self._init_cache_raw(S, C))
        return [tuple(jax.ShapeDtypeStruct(p.shape, p.dtype) for p in c)
                for c in raw]

    def slot_cache_avals_all(self, S, C):
        """Abstract values of the FULL slot-cache tree the step program
        donates — every plane the KV data movers (pull/push below) must
        cover.  The speculative subclass widens this to its
        (target, draft) cache pair."""
        return self._slot_cache_avals(S, C)

    def _block_avals(self, S, T, C):
        """Avals of one T-column single-row block of the slot cache:
        every plane is 4-D with the column dim at axis 2 (packed bf16
        k/v ``(B, ceil(N/g), C, g*H)``, int8 k/v ``(B, N, C, H)`` and
        their f32 scales ``(B, N, C, 1)`` alike), so a block is the
        same tree with shape ``(1, shape[1], T, shape[3])`` — the data
        movers never need to know how heads lie inside a plane."""
        return jax.tree_util.tree_map(
            lambda p: jax.ShapeDtypeStruct(
                (1, p.shape[1], T, p.shape[3]), p.dtype),
            self.slot_cache_avals_all(S, C))

    # -- KV data movers (prefix/session cache, serving/prefix_cache.py +
    #    serving/sessions.py): pure cache-tree slicing programs, compiled
    #    once at SlotLoop construction like the step/chunk executables --
    def pull_block_exec(self, S, T, C):
        """AOT read of one T-column block of one slot row, every plane
        (ledger kind ``kv_pull_block``): ``(cache, rowidx, base) ->
        block tree``.  Read-only — the cache is NOT donated, so the live
        session planes stay valid; the returned block is the device
        segment the prefix cache publishes."""
        if self._mesh is not None:
            raise InvalidArgumentError(
                "the prefix/session KV cache runs per-replica unsharded "
                "(FLAGS_decode_slots) — drop the mesh")
        key = self._key("pull_block", S, T, C, None, None)

        def pull(cache, rowidx, base):
            zero = jnp.int32(0)
            return jax.tree_util.tree_map(
                lambda p: lax.dynamic_slice(
                    p, (rowidx, zero, base, zero),
                    (1, p.shape[1], T, p.shape[3])), cache)

        avals = (self.slot_cache_avals_all(S, C),
                 jax.ShapeDtypeStruct((), jnp.int32),
                 jax.ShapeDtypeStruct((), jnp.int32))
        return self._compile_data(key, "kv_pull_block", pull, avals,
                                  {"slots": S, "chunk": T, "cache": C})

    def push_block_exec(self, S, T, C):
        """AOT write of one T-column block into one slot row, every
        plane (ledger kind ``kv_push_block``): ``(cache, block, rowidx,
        base) -> cache``.  The cache is donated exactly like the step
        program's, so a restore is an in-place column write, not a
        full-plane copy; the block argument is not donated and stays
        valid (a pinned prefix block can restore into many rows)."""
        if self._mesh is not None:
            raise InvalidArgumentError(
                "the prefix/session KV cache runs per-replica unsharded "
                "(FLAGS_decode_slots) — drop the mesh")
        key = self._key("push_block", S, T, C, None, None)

        def push(cache, block, rowidx, base):
            zero = jnp.int32(0)
            return jax.tree_util.tree_map(
                lambda p, b: lax.dynamic_update_slice(
                    p, b, (rowidx, zero, base, zero)), cache, block)

        avals = (self.slot_cache_avals_all(S, C),
                 self._block_avals(S, T, C),
                 jax.ShapeDtypeStruct((), jnp.int32),
                 jax.ShapeDtypeStruct((), jnp.int32))
        return self._compile_data(key, "kv_push_block", push, avals,
                                  {"slots": S, "chunk": T, "cache": C},
                                  donate_argnums=(0,))

    def pull_row_exec(self, S, C):
        """AOT read of one slot row's FULL-width planes (ledger kind
        ``kv_pull_row``): ``(cache, rowidx) -> row tree``.  One dispatch
        per session park — the host slices the validity window
        ``[start, pos)`` out of the fetched row."""
        if self._mesh is not None:
            raise InvalidArgumentError(
                "the prefix/session KV cache runs per-replica unsharded "
                "(FLAGS_decode_slots) — drop the mesh")
        key = self._key("pull_row", S, None, C, None, None)

        def pull(cache, rowidx):
            zero = jnp.int32(0)
            return jax.tree_util.tree_map(
                lambda p: lax.dynamic_slice(
                    p, (rowidx, zero, zero, zero),
                    (1,) + tuple(p.shape[1:])), cache)

        avals = (self.slot_cache_avals_all(S, C),
                 jax.ShapeDtypeStruct((), jnp.int32))
        return self._compile_data(key, "kv_pull_row", pull, avals,
                                  {"slots": S, "cache": C})

    def put_logits_row_exec(self, S):
        """AOT write of one row into the slot step's logits (ledger kind
        ``logits_put_row``): ``(logits [S, V] f32, row [V] f32, rowidx)
        -> logits``.  How the slot loop activates a row: the final
        chunk's logits go from the chunk program's output into the step
        program's input without leaving the device.  ``logits`` is
        donated, so the write is in place and no second ``[S, V]``
        buffer lives beside the planes."""
        self._require_unsharded_slots()
        vocab = self._vocab_size()
        key = self._key("put_logits_row", S, None, None, None, None)

        def put(logits, row, rowidx):
            return lax.dynamic_update_slice(logits, row[None, :],
                                            (rowidx, jnp.int32(0)))

        avals = (jax.ShapeDtypeStruct((S, vocab), jnp.float32),
                 jax.ShapeDtypeStruct((vocab,), jnp.float32),
                 jax.ShapeDtypeStruct((), jnp.int32))
        return self._compile_data(key, "logits_put_row", put, avals,
                                  {"slots": S, "vocab": vocab},
                                  donate_argnums=(0,))

    def init_slot_cache(self, S, C):
        """Zero device planes for a fresh slot session — never compiled
        as a program of its own (validity windows make the init values
        unobservable; zeros match the in-graph prefill init)."""
        raw = jax.eval_shape(lambda: self._init_cache_raw(S, C))
        return [tuple(jnp.zeros(tuple(p.shape), p.dtype) for p in c)
                for c in raw]

    # -- AOT compile + ledger ------------------------------------------------
    def _key(self, phase, B, P, C, steps, beam, end=None):
        # the cache storage dtype is part of the program: flipping
        # FLAGS_kv_cache_dtype recompiles (ledgered, loud under
        # serving_strict) instead of silently serving stale planes
        kv = str(_flags.flag("kv_cache_dtype")).lower()
        return tuple([("arg:phase", phase), ("arg:batch", B),
                      ("arg:kv", kv)]
                     + ([("arg:mesh", self._mesh_label())]
                        if self._mesh is not None else [])
                     + ([("arg:prompt", P)] if P is not None else [])
                     + [("arg:cache", C)]
                     + ([("arg:steps", steps), ("arg:beam", beam),
                         ("arg:eos", end)]
                        if steps is not None else []))

    def _mesh_label(self):
        if self._mesh is None:
            return ""
        return "x".join(f"{a}{n}" for a, n in dict(self._mesh.shape).items())

    def _state_avals(self):
        """Avals of the leading state arguments every generate program
        takes: a (params, buffers) pair per model of ``_models()``.  A
        weight that lies otherwise than in its shape's default layout
        carries its format, so a program lowered from these takes the
        state as it is placed.  Under a mesh the avals carry the param
        shardings, so the AOT programs lower SPMD."""
        if self._mesh is not None:
            return ({n: jax.ShapeDtypeStruct(
                        tuple(a.shape), a.dtype,
                        sharding=self._sharding(self._param_specs.get(n)))
                     for n, a in self._params.items()},
                    {n: jax.ShapeDtypeStruct(tuple(a.shape), a.dtype,
                                             sharding=self._sharding())
                     for n, a in self._buffers.items()})
        return tuple({n: _aval(a, self._formats.get((i, n)))
                      for n, a in tree.items()}
                     for i, tree in enumerate(self._state))

    def _state_args(self):
        return self._state

    def _program_identity(self):
        """Restart-stable architecture identity for the persistent
        executable cache: layer class + config + state avals + the ring
        planes' packing (an executable stored by a build with another
        plane layout takes other cache shapes and must not load).
        Weights are runtime arguments, so two processes decoding the
        same architecture share executables regardless of parameter
        values — the cold host compiles the grid, every warm host loads
        it."""
        cfg = getattr(self._layer, "config", None)
        cfg_r = repr(sorted(vars(cfg).items())) \
            if cfg is not None and hasattr(cfg, "__dict__") else repr(cfg)
        avals = jax.tree_util.tree_map(
            lambda a: (tuple(a.shape), str(a.dtype)), self._state_avals())
        mesh_id = () if self._mesh is None else (
            self._mesh_label(),
            tuple(sorted((n, repr(s))
                         for n, s in self._param_specs.items())))
        return ("generator", type(self._layer).__name__, cfg_r,
                repr(avals), self._max_len, tuple(self._seq_buckets),
                ("kv_heads_per_lane_row", self.kv_heads_per_lane_row()),
                # a model that takes the live rows compiles another step
                *((("step_passes_rows", True),) if getattr(
                    self._layer, "cached_forward_takes_rows", False)
                  else ()),
                # so does one whose chunk writes its row in place
                *((("chunk_row", "in_place"),)
                  if self.chunk_row() == "in_place" else ()),
                # so does one whose step reads its planes row by row
                *((("step_read", "per_row"),)
                  if self.step_read(self._max_len) == "per_row" else ()),
                # the form of a latent model's cached attention is picked
                # when a program is traced (a step's, the widest block's):
                # an executable stored under another choice must not load
                *((("latent_form", self.latent_form(1),
                    self.latent_form(self._max_len)),)
                  if self.latent_form(1) is not None else ()),
                # a program compiled for relaid weights takes no others
                *((("weight_formats", tuple(sorted(
                    (k, repr(f.layout)) for k, f in self._formats.items()))),)
                  if self._formats else ()),
                *mesh_id)

    def _lower(self, fn, arg_avals, jit_kw, free=False):
        """Lower and compile ``fn(*state, *args)``.  ``free`` leaves the
        device layout of every parameter to the compiler
        (``Layout.AUTO``); the compiled program's ``input_formats`` say
        what it chose."""
        state = self._state_avals()
        if free:
            def where(a):           # an aval's sharding without its layout
                s = a.sharding
                return s.sharding if isinstance(s, Format) else s
            state = tuple(
                tree if i % 2 else {n: _aval(a, where(a))
                                    for n, a in tree.items()}
                for i, tree in enumerate(state))
            auto = tuple(
                None if i % 2 else {n: Format(Layout.AUTO, a.sharding)
                                    for n, a in tree.items()}
                for i, tree in enumerate(state))
            jit_kw = dict(jit_kw,
                          in_shardings=auto + (None,) * len(arg_avals))
        return jax.jit(fn, **jit_kw).lower(*state, *arg_avals).compile()

    def _compile(self, key, kind, fn, arg_avals, extra,
                 out_shardings=None, donate_argnums=None, free=False,
                 events=None):
        ex = self._execs.get(key)
        if ex is not None:
            _ledger.record_cache_hit(self._site)
            return ex
        # whatever is compiled for the weights as they lie fixes where
        # they lie (slot_execs relays only while nothing is)
        self._settled = self._settled or not free
        from ..jit import persistent_cache as _pcache
        jit_kw = {} if out_shardings is None \
            else {"out_shardings": out_shardings}
        if donate_argnums is not None:
            # slot-loop programs donate the ring cache: XLA aliases the
            # input planes to the output planes, turning the per-step
            # column writes into in-place updates instead of full-plane
            # copies (the host never reuses the donated handle)
            jit_kw["donate_argnums"] = donate_argnums
        ex, _loaded = _pcache.load_or_compile(
            lambda: self._lower(fn, arg_avals, jit_kw, free),
            site=self._site, kind=kind, key=key,
            extra_key=self._program_identity(), extra=extra,
            events=events, hlo_text=self._text_of(key))
        self._execs[key] = ex
        return ex

    def _text_of(self, key):
        """For the compile ledger (``profiler.ledger.program_scopes``):
        the text of the executable kept under ``key``, read when it is
        asked for, or None once this Generator is gone."""
        ref = weakref.ref(self)

        def text():
            gen = ref()
            ex = gen._execs.get(key) if gen is not None else None
            return ex.as_text() if ex is not None else None
        return text

    def _compile_data(self, key, kind, fn, arg_avals, extra,
                      donate_argnums=None):
        """`_compile` for pure data-mover programs (the KV pull/push
        executables): no model-state avals are prepended, so the program
        is a function of the cache tree alone and its persistent-cache
        identity is still keyed on `_program_identity()` (the cache
        layout derives from the architecture)."""
        ex = self._execs.get(key)
        if ex is not None:
            _ledger.record_cache_hit(self._site)
            return ex
        from ..jit import persistent_cache as _pcache
        jit_kw = {}
        if donate_argnums is not None:
            jit_kw["donate_argnums"] = donate_argnums
        ex, _loaded = _pcache.load_or_compile(
            lambda: self._lower_data(fn, arg_avals, jit_kw),
            site=self._site, kind=kind, key=key,
            extra_key=self._program_identity(), extra=extra)
        self._execs[key] = ex
        return ex

    def _lower_data(self, fn, arg_avals, jit_kw):
        """Lower and compile a data mover, ``fn(*args)``: ``_lower``
        without the state."""
        return jax.jit(fn, **jit_kw).lower(*arg_avals).compile()

    def is_compiled(self, phase, B, P=None, C=None, steps=None,
                    beam=1, eos_token_id=None) -> bool:
        if steps is None:
            return self._key(phase, B, P, C, None, None) in self._execs
        end = -1 if eos_token_id is None else int(eos_token_id)
        return self._key(phase, B, P, C, steps, beam, end) in self._execs

    def prefill_exec(self, B, P, C):
        key = self._key("prefill", B, P, C, None, None)
        fn = self._build_prefill(B, P, C)
        out_sh = None
        if self._mesh is not None:
            repl = self._sharding()
            avals = (jax.ShapeDtypeStruct((B, P), jnp.int32, sharding=repl),
                     jax.ShapeDtypeStruct((B,), jnp.int32, sharding=repl))
            # pin the cache output planes to the cluster-wide KV layout
            # (and the logits replicated) so the decode executable — and
            # a decode POOL in another process — ingests without guessing
            shapes = jax.eval_shape(lambda: self._init_cache_raw(B, C))
            out_sh = ([tuple(self.kv_plane_sharding(p.shape) for p in c)
                       for c in shapes], repl)
        else:
            avals = (jax.ShapeDtypeStruct((B, P), jnp.int32),
                     jax.ShapeDtypeStruct((B,), jnp.int32))
        return self._compile(key, "generate_prefill", fn, avals,
                             {"batch": B, "prompt": P, "cache": C},
                             out_shardings=out_sh)

    def decode_exec(self, B, C, steps, beam=1, eos_token_id=None):
        end = -1 if eos_token_id is None else int(eos_token_id)
        key = self._key("decode", B, None, C, steps, beam, end)
        fn = self._build_decode(B, C, int(steps), int(beam), end)
        # the decode program's cache avals are exactly the prefill
        # program's cache outputs — derive them abstractly
        cache_avals = jax.eval_shape(lambda: self._init_cache_raw(B, C))
        if self._mesh is not None:
            repl = self._sharding()
            cache_avals = [tuple(jax.ShapeDtypeStruct(
                                     p.shape, p.dtype,
                                     sharding=self.kv_plane_sharding(
                                         p.shape))
                                 for p in c)
                           for c in cache_avals]
            vocab = self._vocab_size()
            avals = (cache_avals,
                     jax.ShapeDtypeStruct((B, vocab), jnp.float32,
                                          sharding=repl),
                     jax.ShapeDtypeStruct((B,), jnp.int32, sharding=repl),
                     jax.ShapeDtypeStruct((), jnp.int32, sharding=repl))
            return self._compile(key, "generate_decode", fn, avals,
                                 {"batch": B, "cache": C,
                                  "steps": int(steps), "beam": int(beam)},
                                 out_shardings=repl)
        cache_avals = [tuple(jax.ShapeDtypeStruct(p.shape, p.dtype)
                             for p in c)
                       for c in cache_avals]
        vocab = self._vocab_size()
        avals = (cache_avals,
                 jax.ShapeDtypeStruct((B, vocab), jnp.float32),
                 jax.ShapeDtypeStruct((B,), jnp.int32),
                 jax.ShapeDtypeStruct((), jnp.int32))
        return self._compile(key, "generate_decode", fn, avals,
                             {"batch": B, "cache": C, "steps": int(steps),
                              "beam": int(beam)})

    def _vocab_size(self):
        cfg = getattr(self._layer, "config", None)
        v = getattr(cfg, "vocab_size", None)
        if v is None:
            raise PreconditionNotMetError(
                "cannot infer vocab size for the decode executable; the "
                "layer must expose config.vocab_size")
        return int(v)

    # -- the two phases, executed --------------------------------------------
    def prefill(self, ids, start, cache_len):
        """Run (compiling if new) the prefill executable on LEFT-padded
        int32 prompts ``ids [B, P]`` with per-row pad offsets ``start
        [B]``; returns (device cache, next-token logits [B, V])."""
        if self._mesh is not None:
            # host arrays: the SPMD executable places them per its own
            # (replicated) input shardings — a pre-committed single-
            # device array would be a layout mismatch
            ids = np.asarray(ids, np.int32)
            B, P = ids.shape
            ex = self.prefill_exec(B, P, int(cache_len))
            return ex(*self._state_args(), ids,
                      np.asarray(start, np.int32))
        ids = jnp.asarray(ids, jnp.int32)
        B, P = ids.shape
        ex = self.prefill_exec(B, P, int(cache_len))
        return ex(*self._state_args(), ids,
                  jnp.asarray(start, jnp.int32))

    def decode(self, cache, logits0, start, pos0, steps, beam_size=1,
               eos_token_id=None):
        """Run (compiling if new) the scanned decode executable from a
        prefill result.  Greedy returns tokens [B, steps]; beam returns
        (ids [B, K, steps], scores [B, K])."""
        B = logits0.shape[0]
        # the session's length: the longest plane's (a window layer's
        # plane may be shorter)
        C = max(p.shape[2] for c in cache for p in c)
        ex = self.decode_exec(B, int(C), int(steps), int(beam_size),
                              eos_token_id)
        if self._mesh is not None:
            return ex(*self._state_args(), cache,
                      np.asarray(logits0, np.float32),
                      np.asarray(start, np.int32), np.int32(pos0))
        return ex(*self._state_args(), cache,
                  jnp.asarray(logits0, jnp.float32),
                  jnp.asarray(start, jnp.int32), jnp.int32(pos0))

    # -- host-side prep + the public call ------------------------------------
    def pack_prompts(self, prompts, bucket):
        """LEFT-pad variable-length int prompts to [rows, bucket]; returns
        (ids int32, start int32 [rows]) — start[b] = bucket - len_b is
        row b's first valid cache column."""
        rows = len(prompts)
        ids = np.zeros((rows, bucket), np.int32)
        start = np.empty((rows,), np.int32)
        for i, p in enumerate(prompts):
            p = np.asarray(p).reshape(-1).astype(np.int32)
            if p.size == 0:
                raise InvalidArgumentError("empty prompt (0 tokens)")
            if p.size > bucket:
                raise OutOfRangeError(
                    f"prompt of {p.size} tokens exceeds bucket {bucket}")
            ids[i, bucket - p.size:] = p
            start[i] = bucket - p.size
        return ids, start

    def generate(self, input_ids, lengths=None, max_new_tokens=32,
                 beam_size=1, eos_token_id=None):
        """Greedy/beam decoding of a batch of prompts.

        ``input_ids`` [B, L] (right-padded; ``lengths`` [B] gives true
        prompt lengths, default L).  Exactly two executables run: the
        (batch, prompt-bucket, cache-bucket) prefill and the (batch,
        cache-bucket, steps, beam) decode scan.  Greedy returns a Tensor
        of generated ids [B, max_new_tokens]; beam returns (ids
        [B, beam, max_new_tokens], scores [B, beam]) Tensors.
        """
        ids_np = np.asarray(unwrap(input_ids))
        if ids_np.ndim != 2:
            raise InvalidArgumentError(
                f"input_ids must be [batch, length], got {ids_np.shape}")
        B, L = ids_np.shape
        steps = int(max_new_tokens)
        if steps < 1:
            raise InvalidArgumentError("max_new_tokens must be >= 1")
        lens = np.full((B,), L, np.int64) if lengths is None \
            else np.asarray(unwrap(lengths)).reshape(-1).astype(np.int64)
        if lens.shape[0] != B or (lens < 1).any() or (lens > L).any():
            raise InvalidArgumentError(
                f"lengths must be [batch] in [1, {L}], got {lens}")
        max_pos = getattr(getattr(self._layer, "config", None),
                          "max_position_embeddings", None)
        if max_pos is not None and int(lens.max()) + steps > int(max_pos):
            raise OutOfRangeError(
                f"prompt ({int(lens.max())}) + max_new_tokens ({steps}) "
                f"exceeds max_position_embeddings={max_pos}")
        P = self.prefill_bucket(int(lens.max()))
        C = self.cache_bucket(P, steps)
        prompts = [ids_np[b, :lens[b]] for b in range(B)]
        ids, start = self.pack_prompts(prompts, P)
        tr = _tracing.start_span("generate", model=self._site, rows=B,
                                 steps=steps, beam=beam_size)
        if tr is None:                     # off-path: one branch, no fence
            cache, logits0 = self.prefill(ids, start, C)
            out = self.decode(cache, logits0, start, P, steps,
                              beam_size=beam_size,
                              eos_token_id=eos_token_id)
        else:
            # traced call: fence at the scan boundary so the
            # prefill/decode split is honest device time; any compile
            # the call pays lands on this span via the ledger hook
            with _tracing.use_span(tr):
                t0 = time.monotonic()
                cache, logits0 = self.prefill(ids, start, C)
                jax.block_until_ready(logits0)
                t1 = time.monotonic()
                _tracing.child(tr, "prefill", t0, t1, prompt_bucket=P,
                               cache_bucket=C)
                out = self.decode(cache, logits0, start, P, steps,
                                  beam_size=beam_size,
                                  eos_token_id=eos_token_id)
                jax.block_until_ready(out)
                t2 = time.monotonic()
            dt = (t2 - t1) / steps
            d = _tracing.start_span("decode", parent=tr, t0=t1,
                                    steps=steps, cache_bucket=C,
                                    per_token_ms=round(dt * 1e3, 4))
            if d is not None:
                self._annotate_decode_span(d, t1, t2, steps)
                _tracing.finish(d, end=t2)
            _tracing.finish(tr, end=t2)
        if beam_size == 1:
            return Tensor(out)
        paths, scores = out
        return Tensor(paths), Tensor(scores)

    def _annotate_decode_span(self, d, t1, t2, steps):
        """Hook for what a subclass knows about the fenced decode window
        (the speculative one adds draft/verify children).  The token
        loop is ONE device program: the host never observes token k
        alone, so the span carries ``steps`` and ``per_token_ms`` and no
        per-token events."""

    __call__ = generate


def generate(layer, input_ids, draft_model=None, **kwargs):
    """Module-level convenience: (build and memoize a Generator on the
    layer, then) decode.  With ``draft_model`` (a second, smaller layer
    implementing the same init_cache/forward_cached contract) the call
    runs draft/target speculative decoding instead — bit-identical
    greedy output at up to gamma+1 tokens per target forward.  See
    :class:`Generator` / text.speculative.SpeculativeGenerator."""
    if draft_model is not None:
        from .speculative import SpeculativeGenerator
        gen = getattr(layer, "_paddle_tpu_spec_generator", None)
        if gen is None or gen._layer is not layer \
                or gen._draft is not draft_model:
            gen = SpeculativeGenerator(layer, draft_model)
            layer._paddle_tpu_spec_generator = gen
        else:
            gen.refresh_state()      # pick up trained/loaded weights
        return gen.generate(input_ids, **kwargs)
    gen = getattr(layer, "_paddle_tpu_generator", None)
    if gen is None or gen._layer is not layer:
        gen = Generator(layer)
        layer._paddle_tpu_generator = gen
    else:
        gen.refresh_state()          # pick up trained/loaded weights
    return gen.generate(input_ids, **kwargs)
