"""Wide&Deep CTR model over parameter-server sparse embeddings.

Reference parity: BASELINE workload 5 — the DistributedStrategy + sparse
embedding CTR configuration the reference serves with its PS stack
(fluid.layers.embedding(is_sparse=True, is_distributed=True) pulled through
lookup_sparse_table / parameter_prefetch).  Model shape follows the classic
Wide&Deep CTR recipe: a wide linear part over the raw sparse slots plus a
deep MLP over slot embeddings and dense features.

TPU-first: the sparse side is two host tables (dim-1 wide weights, dim-D
deep embeddings) behind DistributedEmbedding; everything dense — gathers,
MLP, loss, backward — is on-chip.  The trainer drives pull → dense step →
push per batch (the HeterPS loop).
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from .. import nn, optimizer as opt_mod
from ..framework.tensor import Tensor
from ..distributed.ps import DistributedEmbedding, LocalPsEndpoint
from ..profiler.metrics import default_registry as _registry

# storage-tier attribution for the cached/sharded embedding step: every
# deduped id is served by exactly one tier — the hot-row cache arena
# (hit, zero routing), the mesh table (warm miss, in-graph all-to-all),
# or the host PS (cold miss, one-time host fetch).  Counting ids per
# tier is what makes cache-hit claims auditable from /metrics.
_TIER_HITS = _registry().counter(
    "wide_deep_tier_hits_total",
    "Deduped embedding ids served per storage tier (cache_arena / "
    "mesh_table / host_ps) by the Wide&Deep cached and sharded steps.",
    labels=("tier",))


class WideDeep(nn.Layer):
    def __init__(self, client=None, emb_dim: int = 16, num_slots: int = 26,
                 dense_dim: int = 13, hidden=(400, 400, 400),
                 sparse_lr: float = 0.05, sparse_optimizer: str = "adagrad",
                 **table_kw):
        super().__init__()
        client = client or LocalPsEndpoint()
        self.client = client
        self.num_slots = num_slots
        self.wide_emb = DistributedEmbedding(client, table_id=0, dim=1,
                                             optimizer=sparse_optimizer,
                                             lr=sparse_lr, **table_kw)
        self.deep_emb = DistributedEmbedding(client, table_id=1, dim=emb_dim,
                                             optimizer=sparse_optimizer,
                                             lr=sparse_lr, **table_kw)
        layers = []
        in_dim = num_slots * emb_dim + dense_dim
        for h in hidden:
            layers += [nn.Linear(in_dim, h), nn.ReLU()]
            in_dim = h
        layers.append(nn.Linear(in_dim, 1))
        self.dnn = nn.Sequential(*layers)
        self.wide_dense = nn.Linear(dense_dim, 1)

    def forward(self, sparse_ids, dense_x):
        # wide: sum of per-slot scalar weights + linear over dense feats
        wide = self.wide_emb(sparse_ids).squeeze(-1).sum(axis=-1,
                                                         keepdim=True)
        wide = wide + self.wide_dense(dense_x)
        # deep: slot embeddings concat dense feats -> MLP
        deep_in = self.deep_emb(sparse_ids).reshape(
            [sparse_ids.shape[0], -1])
        from .. import ops
        deep = self.dnn(ops.concat([deep_in, dense_x], axis=-1))
        return wide + deep

    def flush_sparse_grads(self):
        self.wide_emb.flush_grads()
        self.deep_emb.flush_grads()


def sort_unique_static(ids_flat, cap):
    """Static-shape sort-based unique on DEVICE (the XLA replacement for
    the host np.unique every cached-mode step pays over the full B*S id
    block): sort, boundary flags, segment ids by cumsum, then one
    segment-sum for per-unique occurrence counts.

    Returns ``(uniq [cap], inv [N], count, counts [cap])`` — ``uniq`` is
    sorted-unique padded to the static ``cap`` (padding untouched beyond
    ``count``; compare count host-side and re-run at a bigger octave when
    it overflows), ``inv`` maps each input position to its unique slot
    exactly like ``np.unique(return_inverse=True)`` (np.unique also
    sorts, so the two paths produce bit-identical gathers), and
    ``counts`` is the segment-sum occupancy histogram (hot-id stats /
    dedup ratio gauges)."""
    import jax
    order = jnp.argsort(ids_flat)
    s = ids_flat[order]
    flags = jnp.concatenate([jnp.ones((1,), jnp.int32),
                             (s[1:] != s[:-1]).astype(jnp.int32)])
    seg = jnp.cumsum(flags) - 1                   # unique index, sorted order
    count = seg[-1] + 1
    uniq = jnp.zeros((cap,), s.dtype).at[jnp.clip(seg, 0, cap - 1)].set(s)
    inv = jnp.zeros_like(seg).at[order].set(seg)
    counts = jax.ops.segment_sum(jnp.ones_like(seg), seg,
                                 num_segments=cap)
    return uniq, inv, count, counts


def bce_with_logits_mean(x, labels):
    """Numerically stable mean BCE-with-logits (shared by the CTR
    trainers)."""
    l = jnp.maximum(x, 0) - x * labels + jnp.log1p(jnp.exp(-jnp.abs(x)))
    return jnp.mean(l)


def make_adam_update(lr, b1=0.9, b2=0.999, eps=1e-8):
    """Functional Adam over a {name: array} tree with bias correction —
    the dense-side update both CTR trainers jit into their step."""
    def adam_update(params, adam, gp):
        t = adam["t"] + 1
        tf = t.astype(jnp.float32)
        corr = jnp.sqrt(1 - b2 ** tf) / (1 - b1 ** tf)
        new_m = {k: b1 * adam["m"][k] + (1 - b1) * gp[k] for k in gp}
        new_v = {k: b2 * adam["v"][k] + (1 - b2) * gp[k] ** 2 for k in gp}
        new_p = {k: params[k] - lr * corr * new_m[k] /
                 (jnp.sqrt(new_v[k]) + eps) for k in gp}
        return new_p, {"m": new_m, "v": new_v, "t": t}
    return adam_update


class WideDeepTrainer:
    """The PS CTR train loop at two service levels:

    **device-cache mode** (default when the sparse rule runs on-chip and the
    client supports export/import_rows): the HeterPS/PSGPU design
    (framework/fleet/ps_gpu_wrapper.h, trainer.h:281 PSGPUTrainer) — hot
    embedding rows and their optimizer state live in device HBM arenas
    (DeviceEmbeddingCache); per step the host ships only batch INDICES plus
    the miss block, and one jitted XLA program gathers rows, runs dense
    fwd/bwd/Adam, and applies the sparse rule on-chip.  Steady state moves
    zero row bytes over the wire, and ``step_async`` keeps the device queue
    full (host prepares batch N+1 while the chip runs batch N).

    **pull/push mode** (fallback; ``device_cache=False`` or a table rule
    the chip can't run): pull → ONE-JIT dense fwd/bwd/Adam → push, the
    Communicator+DeviceWorker loop (communicator.h:195) with three
    host↔device transfers per step.

    Cache-mode contracts:
    - Host tables hold stale rows until ``flush()`` (PSGPU EndPass
      semantics); eager ``model(...)`` eval stays correct anyway — the
      embeddings read THROUGH the cache while one is bound.
    - ``feature_wire_dtype`` ("float32" default — bit-identical numerics
      with pull/push mode) is the H2D dtype for dense features.  Pass
      "bfloat16" to halve the hot-path wire bytes (standard for
      normalized CTR features; the caller opts in explicitly).  Labels
      always travel f32."""

    def __init__(self, model: WideDeep, lr: float = 1e-3,
                 async_push: bool = False, device_cache: bool = None,
                 cache_capacity: int = 1 << 20,
                 feature_wire_dtype="float32",
                 sharded_embedding: bool = None,
                 sharded_vocab: int = None, mesh=None):
        import jax
        from ..framework import functional as F
        from ..framework.flags import flag as _flag
        from ..distributed.ps.device_cache import (
            DeviceEmbeddingCache, SlotDirectory, DEVICE_RULES,
            apply_rule_device, pad_adaptive)
        self.model = model
        self.lr = float(lr)
        # a_sync communicator parity (communicator.h AsyncCommunicator):
        # sparse pushes (incl. the D2H grad read) drain on a background
        # thread, overlapping the next step's pull+compute; embeddings may
        # be read one step stale, and a failed push surfaces on the NEXT
        # step()/flush() — inherent to async mode, as in the reference.
        self._async_push = bool(async_push)
        self._push_queue = None
        self._push_thread = None
        self._push_err = []
        if self._async_push:
            import queue as queue_mod
            import threading
            self._push_queue = queue_mod.Queue(maxsize=4)
            # the closure captures only the queue + error list (NOT self):
            # the trainer must stay collectable; close() retires the thread
            q, errs = self._push_queue, self._push_err

            def drain():
                while True:
                    item = q.get()
                    try:
                        if item is None:
                            return
                        # one item = one step's pushes for BOTH tables, so
                        # a step's sparse updates apply atomically wrt
                        # flush boundaries; D2H happens here, off the
                        # trainer thread
                        for emb, uniq, grads_dev, n in item:
                            emb.client.push_sparse(
                                emb.table_id, uniq,
                                np.asarray(grads_dev)[:n])
                    except Exception as e:
                        errs.append(e)
                    finally:
                        q.task_done()

            self._push_thread = threading.Thread(target=drain, daemon=True)
            self._push_thread.start()

        core = _DenseCore(model)
        apply, params, buffers = F.functionalize(core, training=True)
        self._params = params
        self._buffers = buffers
        self._adam = {  # functional Adam state
            "m": {k: jnp.zeros_like(v) for k, v in params.items()},
            "v": {k: jnp.zeros_like(v) for k, v in params.items()},
            "t": jnp.zeros((), jnp.int32),
        }
        bce_mean = bce_with_logits_mean
        adam_update = make_adam_update(self.lr)

        def fused(params, adam, wide_rows, deep_rows, wide_inv, deep_inv,
                  dense_x, labels):
            def loss_of(p, wr, dr):
                out = apply(p, buffers, wr, dr, wide_inv, deep_inv,
                            dense_x)
                x = out[0] if isinstance(out, tuple) else out
                return bce_mean(x, labels)

            (loss), grads = jax.value_and_grad(loss_of, argnums=(0, 1, 2))(
                params, wide_rows, deep_rows)
            gp, gw, gd = grads
            new_p, new_adam = adam_update(params, adam, gp)
            return new_p, new_adam, loss, gw, gd

        self._fused = jax.jit(fused)

        # -- device-cache mode (HeterPS/PSGPU) -------------------------------
        we, de = model.wide_emb, model.deep_emb
        can_cache = (we.optimizer in DEVICE_RULES and
                     hasattr(model.client, "export_rows"))
        if device_cache is None:
            # async_push explicitly asks for the a_sync pull/push contract
            # (host tables at most one step stale) — honor it over the cache
            device_cache = can_cache and not self._async_push
        elif device_cache and not can_cache:
            raise ValueError(
                f"device_cache: rule {we.optimizer!r} must be in "
                f"{DEVICE_RULES} and the client needs export/import_rows")
        elif device_cache and self._async_push:
            raise ValueError(
                "device_cache and async_push are mutually exclusive: the "
                "cache applies sparse updates on-chip (no pushes to drain) "
                "and host tables stay stale until flush()")
        self._use_cache = bool(device_cache)
        if self._use_cache:
            self._pad_adaptive = pad_adaptive
            self._feature_wire_dtype = (
                jnp.bfloat16 if str(feature_wire_dtype) in
                ("bfloat16", "bf16") else np.float32)
            # ONE slot directory: both tables share the id space, so ids
            # resolve to slots once per step
            self._slot_dir = SlotDirectory(cache_capacity)
            # device-dedup state (FLAGS_wide_deep_device_dedup): static-
            # shape octave cap + one jitted sort_unique_static per shape
            self._dedup_cap = None
            self._dedup_fns = {}

            def mk_cache(emb):
                kw = {k: v for k, v in emb.table_kw.items()
                      if k in ("eps", "l1", "l2", "lr_power")}
                return DeviceEmbeddingCache(
                    model.client, emb.table_id, emb.dim,
                    optimizer=emb.optimizer, lr=emb.lr,
                    directory=self._slot_dir, **kw)
            self._w_cache, self._d_cache = mk_cache(we), mk_cache(de)
            self._w_ar = self._w_cache.init_arenas()
            self._d_ar = self._d_cache.init_arenas()
            # eager eval reads THROUGH the cache (host tables are stale
            # until flush — PSGPU EndPass semantics)
            we._cache_read = lambda u: self._w_cache.read_rows(u, self._w_ar)
            de._cache_read = lambda u: self._d_cache.read_rows(u, self._d_ar)

            def scatter_miss(ar, slots, rows, state):
                return {"rows": ar["rows"].at[slots].set(rows),
                        "state": {k: ar["state"][k].at[slots].set(state[k])
                                  for k in ar["state"]}}
            self._scatter = jax.jit(scatter_miss, donate_argnums=(0,))

            opt_name = we.optimizer
            hy_w, hy_d = self._w_cache.hyper, self._d_cache.hyper

            def rule_and_scatter(ar, slots, rows, grads, hyper):
                st = {k: ar["state"][k][slots] for k in ar["state"]}
                new_rows, new_st = apply_rule_device(
                    opt_name, rows, st, grads, **hyper)
                return {"rows": ar["rows"].at[slots].set(new_rows),
                        "state": {k: ar["state"][k].at[slots].set(new_st[k])
                                  for k in ar["state"]}}

            def fused_cached(params, adam, w_ar, d_ar, slots_w, slots_d,
                             inv, dense_x, labels):
                inv32 = inv.astype(jnp.int32)
                dense32 = dense_x.astype(jnp.float32)
                lab32 = labels.astype(jnp.float32)
                w_rows = w_ar["rows"][slots_w]
                d_rows = d_ar["rows"][slots_d]

                def loss_of(p, wr, dr):
                    out = apply(p, buffers, wr, dr, inv32, inv32, dense32)
                    x = out[0] if isinstance(out, tuple) else out
                    return bce_mean(x, lab32)

                (loss), grads = jax.value_and_grad(
                    loss_of, argnums=(0, 1, 2))(params, w_rows, d_rows)
                gp, gw, gd = grads
                new_p, new_adam = adam_update(params, adam, gp)
                w_ar = rule_and_scatter(w_ar, slots_w, w_rows, gw, hy_w)
                d_ar = rule_and_scatter(d_ar, slots_d, d_rows, gd, hy_d)
                return new_p, new_adam, w_ar, d_ar, loss

            # raw (unjitted) body kept for the in-graph chained-K probe
            self._fused_cached_raw = fused_cached
            self._fused_cached = jax.jit(fused_cached,
                                         donate_argnums=(0, 1, 2, 3))

        # -- mesh-sharded deep table (FLAGS_sharded_embedding) ---------------
        # The HeterPS hashtable seat done TPU-style: the deep-leg table is
        # row-partitioned over a mesh axis; the hot-row cache arena keeps
        # the skewed head replicated (zero routing for hits), warm misses
        # route via lax.all_to_all INSIDE the jitted step (zero host row
        # bytes), and only cold ids (first sighting) pay a host PS fetch.
        # Off-path = this one branch; the replicated path is unchanged.
        self._sharded = (bool(_flag("sharded_embedding"))
                         if sharded_embedding is None
                         else bool(sharded_embedding))
        if self._sharded and not self._use_cache:
            raise ValueError(
                "FLAGS_sharded_embedding composes with device-cache mode "
                "only (the hot-row arena is the short-circuit for the "
                "skewed head); pull/push + sharded tables is the "
                "HeterTrainer seat")
        if self._sharded:
            if sharded_vocab is None:
                raise ValueError(
                    "sharded embedding mode needs sharded_vocab: the id "
                    "bound sizing the mesh-partitioned deep table")
            from jax.sharding import NamedSharding, PartitionSpec as P
            from .sharded_embedding import ShardedTable
            de = model.deep_emb
            kw = {k: v for k, v in de.table_kw.items()
                  if k in ("eps", "l1", "l2", "lr_power")}
            self._dtab = ShardedTable(de.dim, sharded_vocab,
                                      optimizer=de.optimizer, lr=de.lr,
                                      mesh=mesh, **kw)
            self._dtab_tree = self._dtab.init_tree()
            # one jitted program must see consistently-placed operands:
            # dense state + arenas replicate onto the table's mesh
            self._rep_sh = NamedSharding(self._dtab.mesh, P())
            rep_put = lambda t: jax.tree_util.tree_map(  # noqa: E731
                lambda v: jax.device_put(v, self._rep_sh), t)
            self._params = rep_put(self._params)
            self._adam = rep_put(self._adam)
            self._w_ar = rep_put(self._w_ar)
            self._d_ar = rep_put(self._d_ar)
            self._sharded_fns = {}       # (shape/cap key) -> jitted step
            de._cache_read = self._sharded_read
            dtab = self._dtab

            def make_sharded_fused(cap_v, cap_w):
                """One compiled sharded step per (padded-shape, cap)
                signature — caps are static routing-buffer bounds, octave
                -laddered host-side so the compile count stays bounded."""
                def fused(params, adam, w_ar, d_ar, dtree, slots_w,
                          slots_d, inv, dense_x, labels, vic_ids,
                          vic_slots, warm_ids, warm_slots, cold_slots,
                          cold_rows, cold_state):
                    inv32 = inv.astype(jnp.int32)
                    dense32 = dense_x.astype(jnp.float32)
                    lab32 = labels.astype(jnp.float32)
                    # 1. victims: arena -> sharded table (routed SET; the
                    # arena reads precede every arena scatter this step)
                    vrows = d_ar["rows"][vic_slots]
                    vstate = {k: d_ar["state"][k][vic_slots]
                              for k in d_ar["state"]}
                    dtree = dtab.set_rows(dtree, vic_ids, vrows, vstate,
                                          cap=cap_v)
                    # 2. cold misses (first sighting, host-fetched rows)
                    d_ar = {"rows": d_ar["rows"].at[cold_slots].set(
                                cold_rows),
                            "state": {k: d_ar["state"][k].at[
                                cold_slots].set(cold_state[k])
                                for k in d_ar["state"]}}
                    # 3. warm misses: routed all-to-all fetch, table ->
                    # arena — the steady-state tail traffic; the cached
                    # head never reaches this exchange
                    wrows, wstate, _ovf = dtab.gather(dtree, warm_ids,
                                                      cap=cap_w)
                    d_ar = {"rows": d_ar["rows"].at[warm_slots].set(
                                wrows),
                            "state": {k: d_ar["state"][k].at[
                                warm_slots].set(wstate[k])
                                for k in d_ar["state"]}}
                    # 4. dense fwd/bwd + on-chip sparse rule (the
                    # fused_cached body, unchanged numerics)
                    w_rows = w_ar["rows"][slots_w]
                    d_rows = d_ar["rows"][slots_d]

                    def loss_of(p, wr, dr):
                        out = apply(p, buffers, wr, dr, inv32, inv32,
                                    dense32)
                        x = out[0] if isinstance(out, tuple) else out
                        return bce_mean(x, lab32)

                    (loss), grads = jax.value_and_grad(
                        loss_of, argnums=(0, 1, 2))(params, w_rows,
                                                    d_rows)
                    gp, gw, gd = grads
                    new_p, new_adam = adam_update(params, adam, gp)
                    w_ar = rule_and_scatter(w_ar, slots_w, w_rows, gw,
                                            hy_w)
                    d_ar = rule_and_scatter(d_ar, slots_d, d_rows, gd,
                                            hy_d)
                    return new_p, new_adam, w_ar, d_ar, dtree, loss
                return fused

            self._make_sharded_fused = make_sharded_fused

    def _raise_push_errors(self):
        if self._push_err:
            errs = list(self._push_err)
            del self._push_err[:]
            raise errs[0]

    def _push_both(self, we, de, uniq, gw, gd):
        n = len(uniq)
        if self._async_push:
            self._push_queue.put(((we, uniq, gw, n), (de, uniq, gd, n)))
        else:
            we.client.push_sparse(we.table_id, uniq, np.asarray(gw)[:n])
            de.client.push_sparse(de.table_id, uniq, np.asarray(gd)[:n])

    def close(self):
        """Retire the drain thread (idempotent)."""
        if self._push_thread is not None:
            self._push_queue.put(None)
            self._push_thread.join(timeout=5)
            self._push_thread = None

    def __del__(self):  # pragma: no cover — best-effort cleanup
        try:
            self.close()
        except Exception:
            pass

    def step(self, sparse_ids, dense_x, labels) -> float:
        return float(self.step_async(sparse_ids, dense_x, labels))

    def step_async(self, sparse_ids, dense_x, labels):
        """One train step WITHOUT fencing on the loss: returns the device
        scalar so the host can prepare batch N+1 while the chip runs batch
        N (jax async dispatch is the pipeline).  Fence with float(loss) or
        flush()."""
        if self._use_cache:
            return self._step_cached(sparse_ids, dense_x, labels)
        return self._step_pullpush(sparse_ids, dense_x, labels)

    def _dedup_device(self, ids):
        """Sort-based unique + segment-sum on DEVICE (VERDICT #5 relief,
        FLAGS_wide_deep_device_dedup): the chip dedups the B*S id block at
        a static octave cap; the host reads back only the deduped prefix
        (plus one count scalar) for hot-row-cache slot resolution, instead
        of running np.unique over the full block every step.  Cap
        overflow re-runs one octave up (compile count stays bounded by
        the octave ladder).  Returns (uniq np [count], inv device [B,S
        flat])."""
        import functools
        import jax
        flat = jnp.asarray(ids.reshape(-1))
        n = flat.size
        if self._dedup_cap is None:
            # seed the octave from a one-time host count
            u0 = len(np.unique(ids))
            self._dedup_cap = self._pad_adaptive(min(max(2 * u0, 16), n))
        while True:
            cap = min(self._dedup_cap, n)
            fn = self._dedup_fns.get((n, cap))
            if fn is None:
                fn = jax.jit(functools.partial(sort_unique_static, cap=cap))
                self._dedup_fns[(n, cap)] = fn
            uniq_dev, inv_dev, count_dev, _counts = fn(flat)
            count = int(count_dev)           # one scalar D2H
            if count <= cap or cap >= n:
                break
            # overflow: grow to the octave holding count (strictly > cap)
            self._dedup_cap = self._pad_adaptive(min(count, n))
        return np.asarray(uniq_dev[:count]), inv_dev

    def _prep_cached(self, sparse_ids):
        """Host side of a cached-mode step: id dedup, slot resolution,
        miss fill/scatter, octave-padded slot vector, wire-compressed
        inverse map.  Returns device (slots, inv)."""
        from ..framework.flags import flag
        ids = np.asarray(sparse_ids)
        if flag("wide_deep_device_dedup"):
            # np.unique also sorts, so both paths produce identical
            # (uniq, inv) and the step numerics are bit-identical
            uniq, inv = self._dedup_device(ids)
        else:
            uniq, inv = np.unique(ids, return_inverse=True)
        # ONE id→slot resolution for both tables, then per-table row moves.
        # A failure before the miss rows land in BOTH arenas rolls the
        # resolution back, so a retried step re-misses instead of hitting
        # never-filled slots (the victims fill() already wrote back stay in
        # the host table — consistent either way).
        res = self._slot_dir.resolve(uniq)
        try:
            mw_slots, mw_rows, mw_state = self._w_cache.fill(res, self._w_ar)
            md_slots, md_rows, md_state = self._d_cache.fill(res, self._d_ar)
        except Exception:
            # rollback is only valid pre-scatter (arenas untouched); a
            # fill failure is cleanly retryable
            self._slot_dir.rollback(res)
            raise
        if mw_slots is not None:
            self._w_ar = self._scatter(
                self._w_ar, jnp.asarray(mw_slots), jnp.asarray(mw_rows),
                {k: jnp.asarray(v) for k, v in mw_state.items()})
        if md_slots is not None:
            self._d_ar = self._scatter(
                self._d_ar, jnp.asarray(md_slots), jnp.asarray(md_rows),
                {k: jnp.asarray(v) for k, v in md_state.items()})
        # tier attribution (replicated cached mode has two tiers: the
        # arena for hits, the host PS for every miss)
        n_miss = len(res.miss_idx)
        if len(uniq) - n_miss:
            _TIER_HITS.labels(tier="cache_arena").inc(len(uniq) - n_miss)
        if n_miss:
            _TIER_HITS.labels(tier="host_ps").inc(n_miss)
        # eighth-octave-pad the slot vector (≤8 compiled shapes per
        # doubling of U); padding points at the scratch slot
        u = len(uniq)
        u_pad = self._pad_adaptive(u)
        slots_p = np.full(u_pad, self._slot_dir.cap, np.int32)
        slots_p[:u] = res.slots
        # wire compression: indices uint16 when they fit, features bf16
        inv_w = inv.reshape(ids.shape)
        inv_w = inv_w.astype(np.uint16 if u_pad <= 65536 else np.int32)
        return jnp.asarray(slots_p), jnp.asarray(inv_w)

    # -- mesh-sharded deep leg (FLAGS_sharded_embedding) ----------------------
    def _pad_routed(self, ids, slots, scratch_slot):
        """Pad an (ids, slots) pair for routing: octave length rounded to
        a shard multiple, sentinel -1 ids (the router drops them) and
        scratch arena slots (their scatters land on the arena's spare
        row).  Returns (ids [P] int32, slots [P] int32)."""
        from ..distributed.ps.device_cache import pad_adaptive
        from ..ops.routing import pad_requests
        n = len(ids)
        p = pad_requests(n, self._dtab.n_shards, pad_adaptive)
        out_ids = np.full(p, -1, np.int32)
        out_ids[:n] = ids
        out_slots = np.full(p, scratch_slot, np.int32)
        out_slots[:n] = slots
        return out_ids, out_slots

    def _prep_sharded(self, sparse_ids):
        """Host side of a sharded cached step: dedup + ONE slot
        resolution (shared with the wide table), wide fill from the host
        PS exactly as the replicated path, then the deep-side three-way
        split — victims route arena→table, warm misses route table→arena
        (both in-graph), cold misses pay the one-time host fetch."""
        from ..framework.flags import flag
        ids = np.asarray(sparse_ids)
        if flag("wide_deep_device_dedup"):
            uniq, inv = self._dedup_device(ids)
        else:
            uniq, inv = np.unique(ids, return_inverse=True)
        self._dtab.check_ids(uniq)
        res = self._slot_dir.resolve(uniq)
        try:
            # wide leg: unchanged host fill (incl. wide victim writeback)
            mw_slots, mw_rows, mw_state = self._w_cache.fill(res,
                                                             self._w_ar)
            # deep cold misses: ids never seen by the device table yet
            miss_ids = res.uniq[res.miss_idx]
            miss_slots = np.asarray(res.slots[res.miss_idx], np.int64)
            cold_sel = np.fromiter(
                (int(i) not in self._dtab.resident for i in miss_ids),
                bool, len(miss_ids))
            cold_ids, cold_slots = miss_ids[cold_sel], miss_slots[cold_sel]
            warm_ids, warm_slots = (miss_ids[~cold_sel],
                                    miss_slots[~cold_sel])
            de = self.model.deep_emb
            if len(cold_ids):
                c_rows, c_state = de.client.export_rows(de.table_id,
                                                        cold_ids)
            else:
                c_rows = np.zeros((0, de.dim), np.float32)
                c_state = {k: np.zeros((0, de.dim), np.float32)
                           for k in self._d_cache._state_names}
        except Exception:
            self._slot_dir.rollback(res)
            raise
        if mw_slots is not None:
            self._w_ar = self._scatter(
                self._w_ar, jnp.asarray(mw_slots), jnp.asarray(mw_rows),
                {k: jnp.asarray(v) for k, v in mw_state.items()})
        cap = self._slot_dir.cap          # the arena scratch slot
        # cold pad: bucket-padded like DeviceEmbeddingCache.fill (a tiny
        # fixed shape when there are none, so the steady state ships ~0
        # host bytes instead of a zero-filled bucket)
        from ..distributed.ps.device_cache import _pad_to_bucket
        nc = len(cold_ids)
        c_pad = 8 if nc == 0 else _pad_to_bucket(nc,
                                                 self._d_cache.miss_bucket)
        cold_slots_p = np.full(c_pad, cap, np.int32)
        cold_slots_p[:nc] = cold_slots
        cold_rows_p = np.zeros((c_pad, de.dim), np.float32)
        cold_rows_p[:nc] = c_rows
        cold_state_p = {}
        for k in self._d_cache._state_names:
            buf = np.zeros((c_pad, de.dim), np.float32)
            buf[:nc] = c_state[k]
            cold_state_p[k] = buf
        # routed pads (victims / warm misses) + static routing caps
        vic_ids_p, vic_slots_p = self._pad_routed(res.victim_ids,
                                                  res.victim_slots, cap)
        warm_ids_p, warm_slots_p = self._pad_routed(warm_ids, warm_slots,
                                                    cap)
        n_sh = self._dtab.n_shards
        cap_v = (self._dtab.cap_for(np.asarray(res.victim_ids, np.int64),
                                    len(vic_ids_p) // n_sh)
                 if self._dtab.bucket_cap else len(vic_ids_p) // n_sh)
        cap_w = (self._dtab.cap_for(np.asarray(warm_ids, np.int64),
                                    len(warm_ids_p) // n_sh)
                 if self._dtab.bucket_cap else len(warm_ids_p) // n_sh)
        # residency bookkeeping: victims now live in the table; warm (and
        # cold) misses move into the arena, which becomes authoritative
        self._dtab.resident.update(int(i) for i in res.victim_ids)
        self._dtab.resident.difference_update(int(i) for i in warm_ids)
        # tier attribution: arena short-circuit / routed table / host PS
        n_hit = len(uniq) - len(miss_ids)
        if n_hit:
            _TIER_HITS.labels(tier="cache_arena").inc(n_hit)
        if len(warm_ids):
            _TIER_HITS.labels(tier="mesh_table").inc(len(warm_ids))
        if nc:
            _TIER_HITS.labels(tier="host_ps").inc(nc)
        # slot vector + wire-compressed inverse (replicated-path shapes)
        u = len(uniq)
        u_pad = self._pad_adaptive(u)
        slots_p = np.full(u_pad, cap, np.int32)
        slots_p[:u] = res.slots
        inv_w = inv.reshape(ids.shape)
        inv_w = inv_w.astype(np.uint16 if u_pad <= 65536 else np.int32)
        import jax
        rep = lambda x: jax.device_put(jnp.asarray(x),  # noqa: E731
                                       self._rep_sh)
        return {
            "slots": rep(slots_p), "inv": rep(inv_w),
            "vic_ids": rep(vic_ids_p), "vic_slots": rep(vic_slots_p),
            "warm_ids": rep(warm_ids_p), "warm_slots": rep(warm_slots_p),
            "cold_slots": rep(cold_slots_p), "cold_rows": rep(cold_rows_p),
            "cold_state": {k: rep(v) for k, v in cold_state_p.items()},
            "caps": (int(cap_v), int(cap_w)),
            "stats": {"cold": nc, "warm": len(warm_ids),
                      "victims": len(res.victim_ids)},
        }

    def _step_sharded(self, sparse_ids, dense_x, labels):
        import jax
        prep = self._prep_sharded(sparse_ids)
        self._last_route_stats = prep["stats"]
        key = (prep["vic_ids"].shape[0], prep["warm_ids"].shape[0],
               prep["cold_rows"].shape[0], prep["slots"].shape[0],
               tuple(np.asarray(sparse_ids).shape), prep["caps"])
        fn = self._sharded_fns.get(key)
        if fn is None:
            fn = jax.jit(self._make_sharded_fused(*prep["caps"]),
                         donate_argnums=(0, 1, 2, 3, 4))
            self._sharded_fns[key] = fn
        dense_w = jax.device_put(
            jnp.asarray(np.asarray(dense_x, self._feature_wire_dtype)),
            self._rep_sh)
        lab_w = jax.device_put(
            jnp.asarray(np.asarray(labels, np.float32)), self._rep_sh)
        (self._params, self._adam, self._w_ar, self._d_ar,
         self._dtab_tree, loss) = fn(
            self._params, self._adam, self._w_ar, self._d_ar,
            self._dtab_tree, prep["slots"], prep["slots"], prep["inv"],
            dense_w, lab_w, prep["vic_ids"], prep["vic_slots"],
            prep["warm_ids"], prep["warm_slots"], prep["cold_slots"],
            prep["cold_rows"], prep["cold_state"])
        self.sync_params()
        return loss

    def _sharded_read(self, uniq):
        """Deep-table eval read-through for sharded mode: cache arena for
        cached ids, the mesh table for resident ids, host PS else."""
        uniq = np.asarray(uniq, np.int64).ravel()
        get = self._slot_dir._slot_of.get
        slots = np.fromiter((get(i, -1) for i in uniq.tolist()),
                            np.int64, len(uniq))
        de = self.model.deep_emb
        out = np.empty((len(uniq), de.dim), np.float32)
        hit = slots >= 0
        if hit.any():
            out[hit] = np.asarray(
                self._d_ar["rows"][jnp.asarray(slots[hit])])
        cold = ~hit
        if cold.any():
            resident = np.fromiter(
                (int(i) in self._dtab.resident for i in uniq[cold]),
                bool, int(cold.sum()))
            cold_ids = uniq[cold]
            block = np.empty((len(cold_ids), de.dim), np.float32)
            if resident.any():
                block[resident], _ = self._dtab.host_read(
                    self._dtab_tree, cold_ids[resident])
            if (~resident).any():
                block[~resident] = de.client.pull_sparse(
                    de.table_id, cold_ids[~resident])
            out[cold] = block
        return out

    def _step_cached(self, sparse_ids, dense_x, labels):
        if getattr(self, "_sharded", False):
            return self._step_sharded(sparse_ids, dense_x, labels)
        slots_dev, inv_dev = self._prep_cached(sparse_ids)
        dense_w = np.asarray(dense_x, self._feature_wire_dtype)
        lab_w = np.asarray(labels, np.float32)
        self._params, self._adam, self._w_ar, self._d_ar, loss = \
            self._fused_cached(self._params, self._adam, self._w_ar,
                               self._d_ar, slots_dev, slots_dev,
                               inv_dev, jnp.asarray(dense_w),
                               jnp.asarray(lab_w))
        self.sync_params()
        return loss

    def in_graph_step_s(self, sparse_ids, dense_x, labels, k_small=2,
                        k_large=6, reps=2):
        """Seconds per device-side train step, measured as the DELTA of
        two chained in-graph loop lengths over the cached-mode fused step
        (one dispatch per K, loss riding the carry so no step can be
        dead-code-eliminated).  This is Wide&Deep's in-graph control
        number (VERDICT r5 #2/#8): what
        the framework's compiled sparse+dense step costs with the host
        hash/dedup and per-step dispatch factored out."""
        import time
        import jax
        if not self._use_cache:
            raise RuntimeError("in-graph probe needs device-cache mode")
        if getattr(self, "_sharded", False):
            return self._in_graph_sharded_s(sparse_ids, dense_x, labels,
                                            k_small, k_large, reps)
        slots_dev, inv_dev = self._prep_cached(sparse_ids)
        dense_dev = jnp.asarray(np.asarray(dense_x,
                                           self._feature_wire_dtype))
        lab_dev = jnp.asarray(np.asarray(labels, np.float32))
        raw = self._fused_cached_raw

        def loop(params, adam, w_ar, d_ar, k):
            def one(_, c):
                p, a, w, d, acc = c
                p, a, w, d, loss = raw(p, a, w, d, slots_dev, slots_dev,
                                       inv_dev, dense_dev, lab_dev)
                return (p, a, w, d, acc + loss.astype(jnp.float32))
            init = (params, adam, w_ar, d_ar, jnp.float32(0.0))
            return jax.lax.fori_loop(0, k, one, init)[4]

        f = jax.jit(loop, static_argnums=(4,))
        times = {}
        for k in (k_small, k_large):
            float(f(self._params, self._adam, self._w_ar, self._d_ar, k))
            best = None
            for _ in range(reps):
                t0 = time.perf_counter()
                float(f(self._params, self._adam, self._w_ar, self._d_ar,
                        k))
                dt = time.perf_counter() - t0
                best = dt if best is None else min(best, dt)
            times[k] = best
        return (times[k_large] - times[k_small]) / (k_large - k_small)

    def _in_graph_sharded_s(self, sparse_ids, dense_x, labels, k_small,
                            k_large, reps):
        """Sharded-mode in-graph probe: the chained-K delta over the full
        sharded step body (victim route + warm all-to-all fetch + dense
        fwd/bwd + on-chip rule), so the number includes the routing legs
        a steady-state step actually pays."""
        import time
        import jax
        prep = self._prep_sharded(sparse_ids)
        raw = self._make_sharded_fused(*prep["caps"])
        dense_dev = jax.device_put(
            jnp.asarray(np.asarray(dense_x, self._feature_wire_dtype)),
            self._rep_sh)
        lab_dev = jax.device_put(
            jnp.asarray(np.asarray(labels, np.float32)), self._rep_sh)
        p = prep

        def loop(params, adam, w_ar, d_ar, dtree, k):
            def one(_, c):
                pr, a, w, d, t, acc = c
                pr, a, w, d, t, loss = raw(
                    pr, a, w, d, t, p["slots"], p["slots"], p["inv"],
                    dense_dev, lab_dev, p["vic_ids"], p["vic_slots"],
                    p["warm_ids"], p["warm_slots"], p["cold_slots"],
                    p["cold_rows"], p["cold_state"])
                return (pr, a, w, d, t, acc + loss.astype(jnp.float32))
            init = (params, adam, w_ar, d_ar, dtree, jnp.float32(0.0))
            return jax.lax.fori_loop(0, k, one, init)[5]

        f = jax.jit(loop, static_argnums=(5,))
        times = {}
        for k in (k_small, k_large):
            args = (self._params, self._adam, self._w_ar, self._d_ar,
                    self._dtab_tree, k)
            float(f(*args))
            best = None
            for _ in range(reps):
                t0 = time.perf_counter()
                float(f(*args))
                dt = time.perf_counter() - t0
                best = dt if best is None else min(best, dt)
            times[k] = best
        return (times[k_large] - times[k_small]) / (k_large - k_small)

    def sharded_step_stats(self, sparse_ids, dense_x, labels):
        """Collective census of the compiled sharded step for this batch
        signature (AOT lower + compile, NO execution): per-kind counts,
        result bytes and ring-model wire bytes.  Call with an
        already-trained batch so the prep pass leaves cache state
        effectively unchanged (all ids hit)."""
        if not getattr(self, "_sharded", False):
            raise RuntimeError("sharded_step_stats needs sharded mode "
                               "(FLAGS_sharded_embedding)")
        import jax
        from ..analysis.hlo.extract import program_stats
        prep = self._prep_sharded(sparse_ids)
        dense_w = jax.device_put(
            jnp.asarray(np.asarray(dense_x, self._feature_wire_dtype)),
            self._rep_sh)
        lab_w = jax.device_put(
            jnp.asarray(np.asarray(labels, np.float32)), self._rep_sh)
        # a fresh un-donated jit so the lowering never invalidates live
        # trainer state
        fn = jax.jit(self._make_sharded_fused(*prep["caps"]))
        compiled = fn.lower(
            self._params, self._adam, self._w_ar, self._d_ar,
            self._dtab_tree, prep["slots"], prep["slots"], prep["inv"],
            dense_w, lab_w, prep["vic_ids"], prep["vic_slots"],
            prep["warm_ids"], prep["warm_slots"], prep["cold_slots"],
            prep["cold_rows"], prep["cold_state"]).compile()
        stats = program_stats(compiled)
        return {
            "collectives": stats.collectives,
            "all_to_all_count": int(
                stats.collectives.get("all-to-all", {}).get("count", 0)),
            "all_to_all_wire_bytes": float(
                stats.collectives.get("all-to-all", {}).get("wire_bytes",
                                                            0.0)),
            "collective_wire_bytes": round(stats.collective_wire_bytes, 1),
            "route": dict(prep["stats"]),
            "n_shards": self._dtab.n_shards,
        }

    def _step_pullpush(self, sparse_ids, dense_x, labels):
        if self._async_push:
            # surface background push failures BEFORE advancing dense
            # state for this batch
            self._raise_push_errors()
        ids = np.asarray(sparse_ids)
        we, de = self.model.wide_emb, self.model.deep_emb
        # one unique/inverse shared by both tables (same id space)
        uniq, inv = np.unique(ids, return_inverse=True)
        w_rows = jnp.asarray(we.pull_padded_rows(uniq))
        d_rows = jnp.asarray(de.pull_padded_rows(uniq))
        inv_dev = jnp.asarray(inv.reshape(ids.shape), jnp.int32)
        self._params, self._adam, loss, gw, gd = self._fused(
            self._params, self._adam, w_rows, d_rows, inv_dev, inv_dev,
            jnp.asarray(dense_x), jnp.asarray(labels))
        self._push_both(we, de, uniq, gw, gd)
        # keep the eager model in sync: rebinding _value to the updated
        # device arrays is a pointer swap (no transfer), so eval /
        # state_dict always see the trained weights
        self.sync_params()
        return loss

    def flush(self):
        """Barrier before eval/save: drain pending async pushes, or in
        device-cache mode write every cached row back to the host table
        (PSGPU EndPass).  Sharded mode additionally drains the
        mesh-resident tail of the deep table (resident ids' rows + state)
        back to the host PS — cache and table populations are disjoint by
        construction, so nothing double-writes."""
        if self._use_cache:
            self._w_cache.writeback_all(self._w_ar)
            self._d_cache.writeback_all(self._d_ar)
            if getattr(self, "_sharded", False):
                de = self.model.deep_emb
                self._dtab.flush_to_client(self._dtab_tree, de.client,
                                           de.table_id)
        if self._push_queue is not None:
            self._push_queue.join()
        self._raise_push_errors()

    def sync_params(self):
        """Point the eager model's dense params at the jit-updated device
        arrays (free — same buffers, no copy)."""
        if not hasattr(self, "_name_map"):
            self._name_map = dense_param_map(self.model, self._params)
        for name, p in self._name_map:
            p._value = self._params[name]


def dense_param_map(model: "WideDeep", params):
    """(name, Parameter) pairs of the model's dense core that appear in a
    functional params tree — the pointer-swap map both CTR trainers use to
    keep the eager model in sync."""
    core = _DenseCore(model)
    return [(n, p) for n, p in core.named_parameters() if n in params]


class _DenseCore(nn.Layer):
    """The dense compute of WideDeep as a pure layer over pulled rows:
    (wide_rows [U1,1], deep_rows [U2,D], wide_inv [B,S], deep_inv [B,S],
    dense_x [B,F]) -> logits [B,1]."""

    def __init__(self, wd: WideDeep):
        super().__init__()
        self.dnn = wd.dnn
        self.wide_dense = wd.wide_dense
        self._emb_dim = wd.deep_emb.dim

    def forward(self, wide_rows, deep_rows, wide_inv, deep_inv, dense_x):
        from .. import ops
        from ..nn import functional as F
        wide_g = F.embedding(wide_inv, wide_rows)      # [B, S, 1]
        wide = wide_g.squeeze(-1).sum(axis=-1, keepdim=True) + \
            self.wide_dense(dense_x)
        deep_g = F.embedding(deep_inv, deep_rows)      # [B, S, D]
        deep_in = deep_g.reshape([deep_g.shape[0], -1])
        deep = self.dnn(ops.concat([deep_in, dense_x], axis=-1))
        return wide + deep




def synthetic_ctr_batch(batch: int, num_slots: int = 26, dense_dim: int = 13,
                        vocab: int = 1_000_000, seed: int = 0):
    """Criteo-shaped synthetic batch: 26 categorical slots (slot-offset id
    space), 13 dense features, clicked/not label correlated with features."""
    rng = np.random.RandomState(seed)
    # power-lawish ids per slot, offset so slots never collide
    ids = (rng.zipf(1.5, size=(batch, num_slots)) % (vocab // num_slots))
    ids = ids + np.arange(num_slots) * (vocab // num_slots)
    dense = rng.standard_normal((batch, dense_dim)).astype(np.float32)
    logit = 0.5 * dense[:, 0] - 0.3 * dense[:, 1] + \
        0.1 * (ids[:, 0] % 7 - 3)
    label = (logit + rng.standard_normal(batch) >
             0).astype(np.float32)[:, None]
    return ids.astype(np.int64), dense, label

def write_ctr_files(dirname, n_examples, n_files=4, num_slots: int = 26,
                    dense_dim: int = 13, vocab: int = 1_000_000, seed=0):
    """Write synthetic CTR data as MultiSlot text files (data_feed.proto
    format): 26 single-id sparse slots, one dense slot, one label slot.
    Returns the filelist."""
    import os
    os.makedirs(dirname, exist_ok=True)
    per = n_examples // n_files
    files = []
    for fi in range(n_files):
        ids, dense, label = synthetic_ctr_batch(per, num_slots, dense_dim,
                                                vocab, seed=seed + fi)
        path = os.path.join(dirname, f"ctr_{fi:03d}.txt")
        with open(path, "w") as f:
            for r in range(per):
                parts = [f"1 {ids[r, s]}" for s in range(num_slots)]
                parts.append(f"{dense_dim} " +
                             " ".join(f"{v:.5f}" for v in dense[r]))
                parts.append(f"1 {int(label[r, 0])}")
                f.write(" ".join(parts) + "\n")
        files.append(path)
    return files


def ctr_dataset(filelist, batch_size, num_slots: int = 26,
                dense_dim: int = 13, kind="InMemoryDataset"):
    """An InMemoryDataset/QueueDataset over CTR MultiSlot files, slot
    schema matching write_ctr_files."""
    from ..distributed.dataset import InMemoryDataset, QueueDataset
    ds = (InMemoryDataset if kind == "InMemoryDataset" else QueueDataset)()
    ds.init(batch_size=batch_size, thread_num=4)
    slots = [{"name": f"C{s}", "type": "uint64"} for s in range(num_slots)]
    slots.append({"name": "dense", "type": "float", "is_dense": True,
                  "shape": (dense_dim,)})
    slots.append({"name": "label", "type": "uint64"})
    ds.set_slots(slots)
    ds.set_filelist(list(filelist))
    return ds


def batch_from_feed(feed, num_slots: int = 26):
    """Compose a dataset feed dict into (ids, dense, label) trainer arrays."""
    ids = np.concatenate([feed[f"C{s}"] for s in range(num_slots)], axis=1)
    dense = feed["dense"].astype(np.float32)
    label = feed["label"].astype(np.float32)
    return ids.astype(np.int64), dense, label
