"""Scope + Executor: static-program execution.

Reference parity: Scope ≙ paddle/fluid/framework/scope.h (name→Variable map);
Executor.run ≙ python/paddle/fluid/executor.py:916 → C++ Executor::Run
(executor.cc:179) whose hot loop interprets ops one-by-one (executor.cc:473).

TPU-first: instead of op-by-op interpretation, ``run`` compiles the WHOLE
block into one XLA computation (jax.jit of the sequential replay) cached by
(program version, feed signature) — the analogue of the reference's program
cache (executor.py:1277) but yielding a single fused device program, which is
the idiomatic (and only fast) way to execute a graph on TPU.  Startup
programs (initializers) run eagerly, matching their one-shot nature.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.tensor import Tensor
from ..profiler import ledger as _ledger
from ..profiler import profiling_enabled as _prof_on
from ..profiler import span as _span
from .program import Program, Variable, default_main_program


class Scope:
    """scope.h parity: name → array, with parent chain."""

    def __init__(self, parent: Optional["Scope"] = None):
        self._vars: Dict[str, jnp.ndarray] = {}
        self._parent = parent
        self._kids: List["Scope"] = []

    def new_scope(self):
        s = Scope(self)
        self._kids.append(s)
        return s

    def drop_kids(self):
        self._kids.clear()

    def find_var(self, name):
        if name in self._vars:
            return self._vars[name]
        if self._parent is not None:
            return self._parent.find_var(name)
        return None

    def set_var(self, name, value):
        self._vars[name] = value

    def var_names(self):
        return list(self._vars)

    def __contains__(self, name):
        return self.find_var(name) is not None


_global_scope = Scope()


def global_scope() -> Scope:
    return _global_scope


def scope_guard(scope):
    import contextlib

    @contextlib.contextmanager
    def guard():
        global _global_scope
        prev = _global_scope
        _global_scope = scope
        try:
            yield
        finally:
            _global_scope = prev
    return guard()



def _collect_persistables(program, scope, persist_names):
    """Resolve persistable values, seeding RNG-key vars (key_advance
    inputs) from the framework generator when a scope never saw them — a
    deserialized program or a fresh Scope carries no record-time seeding,
    and a missing KEY is not a user error the way a missing weight is."""
    rng_keys = {op.input_names[0]
                for op in program.global_block().ops
                if op.prim == "key_advance"}
    vals = []
    for n in persist_names:
        v = scope.find_var(n)
        if v is None:
            if n in rng_keys:
                from ..framework.random import key_raw, default_generator
                v = key_raw(default_generator.next_key())
                scope.set_var(n, v)
            else:
                raise RuntimeError(
                    f"persistable {n!r} not initialized — run the startup "
                    f"program first (exe.run(paddle.static."
                    f"default_startup_program()))")
        vals.append(v)
    return vals


class Executor:
    """executor.py:475 parity."""

    def __init__(self, place=None):
        self.place = place
        self._cache = {}
        self._aot_dir = None
        self._cache_extra_key = None
        # train_from_dataset replays, keyed per (program, feeds, fetches):
        # re-jitting the epoch scan every call would pay a full XLA
        # recompile per epoch (jit caching lives on the jitted callable)
        self._epoch_fn_cache = {}

    # -- AOT executable cache (inference/api SetOptimCacheDir parity) --------
    def set_aot_cache_dir(self, path):
        """Persist compiled PJRT executables under ``path`` so a process
        restart replays them instead of recompiling — the TPU seat of the
        reference's optimization-cache dir (analysis_config SetOptimCacheDir)
        and TensorRT engine serialization.  Entries go through
        ``jit.persistent_cache`` (atomic writes + sha256 manifests, the
        checkpoint discipline), so a torn write can never poison a load."""
        import os
        os.makedirs(path, exist_ok=True)
        self._aot_dir = path

    def _exec_cache(self):
        """(cache, writable): the legacy per-predictor optim-cache dir
        (always readwrite — the caller asked for it explicitly) or the
        FLAGS_executable_cache global dir; (None, False) when neither is
        configured — the one off-path branch."""
        from ..jit import persistent_cache as _pcache
        if self._aot_dir is not None:
            return _pcache.cache_at(self._aot_dir), True
        c = _pcache.get_cache()
        if c is not None:
            return c, _pcache.mode() == "readwrite"
        return None, False

    def set_cache_extra_key(self, key):
        """Fold an extra token into the AOT executable digest — the
        Predictor passes the model's quantization signature here so int8
        and float programs sharing one optim-cache dir never collide onto
        each other's serialized executables."""
        self._cache_extra_key = None if key is None else str(key)

    def _aot_digest(self, program, feed_names, feed_vals, union,
                    persist_names, persist_vals):
        """Restart-stable executable key: program structure + IO signature
        (program._uid is per-process, useless across restarts)."""
        import hashlib
        h = hashlib.sha1()

        def attr_bytes(v):
            # arrays hash by VALUE (repr elides large arrays, and any
            # truncation lets distinct programs collide onto a stale
            # executable); everything else hashes its full repr
            if hasattr(v, "dtype") and hasattr(v, "shape"):
                a = np.asarray(v)
                return f"{a.shape}:{a.dtype}:".encode() + a.tobytes()
            return repr(v).encode()

        for op in program.global_block().ops:
            h.update(repr((op.prim, tuple(op.input_names),
                           tuple(op.output_names))).encode())
            for k in sorted(op.attrs or {}):
                h.update(k.encode())
                h.update(attr_bytes(op.attrs[k]))
        for n, v in zip(feed_names, feed_vals):
            h.update(f"{n}:{v.shape}:{v.dtype}".encode())
        for n, v in zip(persist_names, persist_vals):
            h.update(f"{n}:{getattr(v, 'shape', ())}:"
                     f"{getattr(v, 'dtype', '')}".encode())
        h.update(repr(tuple(union)).encode())
        if self._cache_extra_key is not None:
            h.update(self._cache_extra_key.encode())
        return h.hexdigest()


    # -- eager interpretation (startup programs / debugging) -----------------
    def _run_eager(self, program: Program, scope: Scope):
        env = {}
        for op in program.global_block().ops:
            ins = [self._lookup(n, env, scope, program) for n in op.input_names]
            outs = op.run_fn()(*ins)
            for name, val in zip(op.output_names, outs):
                env[name] = val
        self._writeback(program, env, scope)
        return env

    @staticmethod
    def _lookup(name, env, scope, program):
        if name in env:
            return env[name]
        v = scope.find_var(name)
        if v is None:
            raise RuntimeError(f"variable {name!r} has no value (not fed, "
                               f"not initialized in scope)")
        return v

    @staticmethod
    def _writeback(program, env, scope):
        for b in program.blocks:
            for name, var in b.vars.items():
                if var.persistable and name in env:
                    scope.set_var(name, env[name])

    # -- compiled run --------------------------------------------------------
    def _persistable_names(self, program):
        names = []
        for b in program.blocks:
            for name, var in b.vars.items():
                if var.persistable and name not in names:
                    names.append(name)
        return names

    def _build_replay(self, program, feed_names, fetch_names, persist_names,
                      written):
        ops = program.global_block().ops

        def replay(feed_vals, persist_vals):
            env = dict(zip(feed_names, feed_vals))
            env.update(zip(persist_names, persist_vals))
            for op in ops:
                ins = [env[n] for n in op.input_names]
                outs = op.run_fn()(*ins)
                for name, val in zip(op.output_names, outs):
                    env[name] = val
            fetches = tuple(env[n] for n in fetch_names)
            updates = tuple(env[n] for n in written)
            return fetches, updates

        return replay

    def run(self, program=None, feed=None, fetch_list=None, scope=None,
            return_numpy=True, use_program_cache=True):
        program = program or default_main_program()
        compiled = getattr(program, "_compiled_program", None)
        if compiled is None and type(program).__name__ == "CompiledProgram":
            compiled = program
            program = compiled._program
        scope = scope or global_scope()
        feed = feed or {}
        fetch_list = fetch_list or []

        # startup / init programs: run once, eagerly
        if any(op.prim == "@init" for op in program.global_block().ops):
            self._run_eager(program, scope)
            return []

        fetch_names = [f.name if isinstance(f, Variable) else str(f)
                       for f in fetch_list]
        with _span("executor::data_feed"):
            feed_items = sorted(feed.items())
            feed_names = [k for k, _ in feed_items]
            feed_vals = [v._value if isinstance(v, Tensor)
                         else jnp.asarray(v) for _, v in feed_items]

        persist_names = self._persistable_names(program)
        written = [n for n in persist_names
                   if any(n in op.output_names
                          for op in program.global_block().ops)]

        # cache per (program, feed signature); the compiled replay returns
        # the UNION of all fetch sets seen so far, so alternating fetch
        # lists (loss-only vs loss+acc) share one compiled program instead
        # of one per distinct fetch tuple. A new fetch name recompiles
        # once, then the union is stable.
        key = (program._uid, program._version,
               tuple((n, v.shape, str(v.dtype))
                     for n, v in zip(feed_names, feed_vals)))
        entry = self._cache.get(key) if use_program_cache else None
        fresh = entry is None or not set(fetch_names) <= set(entry[0])
        aot_loaded = False
        if fresh:
            t_compile = time.perf_counter()
            union = list(entry[0]) if entry else []
            union += [n for n in fetch_names if n not in union]
            replay = self._build_replay(program, feed_names, union,
                                        persist_names, written)
            jitted = None
            pcache, pc_writable = (self._exec_cache() if compiled is None
                                   else (None, False))
            if pcache is not None:
                # AOT executable cache: lowering needs the persist values,
                # so gather them here (run() re-gathers below — cheap dict
                # reads)
                pv = [scope.find_var(n) for n in persist_names]
                if all(v is not None for v in pv):
                    from ..jit import persistent_cache as _pcache
                    digest = _pcache.digest_for(
                        ("executor",),
                        extra_key=self._aot_digest(program, feed_names,
                                                   feed_vals, union,
                                                   persist_names, pv))
                    t_load = time.perf_counter()
                    jitted = pcache.load(digest)
                    aot_loaded = jitted is not None
                    if aot_loaded:
                        _pcache.note_hit("executor_aot",
                                         time.perf_counter() - t_load)
                    else:
                        _pcache.note_miss("executor_aot")
                        with _span("executor::compile"):
                            compiled_exe = jax.jit(replay).lower(
                                feed_vals, pv).compile()
                        if pc_writable:
                            pcache.store(
                                digest, compiled_exe,
                                key=key + (tuple(union),),
                                site=f"executor:{program._uid}",
                                kind="executor_aot")
                        jitted = compiled_exe
                        from ..utils.monitor import stat_add
                        stat_add("STAT_executor_compiles")
            if jitted is None:
                jitted = jax.jit(replay)
                from ..utils.monitor import stat_add
                stat_add("STAT_executor_compiles")
            entry = (union, jitted, persist_names, written)
            self._cache[key] = entry
        union, jitted, persist_names, written = entry
        fetch_pos = [union.index(n) for n in fetch_names]

        for hook in getattr(program, "_pre_run_hooks", []):
            hook(scope)

        persist_vals = _collect_persistables(program, scope,
                                             persist_names)
        site = f"executor:{program._uid}"

        if fresh:
            from ..analysis import lint_enabled as _lint_on
            if _lint_on():
                # graph lint over the fresh program (abstract eval only,
                # amortized per compile): the jaxpr passes see the whole
                # replay; program_info adds the op-level fetch view so
                # dead-fetch names the op, not a jaxpr equation
                from ..analysis import lint_traced
                _ops = [(getattr(op, "type", op.prim),
                         tuple(op.input_names), tuple(op.output_names))
                        for op in program.global_block().ops]
                lint_traced(
                    replay, (feed_vals, persist_vals),
                    site=site, kind="executor",
                    cache_key=key + (tuple(union),),
                    prev_key=_ledger.last_key(site),
                    program_info={"ops": _ops, "fetches": union,
                                  "written": written,
                                  "persistable": persist_names,
                                  "feeds": feed_names})

        if compiled is not None and compiled._data_parallel:
            from ..parallel.api import batch_sharding
            from ..parallel.mesh import get_mesh
            mesh = get_mesh()
            with _span("executor::data_feed"):
                feed_vals = [jax.device_put(
                    v, batch_sharding(mesh, ndim=max(v.ndim, 1)))
                    for v in feed_vals]

        if fresh:
            # trace + XLA compile happen inside this first dispatch (the
            # AOT path compiled above; a deserialized executable skipped
            # it) — ledger the wall time and the cache-key diff.  A
            # persistent-cache load is ledgered as ``cache_load`` so warm
            # starts show zero fresh XLA compiles while the steady-state
            # checks keep counting events at this site unchanged.
            with _span("executor::compile"):
                fetches, updates = jitted(feed_vals, persist_vals)
            _ledger.record_compile(
                site, "cache_load" if aot_loaded else "executor",
                key + (tuple(union),),
                (time.perf_counter() - t_compile) * 1e3,
                extra={"orig_kind": "executor_aot"} if aot_loaded
                else None)
        else:
            _ledger.record_cache_hit(site)
            with _span("executor::device_execute"):
                fetches, updates = jitted(feed_vals, persist_vals)
                if _prof_on():
                    # fence so the span reflects device time, not just
                    # async dispatch
                    jax.block_until_ready((fetches, updates))
        for n, val in zip(written, updates):
            scope.set_var(n, val)
        picked = [fetches[i] for i in fetch_pos]
        if return_numpy:
            with _span("executor::fetch"):
                return [np.asarray(f) for f in picked]
        return [Tensor(f) for f in picked]

    def _epoch_entry(self, program, feed_names, fetch_names):
        """The jitted scanned-epoch function for ``program`` — one per
        (program, feed/fetch set): later calls (and later EPOCHS through
        them) hit jax.jit's executable cache instead of retracing +
        recompiling the epoch program every time.  Keyed like exe.run's
        compile cache (program _uid + _version: rewrite passes bump
        _version, compiler.py:110); FIFO-bounded so a long-lived Executor
        over many programs cannot grow unboundedly.  Returns
        ``(jitted_epoch_fn, persist_names)``."""
        persist_names = self._persistable_names(program)
        ck = (program._uid, program._version,
              tuple(op.type for op in program.global_block().ops),
              tuple(feed_names), tuple(fetch_names), tuple(persist_names))
        cached = self._epoch_fn_cache.get(ck)
        if cached is None and len(self._epoch_fn_cache) >= 8:
            self._epoch_fn_cache.pop(next(iter(self._epoch_fn_cache)))
        if cached is None:
            written = [n for n in persist_names
                       if any(n in op.output_names
                              for op in program.global_block().ops)]
            replay = self._build_replay(program, feed_names, fetch_names,
                                        persist_names, written)
            w_pos = [persist_names.index(n) for n in written]

            def epoch_fn(persist_vals, feed_stacks, mask):
                def step(carry, xs):
                    feeds, m = xs[:-1], xs[-1]
                    fetches, updates = replay(list(feeds), list(carry))
                    carry = list(carry)
                    for p, u in zip(w_pos, updates):
                        # masked tail steps keep the carry (padding must
                        # not apply optimizer updates)
                        carry[p] = jnp.where(m, u, carry[p])
                    return tuple(carry), fetches
                return jax.lax.scan(step, tuple(persist_vals),
                                    (*feed_stacks, mask))

            cached = (jax.jit(epoch_fn), program)
            self._epoch_fn_cache[ck] = cached
        return cached[0], persist_names

    def epoch_executable(self, program=None, dataset=None, fetch_list=None,
                         scope=None, chunk_steps=256):
        """AOT-lower the scanned epoch program for ``dataset`` and return
        the compiled executable WITHOUT running the epoch — the
        lowered-executable access surface for the dataset-training engine
        (the HLO audit reads ``cost_analysis()`` /
        ``memory_analysis()`` / ``as_text()`` off it; the hand-maintained
        FLOP models this replaces could silently drift from the program).

        ``dataset`` must be a dict of pre-stacked arrays
        ``{var_name: [steps, ...]}`` (the bench/mfu shape); at most
        ``chunk_steps`` leading steps are lowered.
        """
        program = program or default_main_program()
        scope = scope or global_scope()
        if not isinstance(dataset, dict) or not dataset:
            raise TypeError("epoch_executable needs a dict of pre-stacked "
                            "arrays {var_name: [steps, ...]}")
        feed_names = sorted(dataset)
        fetch_list = fetch_list or []
        fetch_names = [f.name if isinstance(f, Variable) else str(f)
                       for f in fetch_list]
        jitted, persist_names = self._epoch_entry(program, feed_names,
                                                  fetch_names)
        k = min(int(chunk_steps),
                len(next(iter(dataset.values()))))
        feeds = tuple(jnp.asarray(dataset[n][:k]) for n in feed_names)
        mask = jnp.ones((k,), bool)
        persist_vals = tuple(_collect_persistables(program, scope,
                                                   persist_names))
        return jitted.lower(persist_vals, feeds, mask).compile()

    # -- dataset-driven training (Trainer/DeviceWorker runtime) -------------
    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100, epochs=1,
                           chunk_steps=256):
        """trainer.h:51 / device_worker.h parity: stream the dataset
        through a compiled scan — no Python between steps, bounded HBM.

        The reference's DistMultiTrainer spins C++ DeviceWorkers that pull
        minibatches from a DataFeed CHANNEL (data_feed.h:305) and run the
        op graph per batch.  The TPU-shape of that channel: host-stack the
        feeds in chunks of ``chunk_steps``, double-buffer each chunk onto
        the device while the previous chunk's ``lax.scan`` runs, and carry
        the persistables across chunks inside one jitted scan per chunk
        shape.  Peak device memory holds ~2 chunks + parameters instead of
        the whole epoch; the tail chunk pads to an adaptive bucket with a
        per-step validity mask (masked steps keep the carry), so one
        compiled program serves every full chunk.

        ``dataset``: an iterable of feed dicts {var_name: ndarray}, an
        io.DataLoader yielding such dicts, or a dict of pre-stacked
        arrays {var_name: [steps, ...]}.
        Returns {fetch_name: [epochs*steps, ...] numpy} for fetch_list.
        """
        import itertools
        program = program or default_main_program()
        scope = scope or global_scope()
        if dataset is None:
            raise ValueError("train_from_dataset needs a dataset")
        fetch_list = fetch_list or []
        fetch_names = [f.name if isinstance(f, Variable) else str(f)
                       for f in fetch_list]
        chunk_steps = max(1, int(chunk_steps))

        # -- the DataFeed channel: a re-iterable source of host chunks ----
        if isinstance(dataset, dict):
            if not dataset:
                raise ValueError("train_from_dataset: empty dataset")
            # values already on DEVICE stay there: chunk by device-side
            # slicing (pulling them to host and re-uploading per epoch
            # would cost two full-epoch host transfers for nothing)
            host = {k: (v if isinstance(v, jax.Array) else np.asarray(v))
                    for k, v in dataset.items()}
            n_total = len(next(iter(host.values())))

            def raw_chunks():
                for s in range(0, n_total, chunk_steps):
                    yield {k: v[s:s + chunk_steps] for k, v in host.items()}
        else:
            if iter(dataset) is dataset:
                # one-shot iterator: materialize HOST-side once (epochs may
                # re-read); device memory stays chunk-bounded regardless
                dataset = list(dataset)

            def raw_chunks():
                buf, count = {}, 0
                for feed in dataset:
                    for k, v in feed.items():
                        buf.setdefault(k, []).append(np.asarray(
                            v.numpy() if isinstance(v, Tensor) else v))
                    count += 1
                    if count == chunk_steps:
                        yield {k: np.stack(vs) for k, vs in buf.items()}
                        buf, count = {}, 0
                if count:
                    yield {k: np.stack(vs) for k, vs in buf.items()}

        # epoch 0 fills a host-side chunk cache; later epochs replay it
        # instead of re-stacking every feed (the chunks ARE the host copy)
        _chunk_cache: list = []

        def chunk_iter():
            if _chunk_cache:
                yield from _chunk_cache
                return
            for ch in raw_chunks():
                _chunk_cache.append(ch)
                yield ch

        head_it = chunk_iter()
        first = next(head_it, None)
        if first is None:
            raise ValueError("train_from_dataset: empty dataset")
        feed_names = sorted(first)

        jitted, persist_names = self._epoch_entry(program, feed_names,
                                                  fetch_names)

        def upload(chunk):
            """Pad to a stable bucket, ship to device (async H2D)."""
            from ..distributed.ps.device_cache import pad_adaptive
            sp = _span("executor::dataset_upload")
            sp.begin()
            n = len(chunk[feed_names[0]])
            # tail buckets never exceed the full-chunk shape (the documented
            # device budget), and near-full tails reuse the full compile
            k = (chunk_steps if n == chunk_steps
                 else min(pad_adaptive(n), chunk_steps))
            mask = np.zeros(k, bool)
            mask[:n] = True
            feeds = []
            nbytes = 0
            for name in feed_names:
                v = chunk[name]
                if len(v) < k:
                    xp = jnp if isinstance(v, jax.Array) else np
                    v = xp.concatenate(
                        [v, xp.zeros((k - len(v),) + v.shape[1:],
                                     v.dtype)])
                nbytes += v.nbytes
                # device_put is a no-op for arrays already on device
                feeds.append(jax.device_put(v))
            self._train_stats["max_chunk_bytes"] = max(
                self._train_stats["max_chunk_bytes"], nbytes)
            sp.end()
            return tuple(feeds), jax.device_put(mask), n

        persist_vals = tuple(_collect_persistables(program, scope,
                                                   persist_names))

        self._train_stats = {"chunks": 0, "max_chunk_bytes": 0}
        all_fetches = {n: [] for n in fetch_names}
        for ep in range(epochs):
            chunks = (itertools.chain([first], head_it) if ep == 0
                      else chunk_iter())
            pending = upload(next(chunks))
            while pending is not None:
                feeds, mask, n_valid = pending
                nxt = next(chunks, None)
                with _span("executor::dataset_scan"):
                    persist_vals, fetches = jitted(persist_vals, feeds,
                                                   mask)
                # double buffer: ship chunk i+1 while chunk i scans
                pending = upload(nxt) if nxt is not None else None
                self._train_stats["chunks"] += 1
                for n, f in zip(fetch_names, fetches):
                    all_fetches[n].append(np.asarray(f)[:n_valid])
            if debug and fetch_names:
                head = fetch_names[0]
                _last = all_fetches[head][-1]
                print(f"[train_from_dataset] epoch {ep}: {head} "
                      f"mean={np.mean(_last):.6f}")
        for n, val in zip(persist_names, persist_vals):
            scope.set_var(n, val)
        return {n: np.concatenate(v) if v else np.array([])
                for n, v in all_fetches.items()}

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        """Inference twin of train_from_dataset (same scanned engine; the
        program simply has no optimizer ops, so nothing is written back)."""
        return self.train_from_dataset(program, dataset, scope, thread,
                                       debug, fetch_list, fetch_info,
                                       print_period, epochs=1)

    def close(self):
        self._cache.clear()
