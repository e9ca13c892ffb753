"""The compiled, sharded train step — the framework's execution heart.

Reference parity: this one class replaces the reference's entire hot path —
the Executor op loop (paddle/fluid/framework/executor.cc:473), the
ParallelExecutor SSA-graph engine with its AllReduceOpHandles
(parallel_executor.cc:613, details/all_reduce_op_handle.cc), the dygraph
Reducer's bucketed overlap-allreduce (imperative/reducer.cc:100), and the
optimizer graph ops (operators/optimizers/).

TPU-first: forward + loss + backward (jax.grad over the functional bridge)
+ optimizer update are ONE jitted function.  pjit/GSPMD shards it over the
global mesh from PartitionSpec annotations, so DP gradient all-reduce,
TP activation collectives and ZeRO-sharded optimizer states all come out of
the same compiled program, overlapped by the XLA scheduler (the hand-built
overlap machinery of reducer.cc is the compiler's job here).

Options map to reference strategies:
  remat=True            ≙ RecomputeOptimizer (fluid/optimizer.py:4533)
  zero=1                ≙ ShardingOptimizer stage-1 (sharding_optimizer.py:33)
  accumulate_steps=k    ≙ GradientMergeOptimizer (fluid/optimizer.py:5011)
  loss_scale / bf16     ≙ mixed-precision decorator (contrib/mixed_precision/)
"""
from __future__ import annotations

import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..framework.tensor import Tensor
from ..framework import functional as F
from ..framework import flags as _flags
from ..profiler import RecordEvent, ledger as _ledger
from ..profiler import profiling_enabled as _prof_on
from ..profiler import span as _span
from ..profiler import tracing as _tracing
from ..profiler.metrics import default_registry as _registry
from .mesh import get_mesh, DP_AXIS
from .api import named_shardings, batch_sharding

# per-phase step-time breakdown (FLAGS_trace gates observation: the
# device_fence segment needs a block_until_ready the untraced hot path
# must not pay).  host_prep = feed placement; dispatch = handing the
# compiled step to the runtime (async); device_fence = blocking on the
# step's outputs.  Purely host-side timing — observing a step never
# changes the traced program or adds a compile key.
_STEP_PHASE = _registry().histogram(
    "train_step_phase_seconds",
    "Per-phase train-step wall segments under FLAGS_trace "
    "(host_prep / dispatch / device_fence).",
    labels=("phase",))

_NULL_CM = contextlib.nullcontext()     # shared no-op (reentrant, stateless)


def _as_array(x):
    if x is None:
        return None
    if isinstance(x, Tensor):
        return x._value
    return jnp.asarray(x)


def _global_put(v, sharding):
    """device_put that also works when ``sharding`` spans processes (the
    multi-host SPMD path: jax.distributed has formed a global mesh, as the
    reference's c_comm_init builds cross-node NCCL rings,
    operators/collective/c_comm_init_op.cc:123).  Host data is the SPMD
    contract — identical on every process — so each process materializes its
    addressable shards; single-device jax arrays are pulled to host first."""
    if jax.process_count() > 1 and isinstance(v, jax.Array):
        if not v.is_fully_addressable:
            return jax.device_put(v, sharding)  # global→global reshard
        v = np.asarray(v)
    return jax.device_put(v, sharding)


def _wrap_loss(loss_fn):
    """Run a Tensor-level loss (e.g. nn.CrossEntropyLoss) on raw arrays."""
    def run(out, label):
        from ..framework import core
        with core.no_grad_guard():
            o = Tensor(out) if not isinstance(out, Tensor) else out
            l = Tensor(label)
            res = loss_fn(o, l)
        return res._value if isinstance(res, Tensor) else res
    return run


class TrainStep:
    """Compile ``layer`` + ``loss_fn`` + ``optimizer`` into one sharded step.

    step semantics: ``loss = loss_fn(layer(*inputs), label)``; if ``loss_fn``
    is None the layer is called with the full batch and must return the loss.
    """

    def __init__(self, layer, optimizer, loss_fn=None, *, mesh=None,
                 remat: bool = False, zero: int = 0, accumulate_steps: int = 1,
                 donate: bool = True, seed: int = 0,
                 batch_spec=None, compute_dtype=None,
                 localsgd_k: int = 0, localsgd_begin: int = 1,
                 dgc_sparsity: float = 0.0, dgc_momentum: float = 0.9,
                 dgc_rampup_begin: int = 1,
                 sentinel: bool = None, grad_scaler=None,
                 checkpoint_manager=None):
        self.layer = layer
        self.optimizer = optimizer
        self.loss_fn = _wrap_loss(loss_fn) if loss_fn is not None else None
        self.mesh = mesh or get_mesh()
        self.remat = remat
        self.zero = zero
        self.accumulate_steps = int(accumulate_steps)
        self.seed = seed
        self.batch_spec = batch_spec
        self.compute_dtype = compute_dtype
        # LocalSGD (meta_optimizers/localsgd_optimizer.py parity): each dp
        # rank trains its OWN parameter copy for k steps, then copies are
        # averaged. TPU-shape: params/opt-state carry a leading dp-sharded
        # axis and the step vmaps over it — per-rank updates stay local
        # (zero collectives) until the periodic mean. localsgd_begin is the
        # warmup boundary: before it, every step syncs (adaptive ramp-in).
        self.localsgd_k = int(localsgd_k)
        self.localsgd_begin = int(localsgd_begin)
        if self.localsgd_k > 1 and zero:
            raise ValueError(
                "localsgd does not compose with sharding (zero) in this "
                "engine: per-rank replicas need the whole parameter tree "
                "local, ZeRO shards it over the same dp axis "
                "(strategy-ledger row localsgd+sharding)")
        # DGC (meta_optimizers/dgc_optimizer.py / operators/dgc_op.h
        # parity as an ENGINE mode): per-dp-rank momentum correction +
        # residual accumulation + sampled top-k sparsification BEFORE the
        # cross-rank mean — the wire-compression algorithm expressed as a
        # vmap over per-rank gradient shards.  The momentum lives INSIDE
        # the compression (DGCMomentumOptimizer), so pair it with a plain
        # SGD outer optimizer; with sparsity→0 the mode reduces exactly
        # to dense Momentum(dgc_momentum).
        self.dgc_sparsity = float(dgc_sparsity)
        self.dgc_momentum = float(dgc_momentum)
        self.dgc_rampup_begin = int(dgc_rampup_begin)
        if self.dgc_sparsity > 0 and (zero or self.localsgd_k > 1):
            raise ValueError(
                "dgc composes with neither sharding (zero) nor localsgd in "
                "this engine: its per-rank u/v state assumes replicated "
                "params and a single compression point per step; localsgd "
                "has no per-step gradient exchange to compress")
        if not (0.0 <= self.dgc_sparsity < 1.0):
            raise ValueError("dgc_sparsity must be in [0, 1)")
        if self.dgc_sparsity > 0 and getattr(optimizer, "_momentum", 0):
            raise ValueError(
                "dgc carries its own momentum correction (dgc_momentum); "
                "a Momentum outer optimizer would compound momentum twice "
                "— use plain SGD (fleet's strategy.dgc performs this swap "
                "and carries the coefficient automatically)")
        self._state = None
        self._compiled = None
        self._donate = donate
        self._seen_sigs = set()     # input signatures already compiled
        self._autoshard_plan = None  # set by init_state when autoshard on
        # -- fault-tolerance runtime (ISSUE 3) --------------------------------
        # numerics sentinel: None = follow FLAGS_train_sentinel at compile
        # time; an explicit True composes only with the standard engine
        # path (checked in compile()). grad_scaler: an amp.GradScaler —
        # when enabled, the loss is scaled IN-GRAPH (scale rides as a
        # traced operand, so scale changes never recompile), grads are
        # unscaled before the optimizer, and the sentinel verdict drives
        # the scaler's dynamic backoff.
        self._sentinel_requested = sentinel
        self._sentinel_active = False
        self._sentinel_names = ["loss"]
        self._bad_streak = 0
        self._host_step = 0
        self.grad_scaler = grad_scaler
        self.checkpoint_manager = checkpoint_manager

        from .pipeline import PipelineModule
        self._pipe = layer if isinstance(layer, PipelineModule) else None
        if self.localsgd_k > 1 and self._pipe is not None:
            raise ValueError("localsgd is a data-parallel strategy; it does "
                             "not compose with pipeline parallelism")
        if self.dgc_sparsity > 0 and self._pipe is not None:
            raise ValueError("dgc is a data-parallel strategy; it does not "
                             "compose with pipeline parallelism")
        if self._pipe is not None:
            # microbatching IS the gradient accumulation in a pipeline:
            # strategy accumulate_steps sets the GPipe microbatch count
            if self.accumulate_steps > 1:
                self._pipe.M = self.accumulate_steps
                self.accumulate_steps = 1
            self._pipe_fwd = self._pipe.build_body(remat=self.remat)

    # -- state ---------------------------------------------------------------
    def _param_sharding_tree(self, params):
        if self._pipe is not None:
            from .mesh import PP_AXIS
            shardings = {}
            for tag, layer in (("embed", self._pipe.embed),
                               ("head", self._pipe.head)):
                if layer is None:
                    continue
                sub = named_shardings(layer, self.mesh)
                shardings.update({f"{tag}::{n}": s for n, s in sub.items()})
            pp_live = self.mesh.shape.get(PP_AXIS, 1) > 1
            for n in params:
                if n.startswith("pipe::"):
                    shardings[n] = NamedSharding(
                        self.mesh, P(PP_AXIS) if pp_live else P())
        else:
            shardings = named_shardings(self.layer, self.mesh)
        return {n: shardings.get(n, NamedSharding(self.mesh, P()))
                for n in params}

    def _zero_spec(self, base_spec, shape):
        """Add a dp shard onto the first replicated, dp-divisible dim of a
        per-param array (the ZeRO layout rule)."""
        spec = list(base_spec) + [None] * (len(shape) - len(base_spec))

        def has_dp(entry):
            return entry == DP_AXIS or (
                isinstance(entry, (tuple, list)) and DP_AXIS in entry)
        if any(has_dp(e) for e in spec):
            return P(*spec)  # already ZeRO-laid-out (idempotent)
        if self.mesh.shape.get(DP_AXIS, 1) > 1:
            for d in range(len(shape)):
                if spec[d] is None and shape[d] % self.mesh.shape[DP_AXIS] == 0:
                    spec[d] = DP_AXIS
                    break
        return P(*spec)

    def _opt_sharding(self, param_shardings, opt_state):
        """Optimizer accumulators inherit their param's spec; with zero>=1 the
        first fully-replicated dim additionally shards over dp (ZeRO-1:
        sharding_optimizer.py:33 equivalent, but as a layout annotation)."""
        out = {}
        for sname, acc in opt_state.items():
            out[sname] = {}
            for pname, arr in acc.items():
                spec = param_shardings[pname].spec
                if self.zero >= 1:
                    spec = self._zero_spec(spec, arr.shape)
                out[sname][pname] = NamedSharding(self.mesh, spec)
        return out

    def _localsgd_degree(self):
        return self.mesh.shape.get(DP_AXIS, 1) if self.localsgd_k > 1 else 0

    def init_state(self):
        if self._pipe is not None:
            params, buffers = self._pipe.flat_state()
        else:
            # rules-driven auto-sharding (analysis.autoshard, ISSUE 9):
            # FLAGS_autoshard=apply annotates unannotated params from the
            # active PartitionRules table BEFORE the sharding tree below
            # reads the annotations; =propose publishes the plan without
            # mutating. One branch when off. The plan rides to the
            # compile-site lint (autoshard-conflict / sharding-coverage).
            from ..analysis.autoshard import maybe_autoshard
            self._autoshard_plan = maybe_autoshard(
                self.layer, mesh=self.mesh,
                site=f"train_step:{type(self.layer).__name__}")
            params, buffers = F.layer_state(self.layer)
        D = self._localsgd_degree()
        if D > 1:
            # per-rank copies: leading dp-sharded axis on params, buffers
            # and optimizer state; one copy per device, same memory as
            # replicated storage
            pshard = self._param_sharding_tree(params)
            rank_shard = {n: NamedSharding(self.mesh, P(DP_AXIS, *s.spec))
                          for n, s in pshard.items()}
            base = dict(params)
            opt_base = self.optimizer.functional_state(base)
            # accumulators matching the param shape inherit its rank spec;
            # scalar/odd-shaped ones just shard the leading rank axis
            oshard = {s: {n: (rank_shard[n] if v.shape == base[n].shape
                              else NamedSharding(self.mesh, P(DP_AXIS)))
                          for n, v in acc.items()}
                      for s, acc in opt_base.items()}
            buf_shard = NamedSharding(self.mesh, P(DP_AXIS))
            rep_n = lambda v: jnp.broadcast_to(v, (D,) + v.shape)
            params = {n: _global_put(rep_n(v), rank_shard[n])
                      for n, v in base.items()}
            buffers = {n: _global_put(rep_n(v), buf_shard)
                       for n, v in buffers.items()}
            opt_state = {s: {n: _global_put(rep_n(v), oshard[s][n])
                             for n, v in acc.items()}
                         for s, acc in opt_base.items()}
            rep = NamedSharding(self.mesh, P())
            self._state = {
                "params": params, "buffers": buffers, "opt": opt_state,
                "step": _global_put(np.zeros((), np.int32), rep),
            }
            self._shardings = {
                "params": rank_shard,
                "buffers": {n: buf_shard for n in buffers},
                "opt": oshard,
                "step": rep,
            }
            self._grad_shardings = None
            return self._state
        pshard = self._param_sharding_tree(params)
        if self.zero >= 3:
            # ZeRO-3: parameters themselves are stored dp-sharded; GSPMD
            # all-gathers each param at its use sites inside the step
            # (sharding_optimizer.py stage-3 param shard + broadcast)
            pshard = {n: NamedSharding(
                self.mesh, self._zero_spec(s.spec, params[n].shape))
                for n, s in pshard.items()}
        if self.zero >= 2:
            # ZeRO-2: gradients leave the backward pass reduce-scattered
            # over dp (sharding_optimizer.py stage-2 grad shard); the same
            # layout rule as the opt state so the update is local
            self._grad_shardings = {
                n: NamedSharding(self.mesh,
                                 self._zero_spec(pshard[n].spec,
                                                 params[n].shape))
                for n in params}
        else:
            self._grad_shardings = None
        params = {n: _global_put(v, pshard[n]) for n, v in params.items()}
        rep = NamedSharding(self.mesh, P())
        buffers = {n: _global_put(v, rep) for n, v in buffers.items()}
        opt_state = self.optimizer.functional_state(params)
        oshard = self._opt_sharding(pshard, opt_state)
        opt_state = {s: {n: _global_put(v, oshard[s][n])
                         for n, v in acc.items()}
                     for s, acc in opt_state.items()}
        self._state = {
            "params": params, "buffers": buffers, "opt": opt_state,
            "step": _global_put(np.zeros((), np.int32), rep),
        }
        self._shardings = {"params": pshard, "buffers": {n: rep for n in buffers},
                          "opt": oshard, "step": rep}
        if self.dgc_sparsity > 0:
            # per-rank momentum-correction (u) and residual (v) buffers,
            # one slice per dp rank (dgc_op.h U/V state)
            D = max(1, self.mesh.shape.get(DP_AXIS, 1))
            ushard = {n: NamedSharding(self.mesh, P(DP_AXIS, *pshard[n].spec))
                      for n in params}
            for tag in ("dgc_u", "dgc_v"):
                self._state[tag] = {
                    n: _global_put(np.zeros((D,) + tuple(v.shape),
                                            np.float32), ushard[n])
                    for n, v in params.items()}
                self._shardings[tag] = ushard
        return self._state

    @property
    def state(self):
        if self._state is None:
            self.init_state()
        return self._state

    # -- step function -------------------------------------------------------
    @staticmethod
    def _cast_compute(params, buffers, inputs, cd):
        """Low-precision compute cast for params and float inputs. Buffers
        (BN running stats) deliberately stay fp32: each op re-casts its
        output to the activation dtype, so stats never leak fp32 into the
        compute path, and casting them would round-trip the running
        averages through bf16 every step (losing small-momentum updates).
        Returns (params, buffers, inputs)."""
        fl = lambda v: jnp.issubdtype(v.dtype, jnp.floating)
        with jax.named_scope("cast_params"):
            params = {n: (v.astype(cd) if fl(v) else v)
                      for n, v in params.items()}
            inputs = tuple(x.astype(cd) if x is not None and fl(x) else x
                           for x in inputs)
        return params, buffers, inputs

    def _pipe_loss_of(self, params, buffers, inputs, label, rng_key):
        """Pipelined forward: embed (replicated) → GPipe trunk over pp →
        head (replicated) → loss.  One SPMD program; jax.grad reverses the
        whole schedule."""
        if self.compute_dtype is not None:
            params, buffers, inputs = self._cast_compute(
                params, buffers, inputs, self.compute_dtype)

        def sub(tree, tag):
            pre = tag + "::"
            return {n[len(pre):]: v for n, v in tree.items()
                    if n.startswith(pre)}

        pipe = self._pipe
        new_buffers = dict(buffers)
        if pipe.embed is not None:
            x, eb = F.functional_call(
                pipe.embed, sub(params, "embed"), sub(buffers, "embed"),
                inputs, training=True, rng_key=rng_key, mutable_buffers=True)
            if isinstance(x, (tuple, list)):
                x = x[0]
            new_buffers.update({f"embed::{n}": v for n, v in eb.items()})
        else:
            x = inputs[0]

        h = self._pipe_fwd(sub(params, "pipe"), x,
                           jax.random.fold_in(rng_key, 1))

        if pipe.head is not None:
            head_args = (h,) if self.loss_fn is not None or label is None \
                else (h, label)
            out, hb = F.functional_call(
                pipe.head, sub(params, "head"), sub(buffers, "head"),
                head_args, training=True,
                rng_key=jax.random.fold_in(rng_key, 2), mutable_buffers=True)
            new_buffers.update({f"head::{n}": v for n, v in hb.items()})
        else:
            out = h
        if isinstance(out, (tuple, list)):
            out = out[0]
        loss = self.loss_fn(out, label) if self.loss_fn is not None else out
        return loss.astype(jnp.float32).mean(), new_buffers

    def _loss_of(self, params, buffers, inputs, label, rng_key):
        if self.compute_dtype is not None:
            params, buffers, inputs = self._cast_compute(
                params, buffers, inputs, self.compute_dtype)
        if self.loss_fn is None:
            args = inputs if label is None else inputs + (label,)
            out, new_buffers = F.functional_call(
                self.layer, params, buffers, args, training=True,
                rng_key=rng_key, mutable_buffers=True)
            loss = out[0] if isinstance(out, (tuple, list)) else out
        else:
            out, new_buffers = F.functional_call(
                self.layer, params, buffers, inputs, training=True,
                rng_key=rng_key, mutable_buffers=True)
            if isinstance(out, (tuple, list)):
                out = out[0]
            with jax.named_scope("loss"):
                loss = self.loss_fn(out, label)
        with jax.named_scope("loss"):
            return loss.astype(jnp.float32).mean(), new_buffers

    def _rank_grad(self, loss_of, params, buffers, mb_in, mb_lb, key):
        """(loss, grads, new_buffers) for ONE dp rank's batch shard,
        gradient-merging over ``accumulate_steps`` microbatches first when
        configured.  This is GradientMergeOptimizer composed INSIDE the
        per-rank leg of localsgd/dgc (VERDICT r5 #7): the accumulation
        happens strictly BEFORE any compression or replica averaging, the
        same ordering fleet's strategy_compiler.py ranks the reference
        meta-optimizers in."""
        grad_fn = jax.value_and_grad(loss_of, has_aux=True)
        k = self.accumulate_steps
        if k <= 1:
            (loss, nb), g = grad_fn(params, buffers, mb_in, mb_lb, key)
            return loss, g, nb

        def split(x):
            if x is None:
                return None
            return x.reshape((k, x.shape[0] // k) + x.shape[1:])

        def micro(carry, mb):
            g_acc, l_acc, buf = carry
            mi, ml = mb
            (loss, buf), g = grad_fn(params, buf, mi, ml, key)
            g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
            return (g_acc, l_acc + loss, buf), None

        g0 = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (g, loss, nb), _ = jax.lax.scan(
            micro, (g0, jnp.float32(0.0), buffers),
            (tuple(split(x) for x in mb_in),
             None if mb_lb is None else split(mb_lb)))
        g = jax.tree_util.tree_map(lambda q: q / k, g)
        return loss / k, g, nb

    def _build_localsgd_step(self):
        """LocalSGD step: vmap the (grad + update) over the per-rank leading
        axis — each dp rank advances its own replica from its own batch
        shard; every localsgd_k-th step (and every step before
        localsgd_begin) the replicas are averaged
        (localsgd_optimizer.py:440's allreduce-of-params, here one mean
        over the dp-sharded axis)."""
        loss_of = self._loss_of
        if self.remat:
            loss_of = jax.checkpoint(loss_of, static_argnums=())
        D = self._localsgd_degree()
        k = self.localsgd_k
        begin = self.localsgd_begin

        def step(state, inputs, label, lr, scale):
            new_step = state["step"] + 1
            base_key = jax.random.fold_in(jax.random.key(self.seed), new_step)

            def per_rank(p, b, o, mb_in, mb_lb, ridx):
                key = jax.random.fold_in(base_key, ridx)
                loss, g, nb = self._rank_grad(loss_of, p, b, mb_in, mb_lb,
                                              key)
                np_, no = self.optimizer.functional_apply(p, g, o, new_step,
                                                          lr)
                return loss, np_, nb, no

            def split(x):
                if x is None:
                    return None
                return x.reshape((D, x.shape[0] // D) + x.shape[1:])

            mb_in = tuple(split(x) for x in inputs)
            mb_lb = None if label is None else split(label)
            loss, new_params, new_buffers, new_opt = jax.vmap(
                per_rank, in_axes=(0, 0, 0, 0, 0, 0))(
                state["params"], state["buffers"], state["opt"],
                mb_in, mb_lb, jnp.arange(D))

            do_sync = jnp.logical_or(new_step < begin, new_step % k == 0)

            def avg(tree):
                return jax.tree_util.tree_map(
                    lambda v: jnp.broadcast_to(
                        jnp.mean(v, axis=0, keepdims=True,
                                 dtype=v.dtype if jnp.issubdtype(
                                     v.dtype, jnp.floating) else None),
                        v.shape) if jnp.issubdtype(v.dtype, jnp.floating)
                    else v,
                    tree)

            new_params, new_buffers = jax.lax.cond(
                do_sync, lambda t: (avg(t[0]), avg(t[1])), lambda t: t,
                (new_params, new_buffers))
            return {"params": new_params, "buffers": new_buffers,
                    "opt": new_opt, "step": new_step}, loss.mean()

        return step

    def _build_dgc_step(self):
        """DGC engine step (dgc_op.h + dgc_optimizer.py): the batch splits
        into dp shards; each rank's gradient passes momentum correction
        (u = m·u + g), residual accumulation (v += u), and sampled-top-k
        sparsification; the cross-rank mean runs on the SPARSE tensors and
        u/v keep the unsent mass (+ the sent mass is cleared from both).
        Before dgc_rampup_begin the step transmits v densely (and clears
        it), which makes the mode EXACTLY dense Momentum(dgc_momentum) —
        the rampup contract the reference's DGCMomentumOptimizer keeps."""
        loss_of = self._loss_of
        if self.remat:
            loss_of = jax.checkpoint(loss_of, static_argnums=())
        D = max(1, self.mesh.shape.get(DP_AXIS, 1))
        m = self.dgc_momentum
        sparsity = self.dgc_sparsity
        rampup = self.dgc_rampup_begin

        def sparsify(v):
            """Per-rank sampled threshold (the reference estimates the
            top-k cut from a gradient sample, dgc_op.h k-select)."""
            flat = jnp.abs(v.reshape(D, -1))
            n = flat.shape[1]
            stride = max(1, n // 4096)
            samp = flat[:, ::stride]
            thr = jnp.quantile(samp, sparsity, axis=1)      # [D]
            shape = (D,) + (1,) * (v.ndim - 1)
            return (jnp.abs(v) >= thr.reshape(shape)).astype(v.dtype)

        def step(state, inputs, label, lr, scale):
            new_step = state["step"] + 1
            base_key = jax.random.fold_in(jax.random.key(self.seed),
                                          new_step)

            def split(x):
                if x is None:
                    return None
                return x.reshape((D, x.shape[0] // D) + x.shape[1:])

            def per_rank(mb_in, mb_lb, ridx):
                key = jax.random.fold_in(base_key, ridx)
                # gradient_merge composes INSIDE the rank leg: the mean
                # microbatch gradient forms BEFORE momentum correction /
                # sparsification, so compression sees the merged gradient
                loss, g, nb = self._rank_grad(loss_of, state["params"],
                                              state["buffers"], mb_in,
                                              mb_lb, key)
                return loss, g, nb

            mb_in = tuple(split(x) for x in inputs)
            mb_lb = None if label is None else split(label)
            loss, grads, new_buffers = jax.vmap(
                per_rank, in_axes=(0, 0, 0))(mb_in, mb_lb, jnp.arange(D))
            # replicated buffers: consensus = mean of the rank copies
            new_buffers = {
                n: (jnp.mean(v, axis=0)
                    if jnp.issubdtype(v.dtype, jnp.floating) else v[0])
                for n, v in new_buffers.items()}

            def compress(g, u, v):
                u_m = m * u + g.astype(jnp.float32)
                dense = new_step < rampup
                # rampup: plain Momentum — persistent velocity, nothing
                # masked (DGCMomentumOptimizer 'behaves as normal Momentum
                # before rampup_begin_step')
                # dgc: residual accumulation + top-k masking; sent
                # coordinates clear BOTH u (momentum factor masking) and v
                v_s = v + u_m
                mask = sparsify(v_s)
                pick = lambda a, b: jnp.where(dense, a, b)  # noqa: E731
                send = pick(u_m, v_s * mask)
                new_u = pick(u_m, u_m * (1.0 - mask))
                new_v = pick(v, v_s * (1.0 - mask))
                return send, new_u, new_v

            send, new_u, new_v = {}, {}, {}
            for n, g in grads.items():
                s, nu, nv = compress(g, state["dgc_u"][n],
                                     state["dgc_v"][n])
                send[n] = jnp.mean(s, axis=0)        # cross-rank reduce
                new_u[n], new_v[n] = nu, nv

            new_params, new_opt = self.optimizer.functional_apply(
                state["params"], send, state["opt"], new_step, lr)
            return {"params": new_params, "buffers": new_buffers,
                    "opt": new_opt, "step": new_step,
                    "dgc_u": new_u, "dgc_v": new_v}, loss.mean()

        return step

    # -- numerics sentinel ----------------------------------------------------
    def _resolve_sentinel(self) -> bool:
        """Static (trace-time) sentinel decision — the off-path cost is
        exactly this one Python branch, like PR 1's profiler gates."""
        req = self._sentinel_requested
        incompatible = self.dgc_sparsity > 0 or self._localsgd_degree() > 1
        if req is None:
            req = bool(_flags.flag("train_sentinel"))
            if req and incompatible:
                import warnings
                warnings.warn(
                    "FLAGS_train_sentinel: the in-graph numerics sentinel "
                    "does not compose with the localsgd/dgc engine paths "
                    "yet (per-rank replica state has no single "
                    "skip-step select point); running without it")
                req = False
        elif req and incompatible:
            raise ValueError(
                "sentinel=True does not compose with localsgd/dgc: their "
                "per-rank replica state has no single skip-step select "
                "point in this engine")
        return bool(req)

    def _fault_nan_steps(self):
        """Trace-time fault plan consultation (testing/faults.py): steps
        at which every gradient leaf is overwritten with NaN IN-GRAPH, so
        injected blow-ups travel the exact path a real one does."""
        from ..testing.faults import active_plan
        plan = active_plan()
        return tuple(plan.nan_grad_steps()) if plan is not None else ()

    def _build_step(self):
        if self.dgc_sparsity > 0:
            return self._build_dgc_step()
        if self._localsgd_degree() > 1:
            return self._build_localsgd_step()
        if self._pipe is not None:
            # remat happens per trunk block inside build_body
            loss_of = self._pipe_loss_of
        else:
            loss_of = self._loss_of
            if self.remat:
                # RecomputeOptimizer ≙ jax.checkpoint over the whole loss fn;
                # per-layer policies live in nn layers via recompute() wrapper.
                loss_of = jax.checkpoint(loss_of, static_argnums=())

        acc_k = self.accumulate_steps
        sentinel = self._sentinel_active
        use_scaler = self.grad_scaler is not None and \
            self.grad_scaler.is_enable()
        nan_steps = self._fault_nan_steps()

        def constrain_grads(grads):
            if self._grad_shardings is None:
                return grads
            return {n: jax.lax.with_sharding_constraint(
                g, self._grad_shardings[n]) for n, g in grads.items()}

        def step(state, inputs, label, lr, scale):
            new_step = state["step"] + 1
            rng_key = jax.random.fold_in(jax.random.key(self.seed),
                                         new_step)
            if use_scaler:
                # loss scaling INSIDE the graph (loss_scaler.py parity for
                # fp16): scale is a traced operand, so dynamic-scale
                # changes never force a recompile
                def scaled_loss_of(p, b, i, l, k):
                    loss, nb = loss_of(p, b, i, l, k)
                    return loss * scale, nb
                grad_fn = jax.value_and_grad(scaled_loss_of, has_aux=True)
            else:
                grad_fn = jax.value_and_grad(loss_of, has_aux=True)

            if acc_k > 1:
                # GradientMerge: microbatch scan accumulating grads; the
                # optimizer runs once on the mean gradient.
                def micro(carry, mb):
                    g_acc, l_acc, buf = carry
                    mb_in, mb_lb = mb
                    (loss, buf), g = grad_fn(state["params"], buf, mb_in,
                                             mb_lb, rng_key)
                    g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
                    return (g_acc, l_acc + loss, buf), None

                def split(x):
                    if x is None:
                        return None
                    return x.reshape((acc_k, x.shape[0] // acc_k) + x.shape[1:])
                mb_inputs = tuple(split(x) for x in inputs)
                mb_label = None if label is None else split(label)
                g0 = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), state["params"])
                (grads, loss, new_buffers), _ = jax.lax.scan(
                    micro, (g0, jnp.float32(0.0), state["buffers"]),
                    (mb_inputs, mb_label))
                grads = jax.tree_util.tree_map(lambda g: g / acc_k, grads)
                loss = loss / acc_k
            else:
                (loss, new_buffers), grads = grad_fn(
                    state["params"], state["buffers"], inputs, label, rng_key)
            if use_scaler:
                # check_finite_and_unscale parity: grads (and the reported
                # loss) leave the scaled domain before the sentinel check
                # and the optimizer update
                inv = 1.0 / scale
                grads = {n: g * inv for n, g in grads.items()}
                loss = loss * inv
            if nan_steps:
                bad = jnp.zeros((), bool)
                for s in nan_steps:
                    bad = jnp.logical_or(bad, new_step == s)
                grads = {n: jnp.where(bad, jnp.full_like(g, jnp.nan), g)
                         for n, g in grads.items()}
            grads = constrain_grads(grads)

            with jax.named_scope("optimizer"):
                new_params, new_opt = self.optimizer.functional_apply(
                    state["params"], grads, state["opt"], new_step, lr)
            new_state = {"params": new_params, "buffers": new_buffers,
                         "opt": new_opt, "step": new_step}
            if not sentinel:
                return new_state, loss
            # ONE fused reduction over loss + every gradient leaf (sorted
            # order matches self._sentinel_names); XLA folds the per-leaf
            # isfinite/all into the epilogue of the grad all-reduce it
            # already schedules — there is no extra HBM pass
            finite_vec = jnp.stack(
                [jnp.all(jnp.isfinite(loss))] +
                [jnp.all(jnp.isfinite(grads[n])) for n in sorted(grads)])
            finite = jnp.all(finite_vec)
            bad_idx = jnp.argmax(jnp.logical_not(finite_vec))
            # skip-step: a non-finite step commits NOTHING — params, opt
            # accumulators and BN buffers all keep their previous values
            # (a poisoned batch must not leak through running stats); the
            # step counter alone advances so rng streams/logs move on
            select = lambda new, old: jax.tree_util.tree_map(  # noqa: E731
                lambda n, o: jnp.where(finite, n, o), new, old)
            new_state = {"params": select(new_params, state["params"]),
                         "buffers": select(new_buffers, state["buffers"]),
                         "opt": select(new_opt, state["opt"]),
                         "step": new_step}
            return new_state, (loss, finite, bad_idx)

        return step

    def compile(self):
        if self._compiled is not None:
            return self._compiled
        self.state  # materialize
        self._sentinel_active = self._resolve_sentinel()
        if self._sentinel_active:
            self._sentinel_names = ["loss"] + sorted(
                self._state["params"])   # stack order of finite_vec
        step = self._build_step()
        self._step_fn = step     # raw (unjitted) step: graph-lint traces it
        state_shardings = dict(self._shardings)
        rep = NamedSharding(self.mesh, P())
        loss_out = (rep, rep, rep) if self._sentinel_active else rep
        self._compiled = jax.jit(
            step,
            in_shardings=(state_shardings, None, None, None, None),
            out_shardings=(state_shardings, loss_out),
            donate_argnums=(0,) if self._donate else (),
        )
        return self._compiled

    # -- AOT access (lowered-executable surface, ISSUE 8) --------------------
    def aot_lower(self, inputs, label=None):
        """AOT-lower the compiled sharded step for example ``inputs``
        WITHOUT executing it.  Returns ``jax.stages.Lowered``;
        ``.compile()`` yields the executable whose ``as_text()`` /
        ``cost_analysis()`` / ``memory_analysis()`` the HLO audit
        (``analysis.hlo``) inspects — abstract eval + XLA compile only,
        so pod-width virtual meshes work with no hardware attached."""
        if not isinstance(inputs, (tuple, list)):
            inputs = (inputs,)

        def conv(x):
            if x is None or isinstance(x, jax.ShapeDtypeStruct):
                return x
            return _as_array(x)

        inputs = tuple(conv(x) for x in inputs)
        label = conv(label)
        # place real arrays under the same batch shardings the eager entry
        # uses — the audited program must shard its feed exactly like the
        # executed one (ShapeDtypeStructs pass through unplaced)
        if inputs and not isinstance(inputs[0], jax.ShapeDtypeStruct):
            put = self._feed_placer(inputs)
            inputs = tuple(put(x) for x in inputs)
            label = put(label) if not isinstance(
                label, jax.ShapeDtypeStruct) else label
        fn = self.compile()
        lr = np.float32(self.optimizer.get_lr())
        return fn.lower(self.state, inputs, label, lr, np.float32(1.0))

    def aot_compile(self, inputs, label=None):
        """``aot_lower(...).compile()`` — the compiled executable, never
        dispatched.  Under FLAGS_executable_cache the XLA compile is
        served from the persistent executable cache, keyed by the sha256
        of the lowered StableHLO module itself — exact program identity
        (mesh, shardings, donation, sentinel and every lowering flag are
        all in the module text), so the cache can never substitute a
        different program; lowering (the cheap half) always runs, the
        XLA compile (the expensive half) loads.  HLO-audit lowerings
        ride this path, so pod-scale audits pay one compile per
        signature per CLUSTER, not per host."""
        lowered = self.aot_lower(inputs, label)
        from ..jit import persistent_cache as _pcache
        if not _pcache.enabled():       # off-path: one branch
            return lowered.compile()
        import hashlib
        hlo_sha = hashlib.sha256(
            lowered.as_text().encode()).hexdigest()
        site = f"train_step:{type(self.layer).__name__}:{id(self):#x}"
        compiled, _loaded = _pcache.load_or_compile(
            lowered.compile,
            site=site, kind="train_step_aot",
            key=(("arg:hlo_sha256", hlo_sha[:16]),),
            extra_key=("train_step_hlo", hlo_sha),
            # aot_compile never ledgered its compiles (the HLO audit
            # ledgers its own lowering at kind hlo_audit) — keep that;
            # loads still ledger as cache_load per the warm-start proof
            ledger_miss=False)
        return compiled

    def _text_of(self, fn, inputs, label, lr, scale):
        """For the compile ledger (``profiler.ledger.program_scopes``):
        how to read the text of the step that has just run for these
        arguments.  ``jax.jit`` keeps the executable it dispatches to
        itself; lowering the same arguments again, right after the call,
        is answered from its caches (milliseconds: no trace, no compile)
        and yields the handle of THAT executable.  The handle is kept by
        what is returned, not by this TrainStep: a training loop's
        TrainStep may be gone by the time a capture is read (the
        benchmark's runner drops it with its frame), and nothing else
        holds the program.  The text itself is fetched when asked for.
        None where that fails: a step never fails for its trace's sake."""
        try:
            return fn.lower(self._state, inputs, label, lr, scale) \
                .compile().as_text
        except Exception:
            return None

    # -- eager entry ---------------------------------------------------------
    def _feed_placer(self, inputs):
        """The batch-placement rule shared by the eager entry and the AOT
        lowering path (the audited program must shard its feed exactly
        like the executed one): returns ``put(x)`` mapping one host/global
        array onto its mesh sharding."""
        dp = self.mesh.shape.get(DP_AXIS, 1)
        lead_ndim = inputs[0].ndim
        nproc = jax.process_count()
        local_dp = dp // nproc if (nproc > 1 and dp > 1 and
                                   dp % nproc == 0) else dp

        def put(x):
            if x is None:
                return None
            # multi-host SPMD: a global array (e.g. built by the caller with
            # make_array_from_process_local_data) passes straight through
            if isinstance(x, jax.Array) and not x.is_fully_addressable:
                return x
            if nproc > 1 and dp > 1 and dp % nproc != 0:
                # host-fed local shards can only tile the dp axis when every
                # process owns the same whole number of dp slots; otherwise
                # the shard boundaries straddle process device halves.
                # (Caller-built global arrays took the passthrough above.)
                raise ValueError(
                    f"multi-process feed: dp degree {dp} must be divisible "
                    f"by the process count {nproc} (each process feeds "
                    "whole dp slots); reshape the mesh or build the global "
                    "arrays yourself with "
                    "jax.make_array_from_process_local_data")
            # explicit batch_spec only applies to arrays of the lead rank;
            # lower-rank labels get their own rank-matched sharding
            if self.batch_spec is not None and x.ndim == lead_ndim:
                sh = self.batch_spec
            elif x.ndim >= 1 and dp > 1 and x.shape[0] % local_dp == 0:
                sh = batch_sharding(self.mesh, ndim=x.ndim)
            elif nproc > 1 and dp > 1:
                # replication across processes assumes IDENTICAL host data
                # on every rank — but with a live dp axis each rank feeds
                # its OWN shard, so 'replicating' would commit different
                # values per rank and silently diverge the SPMD state.
                raise ValueError(
                    f"multi-process feed: local batch dim {x.shape[0]} is "
                    f"not divisible by this process's dp slots ({local_dp}"
                    f"; dp={dp} over {nproc} processes) — pad the batch or "
                    "build the global array yourself with "
                    "jax.make_array_from_process_local_data")
            else:
                # no dp axis (or single-process indivisible batch):
                # replicate. Multi-process contract: with dp==1 every rank
                # must feed the SAME full batch (there is no shard to own).
                return _global_put(x, NamedSharding(self.mesh, P()))
            if nproc > 1:
                # each process feeds its LOCAL batch shard; assemble the
                # global dp-sharded array (the multi-host DataLoader contract
                # — reference: each trainer reads its own file split,
                # fleet/data_generator + dist-train doc)
                with _span("train_step::collective_assemble"):
                    return jax.make_array_from_process_local_data(
                        sh, np.asarray(x))
            return jax.device_put(x, sh)

        return put

    def __call__(self, inputs, label=None):
        if not isinstance(inputs, (tuple, list)):
            inputs = (inputs,)
        inputs = tuple(_as_array(x) for x in inputs)
        label = None if label is None else _as_array(label)

        dp = self.mesh.shape.get(DP_AXIS, 1)
        lead_ndim = inputs[0].ndim
        nproc = jax.process_count()
        local_dp = dp // nproc if (nproc > 1 and dp > 1 and
                                   dp % nproc == 0) else dp
        if self._localsgd_degree() > 1 or self.dgc_sparsity > 0:
            # each rank computes over its own shard, so there is no
            # replicate fallback; a caller-built global array carries the
            # GLOBAL batch while a host-fed array carries this process's
            # local slice — validate each against the dp slots it covers
            x0 = inputs[0]
            is_global = isinstance(x0, jax.Array) and \
                not x0.is_fully_addressable
            need = dp if is_global else max(1, local_dp)
            # with gradient_merge composed into the rank leg, each rank's
            # shard further splits into accumulate_steps microbatches
            need *= max(1, self.accumulate_steps)
            if x0.shape[0] % need != 0:
                raise ValueError(
                    f"localsgd/dgc need the "
                    f"{'global' if is_global else 'per-process'} batch "
                    f"({x0.shape[0]}) divisible by the "
                    f"{'dp degree' if is_global else 'local dp slots'} "
                    f"× accumulate_steps "
                    f"({need}; dp={dp} over {nproc} processes, "
                    f"accumulate_steps={self.accumulate_steps})")

        put = self._feed_placer(inputs)

        prof = _prof_on()
        # per-step sampling decision for the phase breakdown (off = one
        # branch; sample mode keeps every k-th step)
        tr = _tracing.should_sample() if _tracing.enabled() else False
        t_prep0 = time.monotonic() if tr else 0.0
        with _span("train_step::data_feed"):
            inputs = tuple(put(x) for x in inputs)
            label = put(label)
        if tr:
            _STEP_PHASE.labels(phase="host_prep").observe(
                time.monotonic() - t_prep0)
        fn = self.compile()
        # host scalars (not committed device arrays) so the jit treats them
        # as process-replicated under a multi-host mesh; the loss scale is
        # a traced operand so GradScaler backoff never recompiles
        lr = np.float32(self.optimizer.get_lr())
        scaler = self.grad_scaler if (self.grad_scaler is not None and
                                      self.grad_scaler.is_enable()) else None
        scale = np.float32(scaler.get_loss_scaling() if scaler else 1.0)
        # retrace detection: jax.jit silently recompiles on a new input
        # signature — ledger it like any other cache miss.  Entries are
        # path-labeled and carry the weak-type bit: a python scalar fed
        # one step and a committed array the next LOOK identical by
        # shape/dtype but compile different programs, and the ledger diff
        # must name the argument that moved, not say "key unchanged".
        def _arg_sig(path, x):
            # "arg:" prefix = the ledger's labeled-leaf convention: the
            # cache-key diff prints this path instead of a positional index
            if x is None:
                return ("arg:" + path, "none")
            return ("arg:" + path, tuple(x.shape), str(x.dtype),
                    "weak" if getattr(x, "weak_type", False) else "strong")

        sig = (tuple(_arg_sig(f"inputs[{i}]", x)
                     for i, x in enumerate(inputs))
               + (_arg_sig("label", label),))
        fresh = sig not in self._seen_sigs
        site = f"train_step:{type(self.layer).__name__}:{id(self):#x}"
        if fresh:
            from ..analysis import lint_enabled as _lint_on
            if _lint_on():
                # graph lint over the about-to-compile step (abstract
                # eval only, amortized per retrace): donation and
                # sharding-coverage read the compile-site metadata; in
                # error mode this raises BEFORE the step ever runs
                from ..analysis import lint_traced
                from .api import annotation_source, get_partition_spec
                specs = None
                extra = {}
                if self._pipe is None:
                    try:
                        named = list(self.layer.named_parameters())
                        specs = {n: get_partition_spec(p)
                                 for n, p in named}
                        # hand-vs-rule provenance for autoshard-conflict
                        extra["autoshard_sources"] = {
                            n: annotation_source(p) for n, p in named}
                    except Exception:
                        specs = None
                        extra = {}
                if self._autoshard_plan is not None:
                    extra["autoshard_plan"] = self._autoshard_plan
                lint_traced(self._step_fn,
                            (self.state, inputs, label, lr, scale),
                            site=site, kind="train_step", cache_key=sig,
                            prev_key=_ledger.last_key(site),
                            donate=self._donate, mesh=self.mesh,
                            params=self.state["params"],
                            partition_specs=specs, extra=extra)
            from ..analysis.hlo import audit_enabled as _hlo_audit_on
            if _hlo_audit_on():
                # compiled-program audit (analysis.hlo): AOT-relower the
                # exact signature about to compile and inspect the
                # partitioned HLO (collective census, ZeRO layout
                # contract, per-device memory) BEFORE the step executes —
                # error mode raises with the state untouched.  Costs one
                # extra XLA compile per fresh signature; one branch when
                # off.
                from ..analysis.hlo import audit_train_step
                audit_train_step(self, inputs, label, site="hlo:" + site)
            self._seen_sigs.add(sig)
            t0 = time.perf_counter()
            from ..nn.functional.attention import count_attention_forms
            with _span("train_step::compile"), \
                    count_attention_forms() as forms:
                self._state, out = fn(self.state, inputs, label, lr, scale)
            # the step is traced inside that call: the un-cached attention
            # call sites it holds, by the form each took
            _ledger.record_compile(site, "train_step", sig,
                                   (time.perf_counter() - t0) * 1e3,
                                   extra={"attention_form": forms},
                                   hlo_text=self._text_of(
                                       fn, inputs, label, lr, scale))
        else:
            _ledger.record_cache_hit(site)
            if prof or tr:
                # fence on the loss so the span is device time, not the
                # async dispatch; the same fence splits the traced
                # dispatch / device_fence histogram segments
                rec = RecordEvent("train_step::device_execute") if prof \
                    else _NULL_CM
                t_d0 = time.monotonic()
                with rec:
                    self._state, out = fn(self.state, inputs, label, lr,
                                          scale)
                    t_d1 = time.monotonic()
                    jax.block_until_ready(out)
                if tr:
                    t_d2 = time.monotonic()
                    _STEP_PHASE.labels(phase="dispatch").observe(
                        t_d1 - t_d0)
                    _STEP_PHASE.labels(phase="device_fence").observe(
                        t_d2 - t_d1)
            else:
                with _span("train_step::dispatch"):
                    self._state, out = fn(self.state, inputs, label, lr,
                                          scale)
        self.optimizer._step_count += 1
        self._host_step += 1
        if self._sentinel_active:
            loss, finite, bad_idx = out
            self._sentinel_host_update(finite, bad_idx, scaler)
        else:
            loss = out
        from ..testing.faults import active_plan as _fault_plan
        if _fault_plan() is not None:
            from ..testing.faults import step_hook
            step_hook(self._host_step)
        return Tensor(loss)

    # -- sentinel host side ---------------------------------------------------
    def _sentinel_host_update(self, finite, bad_idx, scaler):
        """Per-step bookkeeping for the in-graph sentinel: skipped-step
        gauge, GradScaler backoff, and the bounded consecutive-bad-step
        abort with a diagnostic dump."""
        from ..utils.monitor import stat_add
        if bool(finite):            # one scalar device→host read per step
            self._bad_streak = 0
            if scaler is not None:
                scaler.on_step_result(False)
            return
        stat_add("train_skipped_steps")
        self._bad_streak += 1
        if scaler is not None:
            scaler.on_step_result(True)   # decr-on-nan backoff
        bad_name = self._sentinel_names[int(bad_idx)]
        limit = int(_flags.flag("sentinel_max_bad_steps"))
        if self._bad_streak < limit:
            return
        info = self._dump_sentinel_abort(bad_name, scaler)
        raise FloatingPointError(
            f"numerics sentinel: {self._bad_streak} consecutive non-finite "
            f"train steps (limit FLAGS_sentinel_max_bad_steps={limit}); "
            f"first non-finite tensor this step: {bad_name!r} at step "
            f"{self._host_step}; last good checkpoint: "
            f"{info.get('last_good_checkpoint')}")

    def _dump_sentinel_abort(self, bad_name, scaler):
        """Diagnostic dump next to the checkpoints (or PADDLE_TPU_DIAG_DIR)
        so the post-mortem has which tensor, which step, and where to
        resume from."""
        import json
        import os
        last_good = None
        if self.checkpoint_manager is not None:
            s = self.checkpoint_manager.latest_step()
            if s is not None:
                from ..checkpoint.manager import _step_dirname
                last_good = os.path.join(self.checkpoint_manager.root,
                                         _step_dirname(s))
        info = {"step": self._host_step, "bad_tensor": bad_name,
                "consecutive_bad_steps": self._bad_streak,
                "loss_scale": (scaler.get_loss_scaling()
                               if scaler is not None else None),
                "last_good_checkpoint": last_good, "wall": time.time()}
        dump_dir = (self.checkpoint_manager.root
                    if self.checkpoint_manager is not None
                    else os.environ.get("PADDLE_TPU_DIAG_DIR", ""))
        if dump_dir:
            try:
                from ..checkpoint.atomic import atomic_write_bytes
                atomic_write_bytes(
                    os.path.join(dump_dir, "sentinel_abort.json"),
                    json.dumps(info, indent=1).encode())
            except OSError:
                pass                    # the raise must not be masked
        return info

    # -- checkpoint hooks -----------------------------------------------------
    def attach_checkpoint_manager(self, manager):
        """Bind a ``checkpoint.CheckpointManager``: save_checkpoint /
        restore_from_checkpoint use it by default and the sentinel's
        abort dump can name the last good checkpoint."""
        self.checkpoint_manager = manager
        return manager

    def save_checkpoint(self, manager=None, wait=False):
        """Atomically checkpoint the compiled state at its current step
        (params + buffers + optimizer accumulators + step counter).
        Returns the step number saved."""
        m = manager or self.checkpoint_manager
        if m is None:
            raise ValueError("no CheckpointManager attached or passed")
        step_no = int(self.state["step"])
        payload = {"params": self.state["params"],
                   "buffers": self.state["buffers"],
                   "opt": self.state["opt"],
                   "step": np.asarray(step_no, np.int64)}
        for tag in ("dgc_u", "dgc_v"):  # engine-mode extras ride along
            if tag in self.state:
                payload[tag] = self.state[tag]
        m.save(step_no, payload, wait=wait)
        return step_no

    def restore_from_checkpoint(self, manager=None, step=None):
        """Restore params/buffers/opt/step from the newest complete (or
        an explicit ``step``) checkpoint, placing every leaf back under
        its compiled sharding.  Returns the restored step number."""
        m = manager or self.checkpoint_manager
        if m is None:
            raise ValueError("no CheckpointManager attached or passed")
        step_no, payload = m.load(step=step, return_numpy=True)
        self.state                      # materialize shardings
        sh = self._shardings
        self._state = {
            "params": {n: _global_put(np.asarray(v), sh["params"][n])
                       for n, v in payload["params"].items()},
            "buffers": {n: _global_put(np.asarray(v), sh["buffers"][n])
                        for n, v in payload["buffers"].items()},
            "opt": {s: {n: _global_put(np.asarray(v), sh["opt"][s][n])
                        for n, v in acc.items()}
                    for s, acc in payload["opt"].items()},
            "step": _global_put(np.asarray(int(payload["step"]), np.int32),
                                sh["step"]),
        }
        for tag in ("dgc_u", "dgc_v"):  # engine-mode extras ride along
            if tag in payload:
                self._state[tag] = {
                    n: _global_put(np.asarray(v), sh[tag][n])
                    for n, v in payload[tag].items()}
        self.optimizer._step_count = int(payload["step"])
        self._host_step = int(payload["step"])
        self._bad_streak = 0
        return step_no

    def sync_to_layer(self):
        """Write compiled-state params/buffers back into the eager Layer and
        optimizer accumulators (for save/eval interop)."""
        params, buffers, opt = (self.state["params"], self.state["buffers"],
                                self.state["opt"])
        if self._localsgd_degree() > 1:
            # collapse per-rank replicas: mean is exact right after a sync
            # step and the consensus answer between syncs
            fold = lambda v: (jnp.mean(v, axis=0)
                              if jnp.issubdtype(v.dtype, jnp.floating)
                              else v[0])
            params = {n: fold(v) for n, v in params.items()}
            buffers = {n: fold(v) for n, v in buffers.items()}
            opt = {s: {n: fold(v) for n, v in acc.items()}
                   for s, acc in opt.items()}
        if self._pipe is not None:
            self._pipe.load_flat_state(params, buffers)
        else:
            F.load_layer_state(self.layer, params, buffers)
        self.optimizer.adopt_functional_state(opt)
        self.optimizer._step_count = int(self.state["step"])


class EvalStep:
    """Jitted, sharded forward pass for evaluation/prediction.

    Uses the same mesh machinery as TrainStep (VERDICT r4 weak #5): params
    are placed once under their PartitionSpec shardings and kept
    device-resident across calls (``invalidate()`` re-reads the eager
    layer after external mutation — sync_to_layer / set_state_dict); the
    batch shards over dp like the training feed."""

    def __init__(self, layer, *, mesh=None, loss_fn=None):
        self.layer = layer
        self.mesh = mesh or get_mesh()
        self.loss_fn = _wrap_loss(loss_fn) if loss_fn is not None else None
        self._compiled = None
        self._state = None

    def invalidate(self):
        """Drop the device-resident param snapshot (call after mutating
        the eager layer's weights)."""
        self._state = None

    def _placed_state(self):
        if self._state is None:
            params, buffers = F.layer_state(self.layer)
            shardings = named_shardings(self.layer, self.mesh)
            rep = NamedSharding(self.mesh, P())
            params = {n: _global_put(v, shardings.get(n, rep))
                      for n, v in params.items()}
            buffers = {n: _global_put(v, rep) for n, v in buffers.items()}
            self._state = (params, buffers)
        return self._state

    def _build(self):
        def fwd(params, buffers, inputs, label):
            out = F.functional_call(self.layer, params, buffers, inputs,
                                    training=False)
            if self.loss_fn is not None and label is not None:
                first = out[0] if isinstance(out, (tuple, list)) else out
                return out, self.loss_fn(first, label)
            return out, None
        return jax.jit(fwd)

    def _put_batch(self, x):
        if x is None:
            return None
        dp = self.mesh.shape.get(DP_AXIS, 1)
        if x.ndim >= 1 and dp > 1 and x.shape[0] % dp == 0:
            return jax.device_put(x, batch_sharding(self.mesh, ndim=x.ndim))
        return x

    def __call__(self, inputs, label=None):
        if not isinstance(inputs, (tuple, list)):
            inputs = (inputs,)
        inputs = tuple(self._put_batch(_as_array(x)) for x in inputs)
        params, buffers = self._placed_state()
        if self._compiled is None:
            self._compiled = self._build()
        out, loss = self._compiled(params, buffers, inputs,
                                   None if label is None else _as_array(label))
        wrap = lambda o: Tensor(o) if o is not None else None
        if isinstance(out, (tuple, list)):
            out = type(out)(Tensor(o) for o in out)
        else:
            out = Tensor(out)
        return (out, wrap(loss)) if self.loss_fn is not None else out
