"""Pipeline parallelism: GPipe over the ``pp`` mesh axis.

Reference parity: PipelineOptimizer (python/paddle/fluid/optimizer.py:3702)
splits the program into per-device section programs by device_guard and
inserts send_v2/recv_v2 at boundaries (:4178); C++ PipelineTrainer +
SectionWorker run the GPipe schedule — all-forward over microbatches
(section_worker.cc:61), all-backward (:87), then update (:106).

TPU-first: the pipeline is ONE SPMD program.  Stages are shards of the
``pp`` mesh axis; the per-stage weights are the same pytree stacked along a
leading [S, ...] dim sharded P('pp'); microbatch activations flow between
stages with lax.ppermute inside a lax.scan over schedule ticks.  The
backward schedule is not hand-written (no section_worker backward loop):
jax.grad differentiates through scan+ppermute and emits the reverse
pipeline automatically, and XLA overlaps the permutes with compute.
"""
from __future__ import annotations

from typing import Callable, List

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..framework import functional as F
from ..framework.tensor import Tensor
from .mesh import get_mesh, PP_AXIS, DP_AXIS


def pipeline_spmd_train(stage_fn: Callable, num_stages: int,
                        num_microbatches: int):
    """GPipe schedule body (call inside shard_map with axis pp).

    ``stage_fn(stage_params, x, key)`` applies ONE stage's layers; the PRNG
    key is folded per schedule tick and stage so every microbatch/stage pass
    draws distinct randomness (dropout).  ``key_data`` is the uint32 key
    data (shard_map-friendly); pass ``jax.random.key_data(key)``.

    Input x_mb: [M, mb, ...] microbatched activations (same on every stage;
    only stage 0's injection is used).  Returns [M, mb, ...] outputs valid
    on every stage (the last stage's result is psum-broadcast so downstream
    loss code is stage-agnostic).
    """
    S, M = num_stages, num_microbatches

    def run(stage_params, x_mb, key_data):
        idx = lax.axis_index(PP_AXIS)
        base = jax.random.wrap_key_data(key_data)
        # carry becomes pp-varying after the first ppermute; mark the initial
        # zeros as varying over pp so scan's carry types line up (VMA rule)
        zero = lax.pcast(jnp.zeros_like(x_mb[0]), (PP_AXIS,),
                         to="varying")

        def tick(carry, t):
            incoming = carry
            mb_idx = jnp.clip(t, 0, M - 1)
            inject = x_mb[mb_idx]
            act_in = jnp.where(idx == 0, inject, incoming)
            key = jax.random.fold_in(jax.random.fold_in(base, t), idx)
            out = stage_fn(stage_params, act_in, key)
            shifted = lax.ppermute(
                out, PP_AXIS, [(i, (i + 1) % S) for i in range(S)])
            return shifted, out

        _, outs = lax.scan(tick, zero, jnp.arange(M + S - 1))
        # last stage emits microbatch m at tick m + S - 1
        final = outs[S - 1:]
        mine = jnp.where(idx == S - 1, final, jnp.zeros_like(final))
        return lax.psum(mine, PP_AXIS)

    return run


def pipeline_spmd(stage_fn: Callable, num_stages: int, num_microbatches: int):
    """Keyless GPipe body: ``stage_fn(stage_params, x)`` (inference /
    deterministic stages).  Same schedule as :func:`pipeline_spmd_train`."""
    train = pipeline_spmd_train(lambda p, x, key: stage_fn(p, x),
                                num_stages, num_microbatches)

    def run(stage_params, x_mb):
        return train(stage_params, x_mb,
                     jax.random.key_data(jax.random.key(0)))

    return run


class PipelineModule:
    """Heterogeneous pipeline model: replicated embed → pp-sharded trunk of
    homogeneous blocks → replicated head.

    ≙ fleet.meta_parallel PipelineLayer + device_guard section programs
    (python/paddle/fluid/optimizer.py:3702 PipelineOptimizer splits by
    device_guard; paddle/fluid/framework/section_worker.cc runs the GPipe
    schedule).  TPU-first, the whole model is ONE jitted SPMD program:
    TrainStep recognizes this class and lays the stacked trunk params out as
    P('pp'), so stage weights live only on their pipeline rank while embed
    and head stay replicated; jax.grad differentiates straight through the
    scan+ppermute schedule (no hand-written backward pipeline).

    ``embed`` may be None (inputs feed the trunk directly); ``head`` may be
    None (trunk output is the model output).  Trunk blocks must be
    structurally identical and carry no buffers (batch-norm trunks are not
    pipelineable here — use group/layer norm, as transformer trunks do).
    """

    def __init__(self, embed, blocks: List, head, num_stages: int = None,
                 num_microbatches: int = 2, mesh=None):
        self.mesh = mesh or get_mesh()
        self.S = num_stages or self.mesh.shape.get(PP_AXIS, 1)
        if len(blocks) % self.S:
            raise ValueError(
                f"{len(blocks)} trunk blocks not divisible by {self.S} stages")
        self.embed = embed
        self.blocks = list(blocks)
        self.head = head
        self.M = num_microbatches
        self.per_stage = len(blocks) // self.S
        p0, b0 = F.layer_state(blocks[0])
        if b0:
            raise ValueError(
                "pipelined trunk blocks must be buffer-free (got buffers "
                f"{list(b0)}); replace batch-norm with layer/group norm")
        self.block_param_names = list(p0)

    # -- flat state ----------------------------------------------------------
    def flat_state(self):
        """(params, buffers) as flat dicts: 'embed::*', 'head::*' straight
        from the sublayers, 'pipe::*' the trunk stacked [S, per_stage, ...]."""
        params, buffers = {}, {}
        for tag, layer in (("embed", self.embed), ("head", self.head)):
            if layer is None:
                continue
            p, b = F.layer_state(layer)
            params.update({f"{tag}::{n}": v for n, v in p.items()})
            buffers.update({f"{tag}::{n}": v for n, v in b.items()})
        per_block = []
        for blk in self.blocks:
            p, _ = F.layer_state(blk)
            per_block.append(p)
        for n in self.block_param_names:
            stacked = jnp.stack([p[n] for p in per_block])
            params[f"pipe::{n}"] = stacked.reshape(
                (self.S, self.per_stage) + per_block[0][n].shape)
        return params, buffers

    def load_flat_state(self, params, buffers):
        """Write a flat state dict back into the eager sublayers."""
        for tag, layer in (("embed", self.embed), ("head", self.head)):
            if layer is None:
                continue
            p = {n[len(tag) + 2:]: v for n, v in params.items()
                 if n.startswith(tag + "::")}
            b = {n[len(tag) + 2:]: v for n, v in buffers.items()
                 if n.startswith(tag + "::")}
            F.load_layer_state(layer, p, b)
        for j, blk in enumerate(self.blocks):
            s, i = divmod(j, self.per_stage)
            F.load_layer_state(blk, {
                n: params[f"pipe::{n}"][s, i]
                for n in self.block_param_names}, None)

    def parameters(self):
        out = []
        for layer in (self.embed, self.head):
            if layer is not None:
                out.extend(layer.parameters())
        for blk in self.blocks:
            out.extend(blk.parameters())
        return out

    def state_dict(self):
        sd = {}
        for tag, layer in (("embed", self.embed), ("head", self.head)):
            if layer is not None:
                sd.update({f"{tag}.{k}": v
                           for k, v in layer.state_dict().items()})
        for j, blk in enumerate(self.blocks):
            sd.update({f"trunk.{j}.{k}": v
                       for k, v in blk.state_dict().items()})
        return sd

    def set_state_dict(self, sd):
        for tag, layer in (("embed", self.embed), ("head", self.head)):
            if layer is not None:
                layer.set_state_dict({k[len(tag) + 1:]: v
                                      for k, v in sd.items()
                                      if k.startswith(tag + ".")})
        for j, blk in enumerate(self.blocks):
            pre = f"trunk.{j}."
            blk.set_state_dict({k[len(pre):]: v for k, v in sd.items()
                                if k.startswith(pre)})

    # -- compiled body -------------------------------------------------------
    def build_body(self, remat: bool = False):
        """fn(stacked_params, x [B, ...], key_data) -> trunk output [B, ...],
        SPMD over the pp (and dp) mesh axes."""
        from jax import shard_map
        block0 = self.blocks[0]
        names = self.block_param_names
        per_stage = self.per_stage
        S, M, mesh = self.S, self.M, self.mesh
        dp = mesh.shape.get(DP_AXIS, 1)

        def apply_block(x, block_params, key):
            params = dict(zip(names, block_params))
            return F.functional_call(block0, params, None, (x,),
                                     training=True, rng_key=key)

        if remat:
            # per-block rematerialization: the classic pipeline memory trade
            # (RecomputeOptimizer inside each section program)
            apply_block = jax.checkpoint(apply_block)

        def stage(stage_params, x, key):
            def body(x, i):
                bp = [stage_params[n][0, i] for n in names]
                return apply_block(x, bp, jax.random.fold_in(key, i)), None
            out, _ = lax.scan(body, x, jnp.arange(per_stage))
            return out

        run = pipeline_spmd_train(stage, S, M)
        param_specs = {n: P(PP_AXIS) for n in names}

        def fwd(stacked, x, key):
            if x.shape[0] % M:
                raise ValueError(
                    f"pipeline batch {x.shape[0]} not divisible by "
                    f"{M} microbatches")
            mb = x.reshape((M, x.shape[0] // M) + x.shape[1:])
            bshard = DP_AXIS if (dp > 1 and mb.shape[1] % dp == 0) else None
            data_spec = P(None, bshard)
            out_mb = shard_map(
                run, mesh=mesh,
                in_specs=(param_specs, data_spec, P(None)),
                out_specs=data_spec,
            )({n: stacked[n] for n in names}, mb,
              jax.random.key_data(key))
            return out_mb.reshape((-1,) + out_mb.shape[2:])

        return fwd


class GPipe:
    """Pipeline a homogeneous stack of blocks (e.g. transformer layers).

    ≙ PipelineOptimizer + PipelineTrainer as one object. Blocks must share
    structure (same param pytree); layers are grouped into ``num_stages``
    stages of equal depth. Embedding/head layers stay replicated outside the
    pipelined trunk.
    """

    def __init__(self, blocks: List, num_stages: int = None, mesh=None,
                 num_microbatches: int = 2):
        self.mesh = mesh or get_mesh()
        self.S = num_stages or self.mesh.shape.get(PP_AXIS, 1)
        assert len(blocks) % self.S == 0, \
            f"{len(blocks)} blocks not divisible by {self.S} stages"
        self.blocks = blocks
        self.M = num_microbatches
        self.per_stage = len(blocks) // self.S

        # stack params: [n_blocks, ...] -> grouped [S, per_stage, ...]
        names = None
        all_params = []
        for b in blocks:
            p, _ = F.layer_state(b)
            if names is None:
                names = list(p)
            all_params.append([p[n] for n in names])
        self.param_names = names
        self.stacked = {
            n: jnp.stack([all_params[i][j] for i in range(len(blocks))])
                 .reshape((self.S, self.per_stage)
                          + all_params[0][j].shape)
            for j, n in enumerate(names)}
        # shard leading stage dim over pp
        self.stacked = {
            n: jax.device_put(v, NamedSharding(
                self.mesh, P(PP_AXIS) if self.mesh.shape.get(PP_AXIS, 1) > 1
                else P()))
            for n, v in self.stacked.items()}

    def _stage_fn(self):
        block0 = self.blocks[0]
        names = self.param_names
        per_stage = self.per_stage

        def apply_block(x, block_params):
            params = dict(zip(names, block_params))
            return F.functional_call(block0, params, None, (x,),
                                     training=False)

        def stage(stage_params, x):
            # inside shard_map the leading [S] dim is sliced to [1]:
            # stage_params[n]: [1, per_stage, ...]
            def body(x, i):
                bp = [stage_params[n][0, i] for n in names]
                return apply_block(x, bp), None
            out, _ = lax.scan(body, x, jnp.arange(per_stage))
            return out

        return stage

    def build_forward(self):
        """Return pure fn(stacked_params, x [B, ...]) -> y executed as SPMD
        over the pp (and dp) axes of the mesh."""
        from jax import shard_map
        S, M = self.S, self.M
        body = pipeline_spmd(self._stage_fn(), S, M)
        mesh = self.mesh
        dp = mesh.shape.get(DP_AXIS, 1)

        param_specs = {n: P(PP_AXIS) for n in self.param_names}

        def fwd(stacked, x):
            mb = x.reshape((M, x.shape[0] // M) + x.shape[1:])
            # shard the per-microbatch batch dim over dp only when divisible
            bshard = DP_AXIS if (dp > 1 and mb.shape[1] % dp == 0) else None
            data_spec = P(None, bshard)
            out_mb = shard_map(
                body, mesh=mesh,
                in_specs=(param_specs, data_spec),
                out_specs=data_spec,
            )({n: stacked[n] for n in self.param_names}, mb)
            return out_mb.reshape((-1,) + out_mb.shape[2:])

        return fwd

    def __call__(self, x):
        fwd = self.build_forward()
        arr = x._value if isinstance(x, Tensor) else jnp.asarray(x)
        return Tensor(fwd(self.stacked, arr))
