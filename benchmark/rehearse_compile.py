#!/usr/bin/env python3
"""Compile rehearsal: the benchmark's programs at their real sizes, compiled
for a DESCRIBED ``v5e:2x2`` chip with the TPU compiler that is installed in
the sandbox.  Nothing runs; ``memory_analysis()`` decides the training batch
and the number of decode slots before any chip call (PERF.md section 4).

    JAX_PLATFORMS=cpu python3 benchmark/rehearse_compile.py train <config> <batch> [<batch> ...]
    JAX_PLATFORMS=cpu python3 benchmark/rehearse_compile.py serve <config> <slots> [<slots> ...]

Run by hand, one process at a time (only one process may load libtpu).  It
hands the program described devices and shapes: ``train_step._global_put``
is stubbed so that ``TrainStep.state`` holds ``ShapeDtypeStruct``s, and the
slot-loop programs are lowered from ``Generator._build_step/_build_chunk``
with the avals the runtime itself uses.
"""
from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402
from jax.experimental import topologies                       # noqa: E402
from jax.sharding import Mesh, SingleDeviceSharding           # noqa: E402


def _report(what, compiled, seconds):
    m = compiled.memory_analysis()
    gb = 1 / 2 ** 30
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(json.dumps({
        "program": what, "compile_s": round(seconds, 1),
        "arguments_GiB": round(m.argument_size_in_bytes * gb, 3),
        "outputs_GiB": round(m.output_size_in_bytes * gb, 3),
        "aliased_GiB": round(m.alias_size_in_bytes * gb, 3),
        "temporaries_GiB": round(m.temp_size_in_bytes * gb, 3),
        "total_GiB": round(total * gb, 3)}), flush=True)


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def train(cfg, batches, dev):
    import paddle_tpu as paddle
    from paddle_tpu.parallel import TrainStep, train_step
    from benchmark.models import bert as family
    train_step._global_put = lambda v, sharding: jax.ShapeDtypeStruct(
        tuple(np.shape(v)), jnp.asarray(v).dtype
        if not hasattr(v, "dtype") else v.dtype, sharding=sharding)
    tr = cfg["train"]
    from paddle_tpu.text.models.bert import BertForPretraining
    model = BertForPretraining(family.program_config(cfg))
    mesh = Mesh(np.asarray([dev]), ("dp",))
    for B in batches:
        opt = paddle.optimizer.AdamW(parameters=model.parameters(),
                                     learning_rate=tr["learning_rate"],
                                     weight_decay=tr["weight_decay"])
        step = TrainStep(model, opt, mesh=mesh,
                         compute_dtype=jnp.dtype(cfg["dtype"]),
                         remat=tr["remat"])
        one = SingleDeviceSharding(dev)
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)  # noqa: E731
        feed = (i32(B, tr["seq"]), None, None, i32(B, tr["masked_per_seq"]),
                None, i32(B, tr["masked_per_seq"]))
        t0 = time.time()
        compiled = step.aot_lower(feed).compile()
        _report(f"{cfg['name']} train step batch {B} x {tr['seq']}",
                compiled, time.time() - t0)


def serve(cfg, slot_counts, dev):
    from paddle_tpu.text.generation import Generator
    from benchmark.models import gpt as family
    sv = cfg["serve"]
    model = family.build_unweighted(cfg)
    gen = Generator(model, seq_buckets=sv["seq_buckets"],
                    max_len=sv["max_len"])
    one = SingleDeviceSharding(dev)
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    state = place(gen._state_avals())
    C, T = sv["max_len"], sv["prefill_chunk"]
    for S in slot_counts:
        for what, fn, avals in (
                ("step", gen._build_step(S, C, -1), gen.step_avals(S, C)),
                ("chunk", gen._build_chunk(S, T, C), gen.chunk_avals(S, T, C))):
            t0 = time.time()
            compiled = jax.jit(fn, donate_argnums=(2,)).lower(
                *state, *place(avals)).compile()
            _report(f"{cfg['name']} slot-loop {what} S={S} C={C}",
                    compiled, time.time() - t0)


def main(argv):
    kind, name, sizes = argv[0], argv[1], [int(x) for x in argv[2:]]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = topo.devices[0]
    {"train": train, "serve": serve}[kind](_config(name), sizes, dev)


if __name__ == "__main__":
    main(sys.argv[1:])
