#!/usr/bin/env python3
"""Hash the LOWERED text of a benchmark configuration's programs: did a
change leave a configuration's programs alone?

A change to shared model code (``nn/layer/transformer.py``, ``text/
generation.py``) must show that the configurations it does not mean to
touch compile what they compiled before.  This tool lowers, for a DESCRIBED
``v5e:2x2`` (no chip; traced as ``tools/kv_layout_check.py`` traces, so a
kernel's gate answers for the chip), the slot loop's step and chunk programs
of a serving configuration, or the training step of a training one, and
prints one line a program: the characters and the SHA-256 of its StableHLO
text (no locations, so an edit that only moves lines does not show).  A few
seconds to a minute a configuration; nothing is compiled.

    JAX_PLATFORMS=cpu python3 tools/program_text_hash.py <config> [<config> ...]

To compare two checkouts, run each tree's own copy of this file and reach
BOTH trees through ONE path (a symlink that is pointed at one, then at the
other): a Pallas kernel travels in the text as its serialized body, which
carries the names of its source files.  For the same reason call-site
tracebacks are left out of locations here
(``jax_include_full_tracebacks_in_locations``): with them, the line numbers
of every caller of a kernel are in its body.  Found in PR 46, where the
programs of three latent configurations and BERT read "changed" until both
were done.  Run by hand, one process at a time (libtpu), like the layout
check.
"""
from __future__ import annotations

import hashlib
import importlib
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the path this file was REACHED by, symlinks unresolved: see above
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))


def _line(config, program, text, **facts):
    print(json.dumps({"config": config, "program": program,
                      "chars": len(text), "sha256": hashlib.sha256(
                          text.encode()).hexdigest(), **facts}), flush=True)


def _train(cfg, dev):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, SingleDeviceSharding
    import paddle_tpu as paddle
    from paddle_tpu.parallel import TrainStep, train_step
    from benchmark.models import bert as family
    from paddle_tpu.text.models.bert import BertForPretraining
    # nothing can be placed on a described chip (benchmark/rehearse_compile)
    train_step._global_put = lambda v, sharding: jax.ShapeDtypeStruct(
        tuple(np.shape(v)), jnp.asarray(v).dtype
        if not hasattr(v, "dtype") else v.dtype, sharding=sharding)
    tr = cfg["train"]
    model = BertForPretraining(family.program_config(cfg))
    opt = paddle.optimizer.AdamW(parameters=model.parameters(),
                                 learning_rate=tr["learning_rate"],
                                 weight_decay=tr["weight_decay"])
    step = TrainStep(model, opt, mesh=Mesh(np.asarray([dev]), ("dp",)),
                     compute_dtype=jnp.dtype(cfg["dtype"]), remat=tr["remat"])
    one = SingleDeviceSharding(dev)
    B = tr["batch"]

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

    feed = (i32(B, tr["seq"]), None, None, i32(B, tr["masked_per_seq"]),
            None, i32(B, tr["masked_per_seq"]))
    _line(cfg["name"], "train_step", step.aot_lower(feed).as_text())


def _serve(cfg, dev):
    import jax
    from jax.sharding import SingleDeviceSharding
    import kv_layout_check
    family = importlib.import_module("benchmark.models." + cfg["family"])
    sv = cfg["serve"]
    gen = kv_layout_check.described_generator(dev)(
        family.build_unweighted(cfg), seq_buckets=sv["seq_buckets"],
        max_len=sv["max_len"])
    S, C, T = sv["slots"], sv["max_len"], sv["prefill_chunk"]
    one = SingleDeviceSharding(dev)
    for what, prog in (("step", gen._step_program(S, C)),
                       ("chunk", gen._chunk_program(S, T, C))):
        _key, _kind, fn, avals, extra, donate = prog
        avals = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            avals)
        text = jax.jit(fn, donate_argnums=donate).lower(
            *gen._state_avals(), *avals).as_text()
        # the facts of the program its ledger event would carry
        _line(cfg["name"], what, text, **{
            k: v for k, v in extra.items()
            if k in ("chunk_row", "step_read", "latent_form",
                     "kv_heads_per_lane_row")})


def main(argv):
    if not argv:
        print(__doc__, file=sys.stderr)
        return 2
    import jax
    from jax.experimental import topologies
    from paddle_tpu.nn.functional import attention
    from paddle_tpu.ops.pallas import _mode
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    # traced HERE for the described chip, as the layout check does
    attention._on_tpu = lambda: True
    _mode.interpret = lambda: False
    dev = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]
    for name in argv:
        with open(os.path.join(ROOT, "benchmark", "configs",
                               name + ".json")) as f:
            cfg = json.load(f)
        (_train if "train" in cfg else _serve)(cfg, dev)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
