#!/usr/bin/env python
"""hlo_audit — AOT-lower zoo train steps over virtual wide meshes and
audit the compiled HLO (paddle_tpu.analysis.hlo's CLI face).

Where tools/graph_lint.py lints what the user *traced*, this audits what
XLA *compiled*: per mesh width it builds a sharded TrainStep for each zoo
model, lowers + compiles it ABSTRACTLY (no execution, no chip — the
script provisions ``--xla_force_host_platform_device_count`` before jax
imports, so a 64-device v5e layout audits on any build host), and runs
the hlo pass family: full-gathers of ZeRO-sharded state (ERROR),
collective census with ring-model wire bytes, per-device memory + FLOPs.

Usage:
    python tools/hlo_audit.py --zoo --mesh 16x2 --strict --json
    python tools/hlo_audit.py --model bert --mesh 4x2x2 --zero 3
    python tools/hlo_audit.py --seeded --mesh 8x2 --strict   # must exit 1

``--mesh DPxMP[xSP]`` is repeatable; every lowering is recompile-ledgered
at kind ``hlo_audit`` with a labeled ``arg:mesh`` key (the
zero-steady-state-recompile convention extended to audit runs; the JSON
report carries the events).  ``--strict`` exits non-zero on any
ERROR-severity finding — the zoo must pass clean at every width, and the
``--seeded`` de-sharded-ZeRO fixture must fail.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ZOO_MODELS = ("lenet", "resnet_block", "bert", "gpt", "gpt_moe",
              "wide_deep")

# --autoshard: shard models through the FLAGS_autoshard=apply TrainStep
# hook (analysis.autoshard rules engine) instead of the models' explicit
# annotation entry points — audits the rules-driven path end-to-end
_AUTOSHARD = [False]


def parse_mesh(spec: str):
    """'16x2' -> {dp:16, mp:2}; '8x2x2' -> {dp:8, mp:2, sp:2}.  Parts
    may also NAME their axis ('ep8', 'dp4xep2' — the expert-parallel
    meshes MoE shards over); bare numbers keep the positional
    DP[xMP[xSP]] meaning."""
    import re
    raw = [p for p in spec.lower().replace("*", "x").split("x") if p]
    named = {}
    positional = []
    for p in raw:
        m = re.fullmatch(r"([a-z]+)(\d+)", p)
        if m:
            named[m.group(1)] = int(m.group(2))
        else:
            positional.append(int(p))
    if len(positional) > 3 or any(p < 1 for p in positional) \
            or any(v < 1 for v in named.values()):
        raise ValueError(
            f"bad mesh spec {spec!r}: want DP[xMP[xSP]] or named parts "
            f"like ep8")
    axes = {}
    for name, v in zip(("dp", "mp", "sp"), positional):
        axes[name] = v
    for name, v in named.items():
        if name in axes:
            raise ValueError(f"axis {name!r} given twice in {spec!r}")
        axes[name] = v
    if not axes:
        raise ValueError(f"empty mesh spec {spec!r}")
    return axes


def _provision(n_devices: int) -> None:
    """Force an ``n_devices``-wide virtual CPU platform BEFORE jax
    initializes (the one simulated-chip provisioning recipe; explicit
    JAX_PLATFORMS in the env wins)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = " ".join(f for f in os.environ.get("XLA_FLAGS", "").split()
                     if not f.startswith("--xla_force_host_platform"))
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n_devices}"
    ).strip()


# -- zoo train-step builders (called after provisioning/imports) ------------

def _build_lenet(mesh, zero):
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.parallel import TrainStep
    from paddle_tpu.vision.models import LeNet
    paddle.seed(0)
    model = LeNet()
    opt = paddle.optimizer.AdamW(parameters=model.parameters(),
                                 learning_rate=1e-3)
    step = TrainStep(model, opt, loss_fn=paddle.nn.CrossEntropyLoss(),
                     mesh=mesh, zero=zero)
    dp = dict(mesh.shape).get("dp", 1)
    rng = np.random.RandomState(0)
    x = rng.randn(2 * dp, 1, 28, 28).astype("float32")
    y = rng.randint(0, 10, (2 * dp,))
    return step, (x,), y


def _build_resnet_block(mesh, zero, ch=8, hw=8):
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.parallel import TrainStep

    class Block(nn.Layer):
        """Residual conv-BN-ReLU pair + linear head (ResNet's high-res
        stage with a classification tail so it trains end-to-end)."""

        def __init__(self):
            super().__init__()
            self.c1 = nn.Conv2D(ch, ch, 3, padding=1, bias_attr=False)
            self.b1 = nn.BatchNorm2D(ch)
            self.c2 = nn.Conv2D(ch, ch, 3, padding=1, bias_attr=False)
            self.b2 = nn.BatchNorm2D(ch)
            self.relu = nn.ReLU()
            self.head = nn.Linear(ch, 16)

        def forward(self, x):
            h = self.relu(self.b1(self.c1(x)))
            h = self.relu(self.b2(self.c2(h)) + x)
            return self.head(h.mean(axis=[2, 3]))

    paddle.seed(0)
    model = Block()
    opt = paddle.optimizer.Momentum(parameters=model.parameters(),
                                    learning_rate=0.1, momentum=0.9)
    step = TrainStep(model, opt, loss_fn=paddle.nn.CrossEntropyLoss(),
                     mesh=mesh, zero=zero)
    dp = dict(mesh.shape).get("dp", 1)
    rng = np.random.RandomState(0)
    x = rng.randn(2 * dp, ch, hw, hw).astype("float32")
    y = rng.randint(0, 16, (2 * dp,))
    return step, (x,), y


def _build_bert(mesh, zero):
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.parallel import TrainStep
    from paddle_tpu.text.models.bert import (
        BertConfig, BertForPretraining, apply_tensor_parallel)
    cfg = BertConfig.tiny(vocab_size=64, hidden_size=16, layers=2,
                          heads=2, seq=32)
    cfg.hidden_dropout_prob = 0.0
    cfg.attention_probs_dropout_prob = 0.0
    paddle.seed(0)
    model = BertForPretraining(cfg)
    if not _AUTOSHARD[0]:
        apply_tensor_parallel(model)
    opt = paddle.optimizer.AdamW(parameters=model.parameters(),
                                 learning_rate=1e-3)
    step = TrainStep(model, opt, mesh=mesh, zero=zero, remat=True)
    dp = dict(mesh.shape).get("dp", 1)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (4 * dp, 16))
    labels = np.where(rng.rand(*ids.shape) < 0.15, ids, -100)
    return step, (ids, None, None, labels), None


def _build_gpt(mesh, zero):
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.parallel import TrainStep
    from paddle_tpu.text.models.gpt import (GPTConfig, GPTModel,
                                            apply_tensor_parallel)
    cfg = GPTConfig.tiny(vocab_size=64, hidden_size=16, layers=2,
                         heads=2, seq=32)
    cfg.dropout = 0.0
    paddle.seed(0)
    model = GPTModel(cfg)
    if not _AUTOSHARD[0]:
        apply_tensor_parallel(model)
    opt = paddle.optimizer.AdamW(parameters=model.parameters(),
                                 learning_rate=1e-3)
    step = TrainStep(model, opt, mesh=mesh, zero=zero, remat=True)
    dp = dict(mesh.shape).get("dp", 1)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (4 * dp, 16))
    return step, (ids, ids.copy()), None


def _build_gpt_moe(mesh, zero):
    """Expert-parallel GPT-MoE step (ISSUE 14): every other block's FFN
    is a MoELayer whose stacked expert parameters shard over the mesh's
    expert axis ('ep' when the mesh has one, else EP=DP over 'dp'), and
    whose token dispatch is two lax.all_to_alls inside shard_map — the
    fourth collective pattern (token-routing-heavy, wire bytes ∝
    capacity, never vocab).  The batch is FIXED across widths (strong
    scaling), so per-device routed bytes stay ~flat as the mesh widens.
    Expert count adapts to the axis (2 experts per shard) so every
    width keeps whole experts per device."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.parallel import TrainStep
    from paddle_tpu.text.models.gpt import GPTMoEConfig, GPTMoEModel
    axes = dict(mesh.shape)
    axis = "ep" if axes.get("ep", 1) > 1 else "dp"
    n = max(1, axes.get(axis, 1))
    # the rules table reads FLAGS_moe_axis, so proposals and the
    # layer's own annotations must name the same axis
    set_flags({"FLAGS_moe_axis": axis})
    paddle.seed(0)
    cfg = GPTMoEConfig.tiny(vocab_size=64, hidden_size=16, layers=2,
                            heads=2, seq=32, experts=max(4, 2 * n),
                            top_k=2, capacity_factor=1.25)
    cfg.dropout = 0.0
    model = GPTMoEModel(cfg, mesh=mesh, dispatch="routed",
                        annotate=not _AUTOSHARD[0])
    opt = paddle.optimizer.AdamW(parameters=model.parameters(),
                                 learning_rate=1e-3)
    step = TrainStep(model, opt, mesh=mesh, zero=zero)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (8, 32))   # 256 tokens, fixed
    return step, (ids, ids.copy()), None


def _build_wide_deep(mesh, zero):
    """Sharded-embedding CTR step (ISSUE 10): the deep-leg table is
    row-partitioned over dp via ShardedEmbedding, so the compiled step
    carries the all-to-all routing pattern — dot-light, all-to-all-heavy,
    the collective mix the transformer zoo never produces.  The batch is
    FIXED across mesh widths (strong scaling: the table grows, the batch
    does not have to), so per-device routed bytes stay ~flat."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.parallel import TrainStep
    from paddle_tpu.rec.sharded_embedding import ShardedWideDeep
    paddle.seed(0)
    model = ShardedWideDeep(vocab=4096, emb_dim=16, num_slots=8,
                            dense_dim=8, hidden=(32, 16), mesh=mesh)
    opt = paddle.optimizer.Adam(parameters=model.parameters(),
                                learning_rate=1e-3)
    step = TrainStep(model, opt, mesh=mesh, zero=zero)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 4096, (128, 8))
    dense = rng.randn(128, 8).astype("float32")
    labels = (rng.rand(128, 1) > 0.5).astype("float32")
    return step, (ids, dense, labels), None


BUILDERS = {"lenet": _build_lenet, "resnet_block": _build_resnet_block,
            "bert": _build_bert, "gpt": _build_gpt,
            "gpt_moe": _build_gpt_moe, "wide_deep": _build_wide_deep}


def audit_model(name: str, axes: dict, zero: int, suppress=()):
    """Build + AOT-lower + audit one zoo model over one mesh.  Returns an
    ``analysis.hlo.HloAuditResult``."""
    import jax
    from paddle_tpu.analysis import hlo as hlo_audit
    from paddle_tpu.parallel import make_mesh
    n = 1
    for v in axes.values():
        n *= v
    mesh = make_mesh(dict(axes), devices=jax.devices()[:n])
    step, inputs, label = BUILDERS[name](mesh, zero)
    return hlo_audit.audit_train_step(
        step, inputs, label, site=f"hlo_audit:zoo:{name}",
        suppress=suppress, do_emit=False)


def audit_seeded(axes: dict, zero: int):
    """The negative gate: the de-sharded ZeRO fixture over this mesh."""
    import jax
    from paddle_tpu.analysis import hlo as hlo_audit
    from paddle_tpu.analysis.hlo.fixtures import desharded_zero_step
    from paddle_tpu.parallel import make_mesh
    n = 1
    for v in axes.values():
        n *= v
    mesh = make_mesh(dict(axes), devices=jax.devices()[:n])
    step, inputs, label = desharded_zero_step(mesh, zero=zero)
    return hlo_audit.audit_train_step(
        step, inputs, label, site="hlo_audit:seeded", do_emit=False)


def audit_seeded_table(axes: dict):
    """Second negative gate: the de-sharded embedding-TABLE fixture —
    an annotated ``P('dp', None)`` table stored replicated must fail the
    annotation contract at ERROR, independent of any ZeRO stage."""
    import jax
    from paddle_tpu.analysis import hlo as hlo_audit
    from paddle_tpu.analysis.hlo.fixtures import desharded_table_step
    from paddle_tpu.parallel import make_mesh
    n = 1
    for v in axes.values():
        n *= v
    mesh = make_mesh(dict(axes), devices=jax.devices()[:n])
    step, inputs, label = desharded_table_step(mesh)
    return hlo_audit.audit_train_step(
        step, inputs, label, site="hlo_audit:seeded_table", do_emit=False)


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="hlo_audit",
        description="compiled-program audit over zoo train steps on "
                    "virtual wide meshes (abstract AOT lowering; no "
                    "device execution, no chip)")
    ap.add_argument("--model", action="append", choices=sorted(BUILDERS),
                    help="audit one model (repeatable)")
    ap.add_argument("--zoo", action="store_true",
                    help="audit every zoo model")
    ap.add_argument("--mesh", action="append", default=None,
                    help="mesh spec DP[xMP[xSP]], repeatable "
                         "(default 4x2)")
    ap.add_argument("--zero", type=int, default=1, choices=(0, 1, 2, 3),
                    help="ZeRO stage for the train steps (default 1)")
    ap.add_argument("--seeded", action="store_true",
                    help="also audit the de-sharded-ZeRO negative "
                         "fixture (must produce ERROR findings)")
    ap.add_argument("--autoshard", action="store_true",
                    help="shard models via the FLAGS_autoshard=apply "
                         "rules engine (analysis.autoshard) instead of "
                         "their explicit annotation entry points")
    ap.add_argument("--strict", action="store_true",
                    help="exit non-zero if any ERROR finding fires")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit a JSON report instead of text")
    ap.add_argument("--suppress", default="",
                    help="comma-separated audit pass ids to skip")
    args = ap.parse_args(argv)

    meshes = [parse_mesh(s) for s in (args.mesh or ["4x2"])]
    names = list(args.model or [])
    if args.zoo or (not names and not args.seeded):
        names = sorted(BUILDERS)
    suppress = tuple(s.strip() for s in args.suppress.split(",")
                     if s.strip())

    import math
    need = max(math.prod(m.values()) for m in meshes)
    _provision(max(1, need))

    from paddle_tpu.analysis import hlo as hlo_audit
    if args.autoshard:
        from paddle_tpu.framework.flags import set_flags
        _AUTOSHARD[0] = True
        set_flags({"FLAGS_autoshard": "apply"})

    results, n_errors = [], 0
    for axes in meshes:
        label = "x".join(f"{a}{v}" for a, v in axes.items())
        for name in names:
            res = audit_model(name, axes, args.zero, suppress=suppress)
            n_errors += res.report.n_errors
            results.append((name, label, res))
        if args.seeded:
            res = audit_seeded(axes, args.zero or 1)
            n_errors += res.report.n_errors
            results.append(("seeded_desharded_zero", label, res))
            res_t = audit_seeded_table(axes)
            n_errors += res_t.report.n_errors
            results.append(("seeded_desharded_table", label, res_t))

    total = sum(len(r.report) for _, _, r in results)
    if args.as_json:
        payload = {
            "results": [{"model": n, **r.as_dict()}
                        for n, _m, r in results],
            "total_findings": total, "n_errors": n_errors,
            "strict": bool(args.strict),
            "ledger": [{"site": e["site"], "key": e["key"],
                        "ms": e["ms"]}
                       for e in hlo_audit.audit_compile_events()],
        }
        print(json.dumps(payload, indent=1))
    else:
        for name, mesh_label, res in results:
            head = (f"[{name} @ {mesh_label}] "
                    f"collectives={res.stats.collective_count} "
                    f"wire={res.stats.collective_wire_bytes / 1024:.1f}KiB "
                    f"hbm={res.stats.memory.get('peak_bytes', 0) / 1048576:.2f}MiB "
                    f"flops={res.stats.cost.get('flops', 0):.3g}")
            print(head)
            if res.report:
                print(res.report.format())
        print(f"hlo_audit: {len(results)} audit(s), {total} finding(s), "
              f"{n_errors} error(s)")
    return 1 if (args.strict and n_errors) else 0


if __name__ == "__main__":
    sys.exit(main())
