#!/usr/bin/env python
"""autoshard — propose/apply rules-driven sharding plans for zoo models
and verify applied plans with the compiled-HLO audit.

The CLI face of ``paddle_tpu.analysis.autoshard``: for each zoo model it
matches the active PartitionRules table over the param pytree and prints
the plan (per-leaf rule provenance, unmatched leaves, hand-annotation
conflicts).  With ``--apply`` it writes the annotations, builds the
sharded TrainStep over the requested virtual mesh and runs the PR-8 HLO
audit on the compiled program — closing the loop from lint diagnosis to
applied PartitionSpecs to partitioned-HLO proof, with no hardware
attached (``--xla_force_host_platform_device_count`` provisioning, same
as tools/hlo_audit.py).

Usage:
    python tools/autoshard.py --zoo --mesh 8x2 --propose
    python tools/autoshard.py --zoo --mesh 8x2 --apply --strict --json
    python tools/autoshard.py --model bert --mesh 16x2 --apply
    python tools/autoshard.py --seeded --strict            # must exit 1

``--strict`` exits non-zero on any rule conflict, any unmatched >=2-d
leaf, or any ERROR-severity audit finding — the zoo must shard cleanly
from the shipped tables (zero hand annotations left), and the
``--seeded`` contradicting-annotation fixture must fail.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ZOO_MODELS = ("bert", "gpt", "resnet_block", "wide_deep")


def parse_mesh(spec: str):
    """'16x2' -> {dp:16, mp:2}; '8x2x2' -> {dp:8, mp:2, sp:2}."""
    parts = [int(p) for p in spec.lower().replace("*", "x").split("x") if p]
    if not parts or any(p < 1 for p in parts) or len(parts) > 3:
        raise ValueError(f"bad mesh spec {spec!r}: want DP[xMP[xSP]]")
    axes = {"dp": parts[0]}
    if len(parts) > 1:
        axes["mp"] = parts[1]
    if len(parts) > 2:
        axes["sp"] = parts[2]
    return axes


def _provision(n_devices: int) -> None:
    """Force an ``n_devices``-wide virtual CPU platform BEFORE jax
    initializes (explicit JAX_PLATFORMS in the env wins)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = " ".join(f for f in os.environ.get("XLA_FLAGS", "").split()
                     if not f.startswith("--xla_force_host_platform"))
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n_devices}"
    ).strip()


# -- zoo builders: (model, TrainStep factory) -------------------------------

def _build_bert():
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.text.models.bert import BertConfig, BertForPretraining
    cfg = BertConfig.tiny(vocab_size=64, hidden_size=16, layers=2,
                          heads=2, seq=32)
    cfg.hidden_dropout_prob = 0.0
    cfg.attention_probs_dropout_prob = 0.0
    paddle.seed(0)
    model = BertForPretraining(cfg)

    def make_step(mesh, zero):
        from paddle_tpu.parallel import TrainStep
        opt = paddle.optimizer.AdamW(parameters=model.parameters(),
                                     learning_rate=1e-3)
        step = TrainStep(model, opt, mesh=mesh, zero=zero, remat=True)
        dp = dict(mesh.shape).get("dp", 1)
        rng = np.random.RandomState(0)
        ids = rng.randint(0, cfg.vocab_size, (4 * dp, 16))
        labels = np.where(rng.rand(*ids.shape) < 0.15, ids, -100)
        return step, (ids, None, None, labels), None

    return model, make_step


def _build_gpt():
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.text.models.gpt import GPTConfig, GPTModel
    cfg = GPTConfig.tiny(vocab_size=64, hidden_size=16, layers=2,
                         heads=2, seq=32)
    cfg.dropout = 0.0
    paddle.seed(0)
    model = GPTModel(cfg)

    def make_step(mesh, zero):
        from paddle_tpu.parallel import TrainStep
        opt = paddle.optimizer.AdamW(parameters=model.parameters(),
                                     learning_rate=1e-3)
        step = TrainStep(model, opt, mesh=mesh, zero=zero, remat=True)
        dp = dict(mesh.shape).get("dp", 1)
        rng = np.random.RandomState(0)
        ids = rng.randint(0, cfg.vocab_size, (4 * dp, 16))
        # forward(input_ids, labels) computes the shifted LM loss itself
        return step, (ids, ids.copy()), None

    return model, make_step


def _build_resnet_block(ch=8, hw=8):
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn

    class Block(nn.Layer):
        """Residual conv-BN-ReLU pair + linear head (the hlo_audit zoo
        block): conv kernels replicate under TP, the head column-shards."""

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

    def make_step(mesh, zero):
        from paddle_tpu.parallel import TrainStep
        opt = paddle.optimizer.Momentum(parameters=model.parameters(),
                                        learning_rate=0.1, momentum=0.9)
        step = TrainStep(model, opt, loss_fn=paddle.nn.CrossEntropyLoss(),
                         mesh=mesh, zero=zero)
        dp = dict(mesh.shape).get("dp", 1)
        rng = np.random.RandomState(0)
        x = rng.randn(2 * dp, ch, hw, hw).astype("float32")
        y = rng.randint(0, 16, (2 * dp,))
        return step, (x,), y

    return model, make_step


def _build_wide_deep(vocab=1024, emb_dim=16, num_slots=26, dense_dim=13):
    """Wide&Deep with a DEVICE-RESIDENT deep table (the embedding-rules
    seat: the PS-backed tables live host-side and outside jit scope, so
    the auditable variant carries its deep embedding in-graph, where the
    row-sharded-embedding rule shards it over mp)."""
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn

    class CtrDense(nn.Layer):
        def __init__(self):
            super().__init__()
            self.embedding = nn.Embedding(vocab, emb_dim)
            layers, in_dim = [], num_slots * emb_dim + dense_dim
            for h in (64, 64):
                layers += [nn.Linear(in_dim, h), nn.ReLU()]
                in_dim = h
            layers.append(nn.Linear(in_dim, 1))
            self.dnn = nn.Sequential(*layers)
            self.wide_dense = nn.Linear(dense_dim, 1)

        def forward(self, ids, dense_x):
            from paddle_tpu import ops
            deep = self.embedding(ids).reshape([ids.shape[0], -1])
            deep = self.dnn(ops.concat([deep, dense_x], axis=-1))
            return deep + self.wide_dense(dense_x)

    paddle.seed(0)
    model = CtrDense()

    def make_step(mesh, zero):
        import jax.numpy as jnp
        from paddle_tpu.parallel import TrainStep

        def bce(out, label):
            from paddle_tpu.framework.tensor import unwrap
            x, y = unwrap(out), unwrap(label)
            l = jnp.maximum(x, 0) - x * y + jnp.log1p(jnp.exp(-jnp.abs(x)))
            return l.mean()

        opt = paddle.optimizer.AdamW(parameters=model.parameters(),
                                     learning_rate=1e-3)
        step = TrainStep(model, opt, loss_fn=bce, mesh=mesh, zero=zero)
        dp = dict(mesh.shape).get("dp", 1)
        rng = np.random.RandomState(0)
        ids = rng.randint(0, vocab, (4 * dp, num_slots))
        dense = rng.randn(4 * dp, dense_dim).astype("float32")
        label = (rng.rand(4 * dp, 1) > 0.5).astype("float32")
        return step, (ids, dense), label

    return model, make_step


BUILDERS = {"bert": _build_bert, "gpt": _build_gpt,
            "resnet_block": _build_resnet_block,
            "wide_deep": _build_wide_deep}


def run_model(name: str, axes: dict, *, rules, do_apply: bool, zero: int):
    """Propose (and optionally apply+audit) one zoo model over one mesh.
    Returns a result dict."""
    import jax
    from paddle_tpu.analysis import autoshard
    from paddle_tpu.parallel import make_mesh
    n = 1
    for v in axes.values():
        n *= v
    mesh = make_mesh(dict(axes), devices=jax.devices()[:n])
    model, make_step = BUILDERS[name]()
    plan = autoshard.propose(model, rules=rules, mesh=mesh)
    out = {"model": name,
           "mesh": "x".join(f"{a}{v}" for a, v in axes.items()),
           "plan": plan.as_dict(), "applied": False, "audit": None}
    if do_apply:
        plan = autoshard.apply(model, rules=rules, mesh=mesh, plan=plan)
        out["applied"] = True
        from paddle_tpu.analysis import hlo as hlo_audit
        step, inputs, label = make_step(mesh, zero)
        res = hlo_audit.audit_train_step(
            step, inputs, label, site=f"autoshard:zoo:{name}",
            do_emit=False)
        out["audit"] = res.as_dict()
        out["audit_errors"] = res.report.n_errors
    out["plan_obj"] = plan
    return out


def run_seeded(axes: dict, *, rules):
    """The negative gate: a hand annotation CONTRADICTING the rules table
    must surface as a conflict (and fail --strict)."""
    import jax
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.analysis import autoshard
    from paddle_tpu.parallel import make_mesh, shard_parameter
    n = 1
    for v in axes.values():
        n *= v
    mesh = make_mesh(dict(axes), devices=jax.devices()[:n])
    model, _ = BUILDERS["bert"]()
    # column-parallel role hand-annotated ROW-parallel: a real layout bug
    shard_parameter(
        model.bert.encoder.layers[0].self_attn.q_proj.weight, P("mp", None))
    plan = autoshard.propose(model, rules=rules, mesh=mesh)
    return {"model": "seeded_conflicting_annotation",
            "mesh": "x".join(f"{a}{v}" for a, v in axes.items()),
            "plan": plan.as_dict(), "applied": False, "audit": None,
            "plan_obj": plan}


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="autoshard",
        description="rules-driven sharding plans for zoo models, "
                    "HLO-audit-verified (abstract lowering; no chip)")
    ap.add_argument("--model", action="append", choices=sorted(BUILDERS),
                    help="plan one model (repeatable)")
    ap.add_argument("--zoo", action="store_true",
                    help="plan every zoo model")
    ap.add_argument("--mesh", action="append", default=None,
                    help="mesh spec DP[xMP[xSP]], repeatable (default 4x2)")
    ap.add_argument("--rules", default="default",
                    help="rules table name (default|transformer|conv|"
                         "embedding|registered)")
    ap.add_argument("--zero", type=int, default=1, choices=(0, 1, 2, 3),
                    help="ZeRO stage for --apply train steps (default 1)")
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--propose", action="store_true",
                      help="plan only (default)")
    mode.add_argument("--apply", action="store_true", dest="do_apply",
                      help="apply the plan, build the sharded TrainStep "
                           "and run the HLO audit on the compiled program")
    ap.add_argument("--seeded", action="store_true",
                    help="also plan the contradicting-hand-annotation "
                         "fixture (must produce a conflict)")
    ap.add_argument("--strict", action="store_true",
                    help="exit non-zero on any conflict, unmatched >=2-d "
                         "leaf, or ERROR audit finding")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit a JSON report instead of text")
    args = ap.parse_args(argv)

    meshes = [parse_mesh(s) for s in (args.mesh or ["4x2"])]
    names = list(args.model or [])
    if args.zoo or (not names and not args.seeded):
        names = sorted(BUILDERS)

    import math
    need = max(math.prod(m.values()) for m in meshes)
    _provision(max(1, need))

    from paddle_tpu.analysis.autoshard import rules_table
    from paddle_tpu.framework.flags import set_flags
    rules = rules_table(args.rules)
    # keep the lint side (sharding-coverage rule naming) on the same table
    set_flags({"FLAGS_autoshard_rules": args.rules})

    results = []
    for axes in meshes:
        for name in names:
            results.append(run_model(name, axes, rules=rules,
                                     do_apply=args.do_apply,
                                     zero=args.zero))
        if args.seeded:
            results.append(run_seeded(axes, rules=rules))

    n_conflicts = sum(len(r["plan_obj"].conflicts) for r in results)
    n_unmatched = sum(len(r["plan_obj"].unmatched) for r in results)
    n_audit_errors = sum(r.get("audit_errors") or 0 for r in results)

    if args.as_json:
        payload = {"results": [{k: v for k, v in r.items()
                                if k != "plan_obj"} for r in results],
                   "rules": args.rules, "n_conflicts": n_conflicts,
                   "n_unmatched": n_unmatched,
                   "n_audit_errors": n_audit_errors,
                   "strict": bool(args.strict)}
        print(json.dumps(payload, indent=1))
    else:
        for r in results:
            print(f"[{r['model']} @ {r['mesh']}]")
            print(r["plan_obj"].format())
            if r["audit"] is not None:
                a = r["audit"]
                print(f"  hlo-audit: {a['findings']['n_errors']} error(s), "
                      f"{len(a['findings']['diagnostics'])} finding(s), "
                      f"collectives={a['stats']['collective_count']} "
                      f"wire={a['stats']['collective_wire_bytes'] / 1024:.1f}"
                      f"KiB")
        print(f"autoshard: {len(results)} plan(s), {n_conflicts} "
              f"conflict(s), {n_unmatched} unmatched, "
              f"{n_audit_errors} audit error(s)")
    bad = n_conflicts + n_unmatched + n_audit_errors
    return 1 if (args.strict and bad) else 0


if __name__ == "__main__":
    sys.exit(main())
