#!/usr/bin/env python
"""graph_lint — trace zoo models in abstract-eval mode and lint them.

The CLI face of ``paddle_tpu.analysis``: builds a model from the zoo
(lenet / resnet_block / bert / wide_deep), captures its forward as a
closed jaxpr via ``jax.make_jaxpr`` over ShapeDtypeStructs — NO device
execution, so this runs anywhere the framework imports — and runs the
full lint pass suite, emitting a text or JSON report.

Usage:
    python tools/graph_lint.py --model lenet
    python tools/graph_lint.py --zoo --strict          # CI lane: rc!=0 on
                                                       # any finding
    python tools/graph_lint.py --zoo --json            # machine-readable

``--strict`` makes ANY diagnostic (any severity) a non-zero exit: the
model zoo is the framework's own conformance corpus and must lint clean
(zero false positives is an acceptance bar for every pass).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# abstract eval needs no accelerator; default to CPU so the lint tool works
# on build hosts without a TPU attached (explicit env overrides win)
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _specs(*shapes_dtypes):
    import jax
    return [jax.ShapeDtypeStruct(tuple(s), d) for s, d in shapes_dtypes]


def build_lenet(batch=8):
    import numpy as np
    from paddle_tpu.vision.models import LeNet
    return LeNet(), _specs(((batch, 1, 28, 28), np.float32))


def build_resnet_block(batch=4, ch=8, hw=8):
    import numpy as np
    import paddle_tpu.nn as nn

    class Block(nn.Layer):
        """One residual conv-BN-ReLU pair (ResNet's high-res stage)."""

        def __init__(self):
            super().__init__()
            self.c1 = nn.Conv2D(ch, ch, 3, padding=1, bias_attr=False)
            self.b1 = nn.BatchNorm2D(ch)
            self.c2 = nn.Conv2D(ch, ch, 3, padding=1, bias_attr=False)
            self.b2 = nn.BatchNorm2D(ch)
            self.relu = nn.ReLU()

        def forward(self, x):
            h = self.relu(self.b1(self.c1(x)))
            return self.relu(self.b2(self.c2(h)) + x)

    return Block(), _specs(((batch, ch, hw, hw), np.float32))


def build_bert(batch=2, seq=32):
    import numpy as np
    from paddle_tpu.text.models.bert import BertConfig, BertModel
    cfg = BertConfig.tiny(seq=seq)
    # int32 ids: under disabled x64 an int64 feed would itself be a
    # dtype-promotion finding — the zoo feeds what the hardware runs
    return BertModel(cfg), _specs(((batch, seq), np.int32))


def build_wide_deep(batch=8, num_slots=26, dense_dim=13, emb_dim=16):
    """The dense compute of Wide&Deep over pre-pulled PS rows
    (rec.wide_deep._DenseCore): the sparse pull is a HOST step by design,
    so the traced-program surface is the dense core."""
    import numpy as np
    from paddle_tpu.rec.wide_deep import WideDeep, _DenseCore
    wd = WideDeep(emb_dim=emb_dim, num_slots=num_slots,
                  dense_dim=dense_dim)
    core = _DenseCore(wd)
    u1, u2 = 64, 64
    return core, _specs(
        ((u1, 1), np.float32),                    # wide rows
        ((u2, emb_dim), np.float32),              # deep rows
        ((batch, num_slots), np.int32),           # wide inverse ids
        ((batch, num_slots), np.int32),           # deep inverse ids
        ((batch, dense_dim), np.float32))         # dense feats


def build_moe(batch=2, seq=32):
    """Alternating dense/MoE GPT blocks with the routed (all-to-all)
    dispatch — the gating/top-k/scatter surface the dense zoo never
    exercises.  Traced with mutable buffers (raw-callable convention):
    the MoE stats buffers (dropped/load) are graph outputs in serving,
    and hiding them here would miscount their compute as dead."""
    import numpy as np
    from paddle_tpu.text.models.gpt import GPTMoEConfig, GPTMoEModel
    from paddle_tpu.framework import functional as F
    cfg = GPTMoEConfig.tiny(seq=seq)
    apply, params, buffers = F.functionalize(
        GPTMoEModel(cfg, dispatch="routed"), training=False,
        with_buffers=True)
    return apply, (params, buffers,
                   *_specs(((batch, seq), np.int32)))


def build_decode_step(slots=2, cache=32):
    """The slot loop's single-step decode program (Generator._build_step)
    — the hot serving dispatch, traced exactly as step_exec compiles it.
    Returns ``(fn, avals)``: a RAW traceable callable, not a layer — the
    already-functionalized step takes (params, buffers, cache, logits,
    start, finished, active, joined, pos)."""
    from paddle_tpu.text.models.gpt import GPTConfig, GPTModel
    from paddle_tpu.text.generation import Generator
    m = GPTModel(GPTConfig.tiny(seq=64))
    gen = Generator(m, site="zoo:decode_step", seq_buckets=(8, 16, 32),
                    max_len=64)
    fn = gen._build_step(slots, cache, -1)
    return fn, (*gen._state_avals(), *gen.step_avals(slots, cache))


ZOO = {
    "lenet": build_lenet,
    "resnet_block": build_resnet_block,
    "bert": build_bert,
    "wide_deep": build_wide_deep,
    "moe": build_moe,
    "decode_step": build_decode_step,
}


def lint_model(name: str, suppress=()):
    """Trace zoo model ``name`` abstractly and lint it.  Returns a
    LintReport.  A builder returns ``(layer, input_specs)`` for the
    functionalize path, or ``(raw_callable, avals)`` for programs that
    are already functional (e.g. the slot-loop decode step)."""
    import jax
    from paddle_tpu import analysis, nn
    from paddle_tpu.framework import functional as F
    layer, specs = ZOO[name]()
    if isinstance(layer, nn.Layer):
        apply, params, buffers = F.functionalize(layer, training=False)

        def fwd(p, b, *xs):
            return apply(p, b, *xs)

        closed = jax.make_jaxpr(fwd)(params, buffers, *specs)
    else:
        closed = jax.make_jaxpr(layer)(*specs)
    return analysis.lint_jaxpr(closed, site=f"zoo:{name}", kind="cli",
                               suppress=suppress)


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="graph_lint",
        description="static-analysis lint over traced zoo models "
                    "(abstract eval; no device execution)")
    ap.add_argument("--model", action="append", choices=sorted(ZOO),
                    help="lint one model (repeatable)")
    ap.add_argument("--zoo", action="store_true",
                    help="lint every zoo model")
    ap.add_argument("--strict", action="store_true",
                    help="exit non-zero if ANY diagnostic fires")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit a JSON report instead of text")
    ap.add_argument("--suppress", default="",
                    help="comma-separated pass ids to skip")
    args = ap.parse_args(argv)

    names = list(args.model or [])
    if args.zoo or not names:
        names = sorted(ZOO)
    suppress = tuple(s.strip() for s in args.suppress.split(",")
                     if s.strip())

    reports = {}
    for name in names:
        reports[name] = lint_model(name, suppress=suppress)

    total = sum(len(r) for r in reports.values())
    if args.as_json:
        payload = {"models": {n: r.as_dict() for n, r in reports.items()},
                   "total_findings": total, "strict": bool(args.strict)}
        print(json.dumps(payload, indent=1))
    else:
        for name, r in reports.items():
            print(r.format())
        print(f"graph_lint: {len(names)} model(s), {total} finding(s)")
    return 1 if (args.strict and total) else 0


if __name__ == "__main__":
    sys.exit(main())
