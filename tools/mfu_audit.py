"""Measured-MFU audit for the bench workloads (VERDICT r4 weak #1/next #2).

For each compiled train step: FLOPs/step and bytes/step from XLA's own
``compile().cost_analysis()`` via the HLO-audit extraction surface
(``paddle_tpu.analysis.hlo.extract_cost`` — the op-level accounting the
reference does in operators/benchmark/op_tester.cc; ISSUE 8 re-based the
last hand-maintained cost model, the static LeNet epoch, onto
``Executor.epoch_executable`` so every number here comes from the program
XLA actually compiled), and per-step time from an IN-GRAPH K-step
``lax.fori_loop`` dispatched once — two K values, delta method, so
the per-dispatch host cost and the fence cancel exactly.

Bounds: the PUBLISHED peaks of the device the audit runs on, from the one
table below (``DEVICE_PEAKS``, keyed by jax's ``device_kind``).  A device
that is not in the table is an error, not a default.

NB: bytes/step from cost_analysis is PRE-FUSION algorithmic traffic
(every HLO op's operands counted as HBM accesses) — an upper bound, not
achieved HBM traffic; the memory fraction is indicative only.

Usage: PYTHONPATH=/root/repo python tools/mfu_audit.py [--dry] [workload ...]
Prints one JSON line per workload: flops/step, bytes/step, ms/step,
achieved TFLOP/s + GB/s, fraction of each bound, and which bound binds.

``--dry``: run every workload at a tiny CPU-safe configuration (resnet18
@32px b4, BERT-tiny, 2-layer transformer, 5-step LeNet epoch) so the whole
harness — TrainStep build, AOT lower, cost_analysis, chained delta-of-K
loop, JSON emit — is exercised end-to-end on the 8-virtual-device CPU
mesh.  A dry record carries no achieved rate and no fraction of a peak
(``null``: not measured — a CPU time is never written under a device
metric); its ``binding_bound`` is the program's arithmetic intensity
against the ridge of ``DRY_PEAKS_OF``.  The run proves the harness can't
silently rot between perf rounds (tests/test_mfu_audit_smoke.py).
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

# Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_tflops": 197.0, "int8_tops": 393.0,
                    "hbm_gbs": 819.0},
}
DRY_PEAKS_OF = "TPU v5 lite"


def device_peaks(dry=False):
    """(device_kind, its row of DEVICE_PEAKS); an unknown device raises."""
    import jax
    kind = DRY_PEAKS_OF if dry else jax.devices()[0].device_kind
    if kind not in DEVICE_PEAKS:
        raise KeyError(
            f"no published peaks for device_kind {kind!r} in "
            f"tools/mfu_audit.py DEVICE_PEAKS ({sorted(DEVICE_PEAKS)}); "
            "add its row with the source, do not default")
    return kind, DEVICE_PEAKS[kind]

K_SMALL, K_LARGE = 3, 9


def _cost(compiled):
    """(flops, bytes_accessed) through the shared HLO-audit extraction
    (one implementation serves mfu_audit, hlo_audit and the dryrun
    scaling table)."""
    from paddle_tpu.analysis.hlo import extract_cost
    c = extract_cost(compiled)
    return c["flops"], c["bytes_accessed"]


def _loop_time(body, state, args, k_small=K_SMALL, k_large=K_LARGE,
               reps=3):
    """Per-step seconds via the DELTA of two in-graph loop lengths (the
    dispatch + fence overhead cancels exactly).  The chained-loss loop
    itself is shared with bench.py (_chained_step_loop): the loss rides
    the carry so XLA cannot dead-code-eliminate any step — returning only
    the step counter measured 6.6 ms for a 47 ms BERT step."""
    from bench import _chained_step_loop, _time_loop_once
    f = _chained_step_loop(body, args)
    times = {k: _time_loop_once(f, state, k, reps)
             for k in (k_small, k_large)}
    return (times[k_large] - times[k_small]) / (k_large - k_small)


def _emit(name, flops, bytes_, sec, units_per_step, unit, extra=None):
    from bench import _device_record
    dry = bool((extra or {}).get("dry"))
    kind, peaks = device_peaks(dry)
    # least time the chip could take on each bound; the larger one binds
    t_compute = flops / (peaks["bf16_tflops"] * 1e12)
    t_memory = bytes_ / (peaks["hbm_gbs"] * 1e9)
    rec = {
        "workload": name,
        "device": _device_record(),
        "peaks_of": kind,
        "flops_per_step": flops, "bytes_per_step": bytes_,
        "ms_per_step": round(sec * 1e3, 3),
        "throughput": round(units_per_step / sec, 1), "unit": unit,
        "achieved_tflops": None if dry else round(flops / sec / 1e12, 2),
        "achieved_gbs": None if dry else round(bytes_ / sec / 1e9, 1),
        "frac_of_peak_tflops": None if dry else round(t_compute / sec, 3),
        "frac_of_peak_gbs": None if dry else round(t_memory / sec, 3),
        "binding_bound": "compute" if t_compute >= t_memory else "memory",
    }
    rec.update(extra or {})
    print(json.dumps(rec), flush=True)


def audit_resnet50(dry=False):
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.ops.pallas import fused_conv
    from paddle_tpu.parallel import init_mesh, TrainStep
    from paddle_tpu.vision.models import resnet50, resnet18

    if dry:
        model, batch, hw = resnet18(data_format="NHWC"), 4, 32
    else:
        model, batch, hw = resnet50(data_format="NHWC"), 256, 224
    mesh = init_mesh({"dp": -1})
    opt = paddle.optimizer.Momentum(parameters=model.parameters(),
                                    learning_rate=0.1, momentum=0.9)
    step = TrainStep(model, opt, loss_fn=paddle.nn.CrossEntropyLoss(),
                     mesh=mesh, compute_dtype=None if dry else jnp.bfloat16,
                     donate=False)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(batch, hw, hw, 3).astype("float32"))
    y = jnp.asarray(rng.randint(0, 1000, (batch,)))
    float(step((x,), y))          # build state + compile the plain step
    import jax
    body = step._build_step()
    lowered = jax.jit(body).lower(step.state, (x,), y, np.float32(0.1))
    flops, bytes_ = _cost(lowered.compile())
    ks = (1, 2) if dry else (K_SMALL, K_LARGE)
    sec = _loop_time(body, step.state, ((x,), y, np.float32(0.1)),
                     k_small=ks[0], k_large=ks[1], reps=1 if dry else 3)
    # record which conv path produced the number — a fused-conv
    # measurement must never be mistaken for an XLA-path one
    _emit("resnet50_dygraph", flops, bytes_, sec, batch, "img/s",
          extra={"pallas_conv": fused_conv.enabled(), "dry": dry})


def audit_bert(dry=False):
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.parallel import init_mesh, TrainStep
    from paddle_tpu.text.models.bert import BertConfig, BertForPretraining

    if dry:
        cfg, batch, seq = BertConfig.tiny(seq=32), 8, 32
    else:
        cfg, batch, seq = BertConfig.base(), 64, 128
    mesh = init_mesh({"dp": -1})
    model = BertForPretraining(cfg)
    opt = paddle.optimizer.AdamW(parameters=model.parameters(),
                                 learning_rate=1e-4, weight_decay=0.01)
    step = TrainStep(model, opt, mesh=mesh,
                     compute_dtype=None if dry else jnp.bfloat16,
                     donate=False)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    n_pred = max(2, int(seq * 0.15))
    pos = np.stack([rng.choice(seq, size=n_pred, replace=False)
                    for _ in range(batch)]).astype("int64")
    labels = jnp.asarray(np.take_along_axis(np.asarray(ids), pos, 1))
    positions = jnp.asarray(pos)
    args = (ids, None, None, labels, None, positions)
    float(step(args))
    body = step._build_step()
    inputs = tuple(None if a is None else jnp.asarray(a) for a in args)
    lowered = __import__("jax").jit(body).lower(
        step.state, inputs, None, np.float32(1e-4))
    flops, bytes_ = _cost(lowered.compile())
    ks = (1, 2) if dry else (K_SMALL, K_LARGE)
    sec = _loop_time(body, step.state, (inputs, None, np.float32(1e-4)),
                     k_small=ks[0], k_large=ks[1], reps=1 if dry else 3)
    _emit("bert_base_pretrain", flops, bytes_, sec, batch, "seq/s",
          extra={"dry": dry})


def audit_transformer_big(dry=False):
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.parallel import init_mesh, TrainStep
    from bench import bench_transformer_big  # noqa: F401  (same model class)
    import paddle_tpu.nn as nn

    if dry:
        vocab, dm, nh, nl, ffn, batch, seq = 128, 32, 2, 2, 64, 2, 16
    else:
        vocab, dm, nh, nl, ffn, batch, seq = 32768, 1024, 16, 6, 4096, 64, 64

    class Seq2SeqLM(nn.Layer):
        def __init__(self):
            super().__init__()
            self.embed = nn.Embedding(vocab, dm)
            self.pos = nn.Embedding(seq, dm)
            self.core = nn.Transformer(
                d_model=dm, nhead=nh, num_encoder_layers=nl,
                num_decoder_layers=nl, dim_feedforward=ffn, dropout=0.0)
            self.proj = nn.Linear(dm, vocab)
            self.loss = nn.CrossEntropyLoss()

        def forward(self, src, tgt, labels):
            p = paddle.arange(src.shape[1])
            s = self.embed(src) + self.pos(p)
            t = self.embed(tgt) + self.pos(p)
            h = self.core(s, t)
            logits = self.proj(h)
            return self.loss(logits.reshape([-1, logits.shape[-1]]),
                             labels.reshape([-1]))

    mesh = init_mesh({"dp": -1})
    model = Seq2SeqLM()
    opt = paddle.optimizer.Adam(parameters=model.parameters(),
                                learning_rate=1e-4)
    step = TrainStep(model, opt, mesh=mesh,
                     compute_dtype=None if dry else jnp.bfloat16,
                     donate=False)
    rng = np.random.RandomState(0)
    src = jnp.asarray(rng.randint(0, vocab, (batch, seq)))
    tgt = jnp.asarray(rng.randint(0, vocab, (batch, seq)))
    lbl = jnp.asarray(rng.randint(0, vocab, (batch, seq)))
    float(step((src, tgt, lbl)))
    body = step._build_step()
    lowered = __import__("jax").jit(body).lower(
        step.state, (src, tgt, lbl), None, np.float32(1e-4))
    flops, bytes_ = _cost(lowered.compile())
    ks = (1, 2) if dry else (K_SMALL, K_LARGE)
    sec = _loop_time(body, step.state, ((src, tgt, lbl), None,
                                        np.float32(1e-4)),
                     k_small=ks[0], k_large=ks[1], reps=1 if dry else 3)
    _emit("transformer_big", flops, bytes_, sec, batch * seq, "tok/s",
          extra={"dry": dry})


def audit_lenet(dry=False):
    """LeNet's scanned epoch is ONE dispatch; FLOPs/bytes from
    cost_analysis of the SAME scanned program via
    ``Executor.epoch_executable`` (ISSUE 8: the hand-maintained per-layer
    FLOP count is gone — it could silently drift from the compiled
    program), per-step time from epoch time / steps."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    import paddle_tpu.static as static

    batch, steps = (8, 5) if dry else (128, 200)
    paddle.enable_static()
    try:
        main, startup = static.Program(), static.Program()
        with static.program_guard(main, startup):
            img = static.data("img", [None, 1, 28, 28], "float32")
            label = static.data("label", [None], "int64")
            h = static.nn.conv2d(img, 6, 5, padding=2, act="relu")
            h = paddle.nn.functional.max_pool2d(h, 2, 2)
            h = static.nn.conv2d(h, 16, 5, act="relu")
            h = paddle.nn.functional.max_pool2d(h, 2, 2)
            h = paddle.flatten(h, start_axis=1)
            h = static.nn.fc(h, 120, activation="relu")
            h = static.nn.fc(h, 84, activation="relu")
            logits = static.nn.fc(h, 10)
            loss = paddle.nn.functional.cross_entropy(logits, label)
            paddle.optimizer.SGD(learning_rate=0.01).minimize(loss)
        exe = static.Executor()
        exe.run(startup)
        rng = np.random.RandomState(0)
        stacks = {"img": jnp.asarray(rng.randn(steps, batch, 1, 28, 28)
                                     .astype("float32")),
                  "label": jnp.asarray(rng.randint(0, 10, (steps, batch))
                                       .astype("int64"))}
        exe.train_from_dataset(main, dataset=stacks, fetch_list=[loss])
        best = None
        for _ in range(1 if dry else 3):
            t0 = time.perf_counter()
            out = exe.train_from_dataset(main, dataset=stacks,
                                         fetch_list=[loss])
            float(np.asarray(out[loss.name]).sum())
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        # FLOPs/bytes of the scanned epoch program itself (the executor's
        # lowered-executable surface): per-step = epoch totals / steps
        epoch_exe = exe.epoch_executable(main, dataset=stacks,
                                         fetch_list=[loss])
        ep_flops, ep_bytes = _cost(epoch_exe)
        sec = best / steps
        _emit("mnist_lenet_static", ep_flops / steps, ep_bytes / steps,
              sec, batch, "img/s", extra={"dry": dry})
    finally:
        paddle.disable_static()


AUDITS = {
    "resnet50_dygraph": audit_resnet50,
    "bert_base_pretrain": audit_bert,
    "transformer_big": audit_transformer_big,
    "mnist_lenet_static": audit_lenet,
}


if __name__ == "__main__":
    argv = sys.argv[1:]
    dry = "--dry" in argv
    names = [a for a in argv if a != "--dry"] or list(AUDITS)
    from paddle_tpu.utils.cache_dirs import enable_jax_compile_cache
    enable_jax_compile_cache()
    device_peaks(dry)                    # unknown device: fail before work
    for n in names:
        print(f"[mfu] {n} ...", file=sys.stderr, flush=True)
        AUDITS[n](dry=dry)
