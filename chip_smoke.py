#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system starts on the chip.

    python chip_smoke.py              # one chip: train, serve, layouts,
                                      # hybrid, latent forms, gated delta,
                                      # kernels, cache
    python chip_smoke.py --multichip  # four chips: only the sharded paths

One process, the entry points a user calls (``parallel.TrainStep``,
``serving.Server``, ``text.generation.Generator``, ``ops.pallas``), random
weights from ``--seed``, full widths with depth as the models ship it.  It
is a smoke run, not a benchmark: the seconds it prints say that a phase ran,
not how fast the system is.

Every phase prints one JSON line (phase, seconds, compile_seconds, checked);
any failed check raises, so the run cannot end with exit code 0.  The LAST
line of stdout is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

The script refuses to run (non-zero exit, no result line) when
``jax.devices()[0].platform`` is not ``tpu``.  The phase functions take a
``Size``; ``tests/test_chip_smoke.py`` drives them at ``TINY`` on the CPU.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from dataclasses import dataclass
from typing import Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.framework.flags import flags_restore, flags_snapshot, set_flags
from paddle_tpu.parallel import TrainStep, init_mesh, make_mesh
from paddle_tpu.profiler import ledger
from paddle_tpu.text.generation import Generator
from paddle_tpu.text.models.bert import (BertConfig, BertForPretraining,
                                         apply_tensor_parallel)
from paddle_tpu.text.models.gpt import (GPTConfig, GPTModel, GPTMoEConfig,
                                        GPTMoEModel)
from paddle_tpu.utils import cache_dirs

# -- tolerances ---------------------------------------------------------------
# bf16 keeps 8 bits of mantissa (eps 2**-8); 12 layers of bf16 matmuls at
# hidden 768 put the run-to-run difference between two evaluation orders of
# the same logits at about 1% of their range.
#
# LOGIT_TOL: relative to max|logit| — first-token logits of the cached
# prefill vs the plain (uncached) forward, and the top-2 margin below which
# a token difference between the server and generate() counts as a near-tie
# of random weights.  A difference at a larger margin fails.
LOGIT_TOL = 2.0 ** -5
# sharded vs one-device bf16 training loss (tests/test_parallel.py's 1e-5 is
# for f32 on the CPU): relative, per step
LOSS_TOL = 2e-2
# Pallas kernel vs XLA reference (f32, highest precision) on bf16 inputs:
# (atol, rtol).  Fused BN uses its CPU test's bf16 case
# (test_pallas_fused_bn.py 5e-2/5e-2); flash attention and fused conv only
# have f32 cases on the CPU, so they take the same bf16 form here.  The two
# cached-attention reads are XLA, held to one bf16 ulp of the output.
KERNEL_TOL = {
    "flash_attention_fwd": (2e-2, 2e-2),
    "flash_attention_bwd": (5e-2, 5e-2),
    "single_block_attention_fwd": (2e-2, 2e-2),
    "single_block_attention_bwd": (5e-2, 5e-2),
    "packed_cached_attention": (4e-3, 2e-2),
    "blocked_decode_attention": (4e-3, 2e-2),
    # the kernel against the loops: the same bf16 operands and float32
    # sums, another order of the sums
    "per_row_decode_attention": (4e-3, 2e-2),
    "fused_conv_bn_relu": (5e-2, 5e-2),
    "fused_bn_relu": (5e-2, 5e-2),
}


@dataclass(frozen=True)
class Size:
    """What one run of the phases builds: FULL on the chip, TINY on the CPU."""
    name: str
    bert: BertConfig
    train_batch: int
    train_seq: int
    train_steps: int
    gpt: GPTConfig
    serve_batch_buckets: Tuple[int, ...]
    serve_seq_buckets: Tuple[int, ...]
    serve_max_new: int
    serve_max_len: int
    prompt_lens: Tuple[int, ...]
    slots: int
    attn: Tuple[int, int, int, int]            # B, N, S, H
    attn_train: Tuple[int, int, int, int]      # B, N, S, H, un-cached
    decode_heads: int                          # of H, over the packed ring
    # the step's per-row read against the loops, alone: (name, rows, query
    # heads, head_dim, queries a cached head, columns, live rows, their
    # contexts (low, high), frontier, loop trips (few, many))
    decode_rows: Tuple[tuple, ...]
    conv_x: Tuple[int, int, int, int]          # N, H, W, C (NHWC)
    conv_w: Tuple[int, int, int, int]          # O, I, kh, kw
    moe: GPTMoEConfig
    moe_batch: int
    moe_seq: int
    hybrid: dict            # over benchmark/tests/data/lfm2_tiny.json


def _moe_cfg(**kw) -> GPTMoEConfig:
    cfg = GPTMoEConfig.tiny(top_k=2, capacity_factor=1.25, **kw)
    cfg.dropout = 0.0
    return cfg


# BERT-base at batch 64 x 128, a 12 x 768 GPT decoder, a 512-wide MoE stack
# of 16 experts cut to 4 layers
FULL = Size(
    name="full", bert=BertConfig.base(), train_batch=64, train_seq=128,
    train_steps=6,
    gpt=GPTConfig(vocab_size=32000, hidden_size=768, num_layers=12,
                  num_heads=12, intermediate_size=3072,
                  max_position_embeddings=1024, dropout=0.0),
    serve_batch_buckets=(1, 4), serve_seq_buckets=(32, 128),
    serve_max_new=16, serve_max_len=256, prompt_lens=(5, 19, 32, 70, 128, 9),
    # attn_train: the benchmark's BERT-large step (16 rows of 512)
    slots=8, attn=(8, 12, 1024, 64), attn_train=(16, 16, 512, 64),
    decode_heads=25,
    # the two GPT-2 XL cells' steps (2 live rows of 32; 15 of ~350 columns)
    # and lfm2's (126 of 128 rows, 4 queries a cached head, 8,192 columns)
    decode_rows=(
        ("gpt2_xl_chat", 32, 25, 64, 1, 1024, 2, (200, 350), 500, (20, 60)),
        ("gpt2_xl_saturated", 32, 25, 64, 1, 1024, 15, (150, 550), 600,
         (20, 60)),
        ("lfm2", 128, 32, 64, 4, 8192, 126, (300, 1500), 4000, (10, 30))),
    conv_x=(32, 56, 56, 64),
    conv_w=(64, 64, 3, 3),
    moe=_moe_cfg(vocab_size=128, hidden_size=512, layers=4, heads=8,
                 seq=128, experts=16),
    moe_batch=32, moe_seq=128,
    # the served geometry in small: 2 KV heads of 64 a lane row, 4 queries a
    # KV head, a state of two 512-lane rows
    hybrid=dict(hidden_size=512, num_attention_heads=8, num_key_value_heads=2,
                vocab_size=512, intermediate_size=512,
                moe_intermediate_size=128))

TINY = Size(
    name="tiny", bert=BertConfig.tiny(seq=128), train_batch=8, train_seq=32,
    train_steps=6,
    gpt=GPTConfig.tiny(vocab_size=128, hidden_size=32, layers=2, heads=2,
                       seq=128),
    serve_batch_buckets=(1, 2), serve_seq_buckets=(8, 16), serve_max_new=4,
    serve_max_len=32, prompt_lens=(3, 7, 12, 1, 9, 5), slots=4,
    attn=(1, 2, 256, 64), attn_train=(1, 2, 128, 64), decode_heads=3,
    decode_rows=(("tiny", 4, 8, 64, 4, 256, 2, (20, 150), 200, (1, 2)),),
    conv_x=(2, 8, 8, 8),
    conv_w=(8, 8, 3, 3),
    moe=_moe_cfg(vocab_size=64, hidden_size=16, layers=2, heads=2, seq=32,
                 experts=4),
    moe_batch=8, moe_seq=16, hybrid={})


def device_record() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _emit(phase: str, t0: float, compile_s: float, checked: dict) -> dict:
    rec = {"phase": phase, "seconds": round(time.perf_counter() - t0, 3),
           "compile_seconds": round(compile_s, 3), "checked": checked}
    print(json.dumps(rec), flush=True)
    return rec


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def _platforms(tree) -> set:
    return {d.platform for leaf in jax.tree_util.tree_leaves(tree)
            for d in leaf.devices()}


# -- train --------------------------------------------------------------------

def _bert_batch(cfg: BertConfig, batch: int, seq: int, seed: int):
    """A BERT pretraining feed: fixed masked positions per row."""
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq))
    n_pred = max(2, int(seq * 0.15))
    pos = np.stack([rng.choice(seq, size=n_pred, replace=False)
                    for _ in range(batch)]).astype("int64")
    labels = np.take_along_axis(ids, pos, 1)
    return (jnp.asarray(ids), None, None, jnp.asarray(labels), None,
            jnp.asarray(pos))


def _bert_step(size: Size, mesh, seed: int, tensor_parallel: bool = False):
    paddle.seed(seed)
    model = BertForPretraining(size.bert)
    if tensor_parallel:
        apply_tensor_parallel(model)
    opt = paddle.optimizer.AdamW(parameters=model.parameters(),
                                 learning_rate=1e-4, weight_decay=0.01)
    return TrainStep(model, opt, mesh=mesh, compute_dtype=jnp.bfloat16,
                     seed=seed)


def _run_steps(step: TrainStep, feed, n: int):
    """n steps on ``feed`` = (inputs, label); returns (losses, seconds of
    the first step, seconds of the rest)."""
    t0 = time.perf_counter()
    losses = [float(step(*feed))]
    t1 = time.perf_counter()
    losses += [float(step(*feed)) for _ in range(n - 1)]
    return losses, t1 - t0, time.perf_counter() - t1


def phase_train(size: Size, seed: int = 0) -> dict:
    """BERT pretraining steps through init_mesh + TrainStep: loss finite and
    falling, state on the device."""
    t0 = time.perf_counter()
    platform = jax.devices()[0].platform
    step = _bert_step(size, init_mesh({"dp": -1}), seed)
    args = _bert_batch(size.bert, size.train_batch, size.train_seq, seed)
    losses, first_s, rest_s = _run_steps(step, (args, None), size.train_steps)
    _check(all(np.isfinite(losses)), f"train: non-finite loss {losses}")
    _check(losses[-1] < losses[0], f"train: loss did not fall {losses}")
    where = _platforms((step.state["params"], step.state["opt"]))
    _check(where == {platform},
           f"train: params/optimizer state on {where}, not {platform}")
    steady = rest_s / (size.train_steps - 1)
    # does block_until_ready fence?  One more step: after blocking on its
    # loss, fetching that loss to the host must find it already there
    t1 = time.perf_counter()
    loss = jax.block_until_ready(step(args))
    t2 = time.perf_counter()
    float(loss)
    fetch_s = time.perf_counter() - t2
    _check(fetch_s < 0.5 * (t2 - t1),
           f"train: block_until_ready returned after {t2 - t1:.4f}s but the "
           f"fetch then took {fetch_s:.4f}s — it does not fence")
    return _emit("train", t0, max(0.0, first_s - steady), {
        "model": f"bert {size.name}", "batch": size.train_batch,
        "seq": size.train_seq, "steps": size.train_steps,
        "loss_first": losses[0], "loss_last": losses[-1],
        "steady_step_seconds": round(steady, 4),
        "block_until_ready_seconds": round(t2 - t1, 4),
        "fetch_after_block_seconds": round(fetch_s, 6),
        "state_platform": sorted(where)})


# -- serve + cache ------------------------------------------------------------

def _gpt(size: Size, seed: int) -> GPTModel:
    paddle.seed(seed)
    model = GPTModel(size.gpt)
    model.eval()
    paddle.amp.decorate(models=model, level="O2", dtype="bfloat16")
    return model


def _prompts(size: Size, seed: int):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, size.gpt.vocab_size, n) for n in size.prompt_lens]


def _plain_logits(model: GPTModel, ids: np.ndarray) -> np.ndarray:
    """Next-token logits of the plain (uncached, unpadded) forward."""
    out = model(paddle.to_tensor(ids[None, :].astype(np.int64)))
    return np.asarray(out.numpy(), np.float32)[0, -1]


def _boot(model: GPTModel, size: Size, slots: int):
    """Server -> register_decode -> start(); returns (server, warm-up
    seconds, ledger events of the warm-up)."""
    set_flags({"FLAGS_decode_slots": slots})
    mark = len(ledger.compile_events())
    srv = serving.Server(serving.ServingConfig(workers=2))
    srv.register_decode("gpt", model, batch_buckets=size.serve_batch_buckets,
                        seq_buckets=size.serve_seq_buckets,
                        max_new_tokens=size.serve_max_new,
                        max_len=size.serve_max_len)
    t0 = time.perf_counter()
    srv.start()
    return srv, time.perf_counter() - t0, ledger.compile_events()[mark:]


def _serve(srv, size: Size, prompts) -> list:
    futs = [srv.submit_decode("gpt", [p], max_new_tokens=size.serve_max_new)
            for p in prompts]
    toks = [np.asarray(f.result(timeout=600)[0][0]) for f in futs]
    srv.assert_zero_steady_state_recompiles()
    return toks


def _kv_platforms(srv, oracle: Generator, size: Size, slots: int) -> set:
    """Where the KV cache lives: the slot loop's resident ring, or (scanned
    mode, one cache per request) what the prefill executable returns."""
    if slots:
        return _platforms(srv._models["gpt"]._loop._cache)
    p = size.serve_seq_buckets[0]
    ids, start = oracle.pack_prompts([np.ones((p,), np.int32)], p)
    cache, _ = oracle.prefill(ids, start,
                              oracle.cache_bucket(p, size.serve_max_new))
    return _platforms(cache)


def _tokens_agree(model, prompt, want, got, tol: float) -> int:
    """Served tokens must equal generate()'s up to the first position where
    the reference's top-2 logit margin is below ``tol`` (a near-tie of
    random weights under bf16); returns how many positions were compared
    equal.  A difference at a larger margin raises."""
    for k in range(len(want)):
        if want[k] == got[k]:
            continue
        ctx = np.concatenate([prompt, want[:k]])
        logits = _plain_logits(model, ctx)
        top2 = np.sort(logits)[-2:]
        margin = float(top2[1] - top2[0])
        bound = tol * float(np.abs(logits).max())
        _check(margin < bound,
               f"serve: token {k} differs ({got[k]} vs {want[k]}) at "
               f"top-2 margin {margin:.4g} >= {bound:.4g}")
        return k
    return len(want)


def phase_serve(size: Size, seed: int = 0) -> dict:
    """The decode server, scanned run-to-completion and slot loop, against
    Generator.generate on the same prompts.  Fills the persistent
    executable cache that phase_cache reads."""
    t0 = time.perf_counter()
    platform = jax.devices()[0].platform
    model = _gpt(size, seed)
    prompts = _prompts(size, seed + 1)
    oracle = Generator(model, seq_buckets=size.serve_seq_buckets,
                       max_len=size.serve_max_len)
    want, logit_err = [], 0.0
    for p in prompts:
        want.append(np.asarray(oracle.generate(
            p[None, :].astype(np.int64),
            max_new_tokens=size.serve_max_new).numpy())[0])
        # first-token logits: cached, left-padded, bucketed prefill vs the
        # plain forward of the same bf16 model
        bucket = oracle.prefill_bucket(len(p))
        ids, start = oracle.pack_prompts([p], bucket)
        _, logits0 = oracle.prefill(
            ids, start, oracle.cache_bucket(bucket, size.serve_max_new))
        got0 = np.asarray(logits0, np.float32)[0]
        ref0 = _plain_logits(model, p)
        _check(got0.shape == ref0.shape and np.isfinite(got0).all(),
               f"serve: first-token logits {got0.shape} not finite "
               f"{ref0.shape}")
        err = float(np.abs(got0 - ref0).max() / np.abs(ref0).max())
        _check(err < LOGIT_TOL, f"serve: first-token logits off by {err:.4g} "
                                f"of max|logit| (tolerance {LOGIT_TOL:.4g})")
        logit_err = max(logit_err, err)

    exec_dir = cache_dirs.executable_cache_dir("chip_smoke")
    shutil.rmtree(exec_dir, ignore_errors=True)   # this run stores, then loads
    snap = flags_snapshot()
    runs, compile_s = {}, 0.0
    try:
        set_flags({"FLAGS_executable_cache": "readwrite",
                   "FLAGS_executable_cache_dir": exec_dir})
        for slots in (0, size.slots):
            srv, warm_s, events = _boot(model, size, slots)
            try:
                toks = _serve(srv, size, prompts)
                kv = _kv_platforms(srv, oracle, size, slots)
            finally:
                srv.stop()
            _check(kv == {platform},
                   f"serve: KV cache on {kv}, not {platform}")
            compared = [_tokens_agree(model, p, w, g, LOGIT_TOL)
                        for p, w, g in zip(prompts, want, toks)]
            compile_s += warm_s
            runs[slots] = {"tokens": toks, "warmup_seconds": round(warm_s, 3),
                           "warmup_compiles": len(events),
                           "positions_equal": int(sum(compared)),
                           "positions": int(sum(len(w) for w in want))}
    finally:
        flags_restore(snap)
    rec = _emit("serve", t0, compile_s, {
        "model": f"gpt {size.name} bf16", "requests": len(prompts),
        "prompt_lens": list(size.prompt_lens),
        "max_new_tokens": size.serve_max_new,
        "first_token_logit_err": round(logit_err, 5),
        "logit_tolerance": LOGIT_TOL,
        "runs": {f"decode_slots={s}": {k: v for k, v in r.items()
                                       if k != "tokens"}
                 for s, r in runs.items()},
        "zero_steady_state_recompiles": True, "kv_platform": platform})
    return {"record": rec, "seed": seed, "prompts": prompts,
            "tokens": {s: r["tokens"] for s, r in runs.items()},
            "exec_dir": exec_dir}


def phase_cache(size: Size, served: dict) -> dict:
    """A second Server per decode runtime, same process, over the executable
    cache phase_serve filled: every warm-up event is a cache_load (zero
    fresh compiles) and the tokens are bit-equal to the first boot's."""
    t0 = time.perf_counter()
    snap = flags_snapshot()
    loads, load_s = 0, 0.0
    try:
        set_flags({"FLAGS_executable_cache": "readwrite",
                   "FLAGS_executable_cache_dir": served["exec_dir"]})
        # the same weights as a new process holds them: in their default
        # layouts (the first boot's slot loop relaid its own model's)
        model = _gpt(size, served["seed"])
        for slots, first in served["tokens"].items():
            srv, warm_s, events = _boot(model, size, slots)
            try:
                toks = _serve(srv, size, served["prompts"])
            finally:
                srv.stop()
            kinds = [e["kind"] for e in events]
            _check(kinds and all(k == "cache_load" for k in kinds),
                   f"cache: decode_slots={slots} warm-up compiled: {kinds}")
            for a, b in zip(first, toks):
                _check(np.array_equal(a, b),
                       f"cache: decode_slots={slots} tokens changed")
            loads += len(kinds)
            load_s += warm_s
    finally:
        flags_restore(snap)
    return _emit("cache", t0, 0.0, {
        "executables_loaded": loads, "fresh_compiles": 0,
        "second_boot_seconds": round(load_s, 3), "tokens_bit_equal": True})


def phase_layouts(size: Size, seed: int = 0) -> dict:
    """The slot step and chunk over RELAID weights (``Generator.slot_execs``:
    each weight lying the way both programs contract over it) against the
    same programs over the weights in their default layouts: last-column
    logits and tokens bit for bit.  A layout change is where a miscompile
    that only the chip shows would hide; a relaid weight holds the same
    bits, so nothing may move."""
    t0 = time.perf_counter()
    S, C, T, steps = size.slots, size.serve_max_len, 16, 8
    gens = [Generator(_gpt(size, seed), seq_buckets=size.serve_seq_buckets,
                      max_len=C) for _ in range(2)]
    plain, relaid = gens
    t1 = time.perf_counter()
    programs = [(plain.step_exec(S, C), plain.chunk_exec(S, T, C)),
                relaid.slot_execs(S, T, C)]
    compile_s = time.perf_counter() - t1
    _check(plain.weights_layout["weights_relaid"] == 0 and not plain._formats,
           "layouts: a program compiled alone relaid weights")
    n = relaid.weights_layout["weights_relaid"]
    if jax.devices()[0].platform == "tpu":
        _check(n > 0, "layouts: the chip's compiler wanted every weight as "
                      "it lay, so the comparison compares nothing")
    # every weight lies where the served programs take it: an argument in
    # another layout would be relaid by the runtime in every call
    for ex in programs[1]:
        for name, fmt in ex.input_formats[0][0].items():
            _check(fmt.layout in (None, relaid._params[name].format.layout),
                   f"layouts: {name} lies otherwise than its program takes it")
    prompt = np.asarray(_prompts(size, seed + 2)[0][:T], np.int32)
    ids = np.zeros((1, T), np.int32)
    ids[0, T - len(prompt):] = prompt
    start = np.full((S,), C, np.int32)      # rows not generating: no window
    start[0] = T - len(prompt)
    active = np.zeros((S,), bool)
    active[0] = True
    outs = []
    for gen, (step, chunk) in zip(gens, programs):
        cache = gen.init_slot_cache(S, C)
        cache, last = chunk(*gen._state_args(), cache, jnp.asarray(ids),
                            jnp.asarray(start[:1]), jnp.int32(0),
                            jnp.int32(0))
        logits = jnp.zeros((S, size.gpt.vocab_size), jnp.float32) \
            .at[0].set(last)
        finished = jnp.zeros((S,), bool)
        toks = []
        for k in range(steps):
            cache, logits, finished, tok = step(
                *gen._state_args(), cache, logits, jnp.asarray(start),
                finished, jnp.asarray(active), jnp.zeros((S,), bool),
                jnp.int32(T + k))
            toks.append(int(tok[0]))
        outs.append((np.asarray(last), toks, np.asarray(logits[0])))
    (last_p, toks_p, log_p), (last_r, toks_r, log_r) = outs
    _check(np.isfinite(last_p).all() and np.isfinite(log_p).all(),
           "layouts: non-finite logits")
    _check(np.array_equal(last_p, last_r),
           "layouts: the chunk's last-column logits moved with the layouts")
    _check(toks_p == toks_r, f"layouts: tokens moved: {toks_p} vs {toks_r}")
    _check(np.array_equal(log_p, log_r),
           "layouts: the step's logits moved with the layouts")
    return _emit("layouts", t0, compile_s, {
        "model": f"gpt {size.name} bf16", "slots": S, "cache": C, "chunk": T,
        "steps": steps, **relaid.weights_layout, "tokens": toks_r,
        "chunk_logits_bit_equal": True, "step_logits_bit_equal": True})


# -- hybrid: conv states beside K/V planes ------------------------------------

# float32 at precision "highest" on both sides: what is left is summation
# order, relative to max|logit|
HYBRID_TOL = 1e-3


def phase_hybrid(size: Size, seed: int = 0) -> dict:
    """The short-convolution / grouped-query MoE decoder at a small size
    against its plain reference (``benchmark/reference/lfm2.py``), float32
    at precision "highest": (a) one row's chunked prefill and 32 steps
    through the Generator's own chunk and step programs, logits at every
    position; (b) six requests over three slots of a ``SlotLoop`` (slots
    reused, rows waiting between their chunks while others step), every
    served token against the reference's best.  The CPU tests hold the same
    comparisons; the chip's compiler has returned other values than the
    CPU's before (PERF.md section 6, PR 25), and a state whose entries sit
    side by side with a block's tokens is where that would show."""
    import os
    from benchmark import harness
    from benchmark.models import lfm2 as models
    from benchmark.reference import lfm2 as ref
    from paddle_tpu.serving.slots import SlotLoop
    t0 = time.perf_counter()
    root = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(root, "benchmark", "configs",
                           "lfm2-8b-a1b-pp2-serve.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "benchmark", "tests", "data",
                           "lfm2_tiny.json")) as f:
        cfg.update({k: v for k, v in json.load(f)["over"].items()
                    if k != "serve"})
    cfg.update(size.hybrid, reference_pad=32)
    cfg["serve"] = {"max_new_tokens": 32}
    V, S, C, T, steps = cfg["vocab_size"], 3, 256, 16, 32
    with jax.default_matmul_precision("highest"):
        mapped = models.to_program(ref.init_weights(cfg, seed))
        model = models.build(cfg, mapped)
        view = harness.canonical_view(mapped, models.leaf_ids(cfg))
        gen = Generator(model, seq_buckets=(C,), max_len=C)
        t1 = time.perf_counter()
        step, chunk = gen.slot_execs(S, T, C)
        compile_s = time.perf_counter() - t1
        rng = np.random.default_rng(seed + 3)
        prompt = rng.integers(0, V, 70).astype(np.int32)
        n = -(-prompt.size // T)
        ids = np.zeros((n * T,), np.int32)
        ids[n * T - prompt.size:] = prompt
        start = np.full((S,), C, np.int32)
        start[1] = n * T - prompt.size
        active = np.zeros((S,), bool)
        active[1] = True
        cache = gen.init_slot_cache(S, C)
        for k in range(n):
            cache, last, _ = chunk(
                *gen._state_args(), cache,
                jnp.asarray(ids[None, k * T:(k + 1) * T]),
                jnp.asarray(start[1:2]), jnp.int32(1), jnp.int32(k * T))
        logits = jnp.zeros((S, V), jnp.float32).at[1].set(last)
        finished, got, toks = jnp.zeros((S,), bool), [np.asarray(last)], []
        for k in range(steps):
            cache, logits, finished, tok = step(
                *gen._state_args(), cache, logits, jnp.asarray(start),
                finished, jnp.asarray(active), jnp.zeros((S,), bool),
                jnp.int32(n * T + k))
            toks.append(int(tok[1]))
            got.append(np.asarray(logits[1]))
        want = np.asarray(ref.served_logits(cfg, view, prompt,
                                            np.asarray(toks, np.int32)))
        got = np.stack(got[:steps])
        _check(np.isfinite(got).all(), "hybrid: non-finite logits")
        worst = float(np.abs(got - want).max() / np.abs(want).max())
        _check(worst < HYBRID_TOL,
               f"hybrid: logits {worst:.2e} of max|logit| from the reference")
        loop = SlotLoop(gen, slots=S, cache_len=C, chunk=T)
        prompts = [rng.integers(0, V, p).astype(np.int32)
                   for p in (9, 40, 17, 70, 5, 33)]
        futs = [loop.submit(p, 12 + 4 * i) for i, p in enumerate(prompts)]
        served = [np.asarray(f.result(timeout=600)) for f in futs]
        stats = loop.stats()
        loop.close()
        gap = max(float(np.max(ref.served_gaps(cfg, view, p, t)))
                  for p, t in zip(prompts, served))
    _check(gap < HYBRID_TOL, f"hybrid: a served token lies {gap:.2e} of "
                             "max|logit| under the reference's best")
    _check(stats["state_rows_held"] > 0 and stats["plane_kinds"]
           == ["conv_state", "kv"], "hybrid: no row waited with a state")
    return _emit("hybrid", t0, compile_s, {
        "model": f"hybrid conv {size.name} f32 highest",
        "hidden": cfg["hidden_size"], "slots": S, "cache": C, "chunk": T,
        "steps": steps, "logits_rel_worst": worst, "served_gap_widest": gap,
        "state_rows_held": stats["state_rows_held"],
        "moe_assignments": stats["moe_assignments"]})


# -- latent forms: absorbed against per head ----------------------------------

# bfloat16 operands, float32 sums, two evaluation orders of one attention
# (the per-head form rounds the expanded keys and values where the absorbed
# one rounds ``W_uk^T q`` and the weighted latent): relative to max|out|
LATENT_FORM_TOL = 2.0 ** -5
# (configuration, the tiny overlay the CPU tests lay over it, a full layer)
LATENT_LAYERS = (("kimi-k2.5-ep32-serve", "kimi_tiny", 1),
                 ("dots3-note-prev-ep8-serve", "dots3_tiny", 1))
LATENT_FORMS = ("absorbed", "per_head", "per_head_fused")
# the share of a plane's numbers the kernel's program may write one step of
# the served dtype away from the XLA forms' (measured on the chip: 1-4 of
# 2,621,440 a chunk, PERF.md section 6, PR 42; float32 on the CPU: none)
LATENT_ROW_FLIPS = 1e-5


def phase_latent_forms(size: Size, seed: int = 0) -> dict:
    """One full latent-attention layer at Kimi-K2.5's width (64 heads, no
    selector) and one at dots3's (128 heads, the selector binding), random
    weights in the served dtype: a prompt prefilled in chunks as wide as
    the served chunk, each chunk run in ALL THREE cached forms from the
    same plane (absorbed, per head as an XLA loop, per head as one Pallas
    kernel); the outputs agree to ``LATENT_FORM_TOL`` at every context
    and the rows the two XLA forms write are the same to the bit, the
    kernel's program's within ``LATENT_ROW_FLIPS``.  The CPU tests
    hold the forms to each other in float32 (the kernel interpreted); what
    the chip's compilers make of either loop or of the kernel only a run
    on the chip shows (PERF.md section 6, PR 25).  ``chunk_ms`` is the
    last chunk's time in each form, to say that it ran."""
    import importlib
    import os
    from paddle_tpu.framework.functional import _bound_state
    from paddle_tpu.framework.tensor import unwrap
    from paddle_tpu.text.models.latent_moe import latent_attention_of
    t0 = time.perf_counter()
    root = os.path.dirname(os.path.abspath(__file__))
    full = size.name == "full"
    checked, compile_s = {}, 0.0
    for name, tiny, index in LATENT_LAYERS:
        with open(os.path.join(root, "benchmark", "configs",
                               name + ".json")) as f:
            cfg = json.load(f)
        if not full:
            with open(os.path.join(root, "benchmark", "tests", "data",
                                   tiny + ".json")) as f:
                over = json.load(f)["over"]
            cfg["serve"].update(over.pop("serve"), prefill_chunk=32,
                                attn_block=32)
            cfg.update(over)
        models = importlib.import_module("benchmark.models." + cfg["family"])
        paddle.seed(seed + index)
        attn = latent_attention_of(models.program_config(cfg), index)
        T = cfg["serve"]["prefill_chunk"]
        n = 8 if full else 3
        C = n * T
        for w in (attn.q_b, attn.w_uk):
            # scores of spread ~2: a softmax far from uniform
            w._value = w._value * 2
        state = attn.functional_state()
        dt = attn.q_a._value.dtype
        ring = attn.gen_ring_cache(1, C, str(dt))
        cache0 = tuple(unwrap(p) for p in ring)
        x = jax.random.normal(jax.random.key(seed), (1, C, attn.hidden),
                              jnp.float32).astype(dt)
        start = jnp.full((1,), T // 3, jnp.int32)

        def chunk_of(form):
            def chunk(params, buffers, xs, planes, pos):
                with _bound_state(attn, params, buffers):
                    out, cache = attn.forward_cached(
                        xs, type(ring)(*planes), pos, start)
                return out, tuple(unwrap(p) for p in cache)
            # the form is the layer's own rule of T; here each is forced
            attn.cached_form = lambda T, columns=None: form
            try:
                return jax.jit(chunk).lower(
                    *state, x[:, :T], cache0, jnp.int32(0)).compile()
            finally:
                del attn.cached_form
        t1 = time.perf_counter()
        forms = {f: chunk_of(f) for f in LATENT_FORMS}
        compile_s += time.perf_counter() - t1
        planes, worst, flips, ms = cache0, 0.0, 0.0, {}
        for k in range(n):
            outs = {f: ex(*state, x[:, k * T:(k + 1) * T], planes,
                          jnp.int32(k * T)) for f, ex in forms.items()}
            a = np.asarray(outs["absorbed"][0], np.float32)
            for f in LATENT_FORMS[1:]:
                b = np.asarray(outs[f][0], np.float32)
                _check(np.isfinite(a).all() and np.isfinite(b).all(),
                       f"latent_forms {name}: non-finite output at chunk "
                       f"{k} ({f})")
                worst = max(worst,
                            float(np.abs(a - b).max() / np.abs(a).max()))
                for p, q in zip(outs["absorbed"][1], outs[f][1]):
                    p, q = (np.asarray(a, np.float32) for a in (p, q))
                    off = p != q
                    flips = max(flips, float(off.mean()))
                    # the two XLA forms write the same rows to the bit; the
                    # kernel's PROGRAM rounds a few numbers in a million of
                    # the row's projection the other way (the compiler
                    # fuses it otherwise beside a custom call): one step
                    # of the served dtype, never more
                    step = 2.0 ** -7 * np.maximum(np.abs(p), np.abs(q))
                    _check(not off.any() if f != "per_head_fused"
                           else off.mean() <= LATENT_ROW_FLIPS
                           and (np.abs(p - q) <= step).all(),
                           f"latent_forms {name}: {f} wrote other rows at "
                           f"chunk {k} ({int(off.sum())} of {off.size} "
                           f"numbers, widest {np.abs(p - q).max():.3g})")
            planes = outs["absorbed"][1]
        for f, ex in forms.items():
            runs = []
            for _ in range(5):
                t1 = time.perf_counter()
                jax.block_until_ready(ex(*state, x[:, -T:], planes,
                                         jnp.int32(C - T)))
                runs.append(time.perf_counter() - t1)
            ms[f] = round(1e3 * sorted(runs)[2], 3)
        _check(worst < LATENT_FORM_TOL,
               f"latent_forms {name}: the forms lie {worst:.2e} of max|out| "
               "apart")
        checked[name] = {"heads": attn.H, "chunk": T, "context": C,
                         "selects": attn.selects, "rule": attn.cached_form(T),
                         "rel_worst": worst, "row_flips": flips,
                         "chunk_ms": ms}
    return _emit("latent_forms", t0, compile_s, checked)


# -- the gated delta rule alone --------------------------------------------------

# the chunked scan's outputs against the token-by-token recurrence in
# float32 at precision "highest", relative to max|out|: the scan's products
# that do not read the carried state take bfloat16 operands
DELTA_TOL = 2.0 ** -6
DELTA_CONFIG = ("gigachat3.5-ep16-serve", "gigachat3_5_tiny")


def _product_inverse(a):
    """``(I + A)^-1`` as ``(I - A)(I + A^2)(I + A^4)...``: exact in exact
    arithmetic, ``log2 L`` squarings and as many products; its powers of
    ``A`` grow like binomials where the keys repeat (tests/
    test_gigachat35_decoder.py), which is why the layer does not use it."""
    mm = lambda x, y: jnp.einsum("...ab,...bc->...ac", x, y,    # noqa: E731
                                 precision=jax.lax.Precision.HIGHEST)
    eye = jnp.eye(a.shape[-1], dtype=a.dtype)
    t, p, n = eye - a, a, 1
    while 2 * n < a.shape[-1]:
        p = mm(p, p)
        t, n = mm(t, eye + p), 2 * n
    return t


def _substitution_inverse(a):
    """... and by forward substitution: row ``i`` from the rows above it,
    ``L - 1`` dependent steps."""
    eye = jnp.broadcast_to(jnp.eye(a.shape[-1], dtype=a.dtype), a.shape)

    def row(i, t):
        r = jnp.einsum("...j,...jk->...k",
                       jax.lax.dynamic_index_in_dim(a, i, a.ndim - 2, False),
                       t, precision=jax.lax.Precision.HIGHEST)
        return jax.lax.dynamic_update_index_in_dim(
            t, jax.lax.dynamic_index_in_dim(t, i, a.ndim - 2, False) - r, i,
            a.ndim - 2)
    return jax.lax.fori_loop(1, a.shape[-1], row, eye)


def phase_gated_delta(size: Size, seed: int = 0) -> dict:
    """The gated delta rule ALONE at GigaChat3.5's widths (32 key heads
    under 64 value heads of 128 x 128), operands in the served dtype, the
    state float32: one prefill chunk of one row as the chunked scan, at
    scan chunks of 64 and of 128 tokens and with the triangular system
    solved by halves (the layer's), by the product of ``log2 L`` factors
    and by forward substitution, each held to the token-by-token
    recurrence (benchmark/reference/gigachat3_5.py); and one step of every
    slot as the one-token update.  Each form is timed with its state
    chained through a loop (:func:`_chained_s`), and the layer's own two against the least time
    ``benchmark/counts/gigachat3_5.py`` gives them (``roofline_pct``, from
    the chip's published peaks; none where the device has no entry)."""
    import os
    from benchmark.counts import gigachat3_5 as counts
    from benchmark.reference import gigachat3_5 as reference
    from paddle_tpu.nn.layer.gated_delta import (delta_scan, delta_update,
                                                 unit_lower_inverse)
    t0 = time.perf_counter()
    root = os.path.dirname(os.path.abspath(__file__))
    name, tiny = DELTA_CONFIG
    with open(os.path.join(root, "benchmark", "configs", name + ".json")) as f:
        cfg = json.load(f)
    full = size.name == "full"
    if not full:
        with open(os.path.join(root, "benchmark", "tests", "data",
                               tiny + ".json")) as f:
            over = json.load(f)["over"]
        cfg["serve"].update(over.pop("serve"))
        cfg.update(over)
    with open(os.path.join(root, "benchmark", "peaks.json")) as f:
        peaks = json.load(f).get(jax.devices()[0].device_kind)
    G, H = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    N, P = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    S, T = int(cfg["serve"]["slots"]), int(cfg["serve"]["prefill_chunk"])
    dt = jnp.dtype(cfg["dtype"])
    one = dict(cfg, num_hidden_layers=1, full_attention_layers=[],
               first_k_dense_replace=1)

    def least_ms(count):
        return None if not peaks else 1e3 * max(
            count["bytes"] / peaks["hbm_bytes_per_s"],
            count["flops"] / peaks["bf16_flops_per_s"])

    def operands(B, n, key):
        ks = jax.random.split(key, 5)
        unit = lambda k, scale: (                               # noqa: E731
            lambda x: (x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True))
                       * scale).astype(dt))(
            jax.random.normal(k, (B, n, G, N), jnp.float32))
        return (unit(ks[0], N ** -0.5), unit(ks[1], 1.0),
                jax.random.normal(ks[2], (B, n, H, P), jnp.float32).astype(dt),
                -0.2 * jax.random.uniform(ks[3], (B, n, H), jnp.float32),
                jax.random.uniform(ks[4], (B, n, H), jnp.float32))
    key = jax.random.key(seed)
    q, k, v, a, beta = operands(1, T, key)
    h0 = jax.random.normal(jax.random.fold_in(key, 1), (1, H, P, N),
                           jnp.float32)
    # the recurrence from a zero state, then the scans from the same
    with jax.default_matmul_precision("highest"):
        want, want_h = jax.jit(lambda q, k, v, a, b: reference.recurrence(
            jnp.repeat(q[0], H // G, 1).astype(jnp.float32),
            jnp.repeat(k[0], H // G, 1).astype(jnp.float32),
            v[0].astype(jnp.float32), jnp.exp(a[0]), b[0],
            final_state=True))(q, k, v, a, beta)
    want, want_h = np.asarray(want), np.asarray(want_h)
    widths = (64, 128) if full else (4, 8)
    forms = {"halves": unit_lower_inverse, "product": _product_inverse,
             "substitution": _substitution_inverse}
    scan_ms, worst, compile_s = {}, {}, 0.0
    for L in widths:
        for form, inverse in forms.items():
            scan = lambda h, L=L, inverse=inverse: delta_scan(   # noqa: E731
                q, k, v, a, beta, h, L, inverse)
            t1 = time.perf_counter()
            o, h = jax.jit(scan)(jnp.zeros_like(h0))
            compile_s += time.perf_counter() - t1
            tag = f"{form}/{L}"
            worst[tag] = max(
                float(np.abs(np.asarray(o)[0] - want).max()
                      / np.abs(want).max()),
                float(np.abs(np.asarray(h)[0] - want_h).max()
                      / np.abs(want_h).max()))
            _check(worst[tag] < DELTA_TOL,
                   f"gated_delta: the scan ({tag}) lies {worst[tag]:.2e} of "
                   "max|out| from the recurrence")
            scan_ms[tag] = round(1e3 * _chained_s(
                lambda h: scan(h)[1], h0, (), (3, 9)), 4)
    q1, k1, v1, a1, b1 = (t[:, 0] for t in operands(
        S, 1, jax.random.fold_in(key, 2)))
    hs = jax.random.normal(jax.random.fold_in(key, 3), (S, H, P, N),
                           jnp.float32)
    o, h = jax.jit(delta_update)(q1, k1, v1, a1, b1, hs)
    _check(np.isfinite(np.asarray(o)).all(), "gated_delta: update not finite")
    update_ms = round(1e3 * _chained_s(
        lambda h: delta_update(q1, k1, v1, a1, b1, h)[1], hs, (), (3, 9)), 4)
    kept = f"halves/{int(cfg.get('linear_scan_chunk', widths[0]))}"
    least = {"update": least_ms(counts.linear_update(one, S)),
             "scan": least_ms(counts.linear_scan(one, T))}
    share = lambda least, ms: None if least is None or not ms \
        else round(100.0 * least / ms, 2)                       # noqa: E731
    return _emit("gated_delta", t0, compile_s, {
        "heads": [G, H], "dims": [N, P], "rows": S, "chunk": T,
        "scan_ms": scan_ms, "scan_rel_worst": worst, "update_ms": update_ms,
        "kept": kept,
        "roofline_pct": {"update": share(least["update"], update_ms),
                         "scan": share(least["scan"], scan_ms.get(kept))}})


# -- kernels ------------------------------------------------------------------

def _close(name: str, got, ref) -> float:
    atol, rtol = KERNEL_TOL[name]
    worst = 0.0
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        g, r = np.asarray(g, np.float32), np.asarray(r, np.float32)
        _check(g.shape == r.shape and np.isfinite(g).all(),
               f"kernels: {name} shape {g.shape} vs {r.shape} / non-finite")
        err = np.abs(g - r) - (atol + rtol * np.abs(r))
        worst = max(worst, float(np.abs(g - r).max()))
        _check(bool((err <= 0).all()),
               f"kernels: {name} off by {float(np.abs(g - r).max()):.4g} "
               f"(atol {atol}, rtol {rtol})")
    return worst


def _run_kernel(name: str, fn, ref_fn, args, compiled_expected: bool):
    """AOT-compile ``fn`` at ``args``, require the Mosaic custom call in the
    lowered program, run it, compare with ``ref_fn`` (f32, highest matmul
    precision).  Returns (compile seconds, max abs error)."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    if compiled_expected:
        _check("tpu_custom_call" in compiled.as_text(),
               f"kernels: {name} lowered without tpu_custom_call "
               "(interpret mode or reference fallback)")
    got = jax.block_until_ready(compiled(*args))
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(ref_fn)(*args)
    return compile_s, _close(name, got, ref)


def _attention_ref(q, k, v, causal=True):
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    s = jnp.einsum("bnsh,bnth->bnst", q, k) / np.sqrt(q.shape[-1])
    if causal:
        s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, -1e30)
    return jnp.einsum("bnst,bnth->bnsh", jax.nn.softmax(s, axis=-1), v)


def decode_attention_reference(q, k, v, start, end):
    """The one-expression masked attention in float32 that the served
    decode reads are held to: ``(B, N, 1, H)`` queries over UNPACKED
    ``(B, N, S, H)`` planes, row ``b`` seeing the columns ``[start[b],
    end[b])``."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    s = jnp.einsum("bnsh,bnth->bnst", q, k) / np.sqrt(q.shape[-1])
    col = jnp.arange(k.shape[2])
    valid = (col >= start[:, None]) & (col < end[:, None])
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    return jnp.einsum("bnst,bnth->bnsh", jax.nn.softmax(s, axis=-1), v)


def _attention_ref_bse(q, k, v, heads):
    """The plain attention, not causal, of ``[B, S, N*H]`` operands."""
    def split(x):
        return x.reshape(x.shape[:2] + (heads, -1)).transpose(0, 2, 1, 3)
    out = _attention_ref(split(q), split(k), split(v), causal=False)
    return out.transpose(0, 2, 1, 3).reshape(q.shape)


def _conv_bn_relu_ref(x, w, gamma, beta):
    xf, wf = x.astype(jnp.float32), w.astype(jnp.float32)
    y = jax.lax.conv_general_dilated(
        xf, jnp.transpose(wf, (2, 3, 1, 0)), (1, 1), [(1, 1), (1, 1)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return _bn_relu_ref(y, gamma, beta)


def _bn_relu_ref(x, gamma, beta):
    xf = x.astype(jnp.float32)
    axes = tuple(range(xf.ndim - 1))
    mean, var = xf.mean(axes), xf.var(axes)
    y = (xf - mean) * jax.lax.rsqrt(var + 1e-5) * gamma + beta
    return jnp.maximum(y, 0.0), mean, var


def _chained_s(body, x, rest, trips):
    """Seconds a trip of ``x = body(x, *rest)`` under ``lax.fori_loop``
    (nothing is hoisted or dropped): two trip counts are timed, the best of
    three each, and their difference leaves the dispatch out."""
    def best(n):
        @jax.jit
        def run(x, *rest):
            return jax.lax.fori_loop(0, n, lambda _, x: body(x, *rest), x)
        jax.block_until_ready(run(x, *rest))
        took = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(run(x, *rest))
            took.append(time.perf_counter() - t0)
        return min(took)
    few, many = trips
    return (best(many) - best(few)) / (many - few)


def _chained_us(fn, q, rest, trips):
    """Microseconds a call of ``fn(q, *rest)``: the output is fed back into
    the queries (:func:`_chained_s`)."""
    return 1e6 * _chained_s(
        lambda q, *rest: (q + fn(q, *rest) * 1e-3).astype(q.dtype), q, rest,
        trips)


def _decode_rows_case(case, rng) -> dict:
    from paddle_tpu.nn.functional.attention import (_decode_rows_fn,
                                                    _decode_span_fn,
                                                    decode_block)
    from paddle_tpu.nn.layer.transformer import kv_heads_per_lane_row
    name, rows, heads, hd, rep, cols, live, ctx, pos, trips = case
    g = kv_heads_per_lane_row(hd)
    groups = -(-(heads // rep) // g)
    block = decode_block(cols)
    q = jnp.asarray(rng.randn(rows, heads, 1, hd), jnp.bfloat16)
    k, v = (jnp.asarray(rng.randn(rows, groups, cols, g * hd), jnp.bfloat16)
            for _ in range(2))
    start = np.full(rows, cols)
    at = rng.permutation(rows)[:live]
    start[at] = pos + 1 - rng.randint(ctx[0], ctx[1] + 1, live)
    end = jnp.full((rows,), pos + 1, jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    forms = {"loops": lambda *a: _decode_span_fn(*a, block=block, rep=rep),
             "kernel": lambda *a: _decode_rows_fn(*a, block=block, rep=rep)}
    out = {f: jax.block_until_ready(fn(q, k, v, start, end))
           for f, fn in forms.items()}
    _check(bool(np.isfinite(np.asarray(out["kernel"], np.float32)).all()),
           f"kernels: per_row_decode_attention {name} non-finite")
    own = np.asarray(start)[at]
    blocks = -(-cols // block)
    rec = {"rows": rows, "live": live, "lane_rows": groups, "block": block,
           "max_abs_diff_loops": round(_close(
               "per_row_decode_attention", out["kernel"][at],
               out["loops"][at]), 6),
           "blocks_read_pct": {
               "loops": round(100.0 * (pos // block + 1 - own.min() // block)
                              / blocks, 2),
               "kernel": round(100.0 * int((pos // block + 1
                                            - own // block).sum())
                               / (rows * blocks), 2)},
           "tolerance": list(KERNEL_TOL["per_row_decode_attention"])}
    rec["us_a_call"] = {f: round(_chained_us(fn, q, (k, v, start, end),
                                             trips), 1)
                        for f, fn in forms.items()}
    return rec


def phase_kernels(size: Size, seed: int = 0) -> dict:
    """Every Pallas kernel a default or a flag can reach, compiled (not
    interpreted) once at a main-path shape, against its XLA reference."""
    from paddle_tpu.ops.pallas import (flash_attention_fn, fused_bn,
                                       fused_conv, packed_attention_fn)
    t0 = time.perf_counter()
    on_chip = jax.default_backend() == "tpu"
    rng = np.random.RandomState(seed)
    bf = jnp.bfloat16

    def rand(shape, dtype=bf, scale=1.0):
        return jnp.asarray(rng.randn(*shape) * scale, dtype)

    B, N, S, H = size.attn
    q, k, v = rand((B, N, S, H)), rand((B, N, S, H)), rand((B, N, S, H))
    q1 = rand((B, N, 1, H))
    # the un-cached training shape, heads side by side as the projections
    # write them (the form the dispatch gives BERT's step)
    Bt, Nt, St, Ht = size.attn_train
    qt, kt, vt = (rand((Bt, St, Nt * Ht)) for _ in range(3))

    def packed(q, k, v):
        return packed_attention_fn(q, k, v, Nt)

    def packed_ref(q, k, v):
        return _attention_ref_bse(q, k, v, Nt)
    # a left-padded ring: each row's valid window starts somewhere else
    start = jnp.asarray(rng.randint(0, S // 2, (B,)), jnp.int32)
    end = jnp.asarray(rng.randint(S // 2 + 1, S + 1, (B,)), jnp.int32)
    x, w = rand(size.conv_x), rand(size.conv_w, scale=0.1)
    cout = size.conv_w[0]
    gamma = jnp.asarray(1.0 + 0.1 * rng.randn(cout), jnp.float32)
    beta = jnp.asarray(0.1 * rng.randn(cout), jnp.float32)
    x2d = rand((int(np.prod(size.conv_x[:3])), size.conv_x[3]))
    gamma2, beta2 = gamma[:x2d.shape[1]], beta[:x2d.shape[1]]

    def loss(fn):
        return lambda *a: fn(*a).astype(jnp.float32).sum()

    cases = {
        "flash_attention_fwd": (
            lambda q, k, v: flash_attention_fn(q, k, v, causal=True),
            _attention_ref, (q, k, v)),
        "flash_attention_bwd": (
            jax.grad(loss(lambda q, k, v: flash_attention_fn(
                q, k, v, causal=True)), argnums=(0, 1, 2)),
            jax.grad(loss(_attention_ref), argnums=(0, 1, 2)), (q, k, v)),
        "single_block_attention_fwd": (packed, packed_ref, (qt, kt, vt)),
        "single_block_attention_bwd": (
            jax.grad(loss(packed), argnums=(0, 1, 2)),
            jax.grad(loss(packed_ref), argnums=(0, 1, 2)), (qt, kt, vt)),
        "fused_conv_bn_relu": (
            lambda x, w, g, b: fused_conv.fused_conv_bn_act(
                x, w, g, b, 1, 1, 1e-5, True),
            _conv_bn_relu_ref, (x, w, gamma, beta)),
        "fused_bn_relu": (
            lambda x, g, b: fused_bn.fused_bn_act(x, g, b, 1e-5, True),
            _bn_relu_ref, (x2d, gamma2, beta2)),
    }
    checked, compile_s = {}, 0.0
    for name, (fn, ref_fn, args) in cases.items():
        c_s, err = _run_kernel(name, fn, ref_fn, args, on_chip)
        compile_s += c_s
        checked[name] = {"compile_seconds": round(c_s, 3),
                         "max_abs_err": round(err, 6),
                         "tolerance": list(KERNEL_TOL[name])}
    # not a Pallas kernel, but chip-only all the same: the XLA attention
    # over PACKED ring planes (two heads of 64 per lane row, an odd head
    # count).  The chip's compiler once returned other values than the
    # CPU's for it (a concatenate of lane-offset slices; PERF.md, PR 25),
    # and "slot loop equals generate()" cannot see that: both are packed
    from paddle_tpu.nn.functional.attention import _sdpa_packed_fn
    from paddle_tpu.nn.layer.transformer import (kv_heads_per_lane_row,
                                                 pack_heads)
    odd = max(1, N - 1)
    g = kv_heads_per_lane_row(H)
    col = jnp.arange(S)
    window = (col >= start[:, None]) & (col < end[:, None])
    mask = jnp.where(window, 0.0, -1e30).astype(jnp.float32)[:, None, None]
    got = jax.block_until_ready(jax.jit(_sdpa_packed_fn)(
        q1[:, :odd], pack_heads(k[:, :odd], g), pack_heads(v[:, :odd], g),
        mask))
    with jax.default_matmul_precision("highest"):
        ref = decode_attention_reference(
            q1[:, :odd], k[:, :odd], v[:, :odd], start, end)
    checked["packed_cached_attention"] = {
        "heads_per_lane_row": g,
        "max_abs_err": round(_close("packed_cached_attention", got, ref), 6),
        "tolerance": list(KERNEL_TOL["packed_cached_attention"])}
    # the decode step's form of it (ISSUE 28): the same planes read in
    # column blocks over the live span under a traced trip count, at the
    # served head geometry (25 heads of 64: 13 lane rows, one half
    # empty), the frontier inside the third block, rows that start in
    # unlike blocks and one that is not generating (start = S)
    from paddle_tpu.nn.functional.attention import (_decode_span_fn,
                                                    decode_block)
    heads, rows = size.decode_heads, 4
    qd = rand((rows, heads, 1, H))
    kd, vd = rand((rows, heads, S, H)), rand((rows, heads, S, H))
    block = decode_block(S)
    pos = min(2 * block + block // 2, S - 1)
    dstart = jnp.asarray([max(pos - block - 5, 0), S, pos // 2, pos],
                         jnp.int32)
    dend = jnp.full((rows,), pos + 1, jnp.int32)
    live = np.asarray(dstart) <= pos
    kp, vp = pack_heads(kd, g), pack_heads(vd, g)
    got = jax.block_until_ready(_decode_span_fn(
        qd, kp, vp, dstart, dend, block=block))
    _check(bool(np.isfinite(np.asarray(got, np.float32)).all()),
           "kernels: blocked_decode_attention non-finite on a dead row")
    dmask = jnp.where((col >= dstart[:, None]) & (col < dend[:, None]),
                      0.0, -1e30).astype(jnp.float32)[:, None, None]
    whole = jax.jit(_sdpa_packed_fn)(qd, kp, vp, dmask)
    with jax.default_matmul_precision("highest"):
        ref = decode_attention_reference(qd, kd, vd, dstart, dend)
    checked["blocked_decode_attention"] = {
        "heads": heads, "block": block, "frontier": pos,
        "max_abs_err": round(_close("blocked_decode_attention",
                                    got[live], ref[live]), 6),
        "max_abs_diff_one_expression": round(float(jnp.abs(
            got[live].astype(jnp.float32)
            - whole[live].astype(jnp.float32)).max()), 6),
        "tolerance": list(KERNEL_TOL["blocked_decode_attention"])}
    # the step's read of each generating row's own blocks in ONE kernel
    # (ISSUE 47; ops/pallas/span_decode.py), alone against those loops at
    # the cells' shapes: the outputs agree on the live rows, a dead row
    # reads finite values, and what a call costs in either form
    checked["per_row_decode_attention"] = {
        case[0]: _decode_rows_case(case, rng) for case in size.decode_rows}
    checked["compiled_not_interpreted"] = on_chip
    checked["shapes"] = {"attention": list(size.attn),
                         "conv_x": list(size.conv_x),
                         "conv_w": list(size.conv_w)}
    return _emit("kernels", t0, compile_s, checked)


# -- multichip ----------------------------------------------------------------

def _shard_census(params: dict, mesh) -> dict:
    """Every parameter lives on all of the mesh's devices; one whose spec
    names a mesh axis is really cut along it."""
    n_dev = mesh.devices.size
    sharded = 0
    for name, arr in params.items():
        devs = {s.device for s in arr.addressable_shards}
        _check(len(devs) == n_dev,
               f"multichip: {name} lives on {len(devs)} of {n_dev} devices")
        want = int(np.prod([mesh.shape[a] for entry in arr.sharding.spec
                            if entry is not None
                            for a in ((entry,) if isinstance(entry, str)
                                      else entry)]))
        pieces = {tuple((sl.start, sl.stop) for sl in s.index)
                  for s in arr.addressable_shards}
        _check(len(pieces) == want,
               f"multichip: {name} spec {arr.sharding.spec} has "
               f"{len(pieces)} distinct shards, expected {want}")
        sharded += want > 1
    return {"params": len(params), "params_sharded": sharded,
            "devices": n_dev}


def phase_multichip_train(size: Size, devices: Sequence, seed: int = 0
                          ) -> dict:
    """The train phase's BERT TrainStep on a dp=2 x mp=2 mesh (tensor-parallel
    layout as __graft_entry__'s dry run applies it) against the same steps
    from the same seed on one device of the same process."""
    t0 = time.perf_counter()
    _check(len(devices) == 4, f"multichip: need 4 devices, got {len(devices)}")
    args = _bert_batch(size.bert, size.train_batch, size.train_seq, seed)
    one = _bert_step(size, make_mesh({"dp": 1}, devices=devices[:1]), seed)
    ref, _, _ = _run_steps(one, (args, None), size.train_steps)
    del one
    mesh = init_mesh({"dp": 2, "mp": 2}, devices=devices)
    step = _bert_step(size, mesh, seed, tensor_parallel=True)
    losses, first_s, rest_s = _run_steps(step, (args, None), size.train_steps)
    _check(all(np.isfinite(losses)) and losses[-1] < losses[0],
           f"multichip: sharded loss not finite/falling {losses}")
    for i, (a, b) in enumerate(zip(losses, ref)):
        _check(abs(a - b) <= LOSS_TOL * abs(b),
               f"multichip: step {i} loss {a} vs one-device {b} "
               f"(tolerance {LOSS_TOL} relative)")
    census = _shard_census(step.state["params"], mesh)
    _check(census["params_sharded"] > 0, "multichip: no parameter is sharded")
    steady = rest_s / (size.train_steps - 1)
    return _emit("multichip_train", t0, max(0.0, first_s - steady), {
        "model": f"bert {size.name}", "mesh": {"dp": 2, "mp": 2},
        "steps": size.train_steps, "losses": losses,
        "losses_one_device": ref, "loss_tolerance": LOSS_TOL, **census})


def phase_multichip_moe(size: Size, devices: Sequence, seed: int = 0) -> dict:
    """GPTMoEModel TrainStep with ep=4 (ops/routing.py's shard_map
    all-to-all) against the dense-dispatch control the MoE tests use: the
    loss of the first step, and the loss of a second step that has the
    first one's update in it (whether it falls is not asked: at lr 1e-3
    this stack's first AdamW step overshoots, in both dispatches alike).
    (Parameters are not compared one by one:
    AdamW's first step moves a weight by lr * sign(grad), so on the chip
    the matmul rounding flips near-zero gradients by 2 * lr either way;
    the CPU tests' bit-equality is an f32 property.)"""
    t0 = time.perf_counter()
    mesh = make_mesh({"ep": len(devices)}, devices=devices)
    ids = jnp.asarray(np.random.RandomState(seed).randint(
        0, size.moe.vocab_size, (size.moe_batch, size.moe_seq)))
    losses, first_s, steps = {}, {}, {}
    for dispatch in ("routed", "dense"):
        paddle.seed(seed)
        model = GPTMoEModel(size.moe, mesh=mesh, dispatch=dispatch,
                            annotate=(dispatch == "routed"))
        opt = paddle.optimizer.AdamW(parameters=model.parameters(),
                                     learning_rate=1e-3)
        steps[dispatch] = TrainStep(model, opt, mesh=mesh, seed=seed)
        losses[dispatch], first_s[dispatch], _ = _run_steps(
            steps[dispatch], ((ids, ids), None), 2)
    for i, (a, b) in enumerate(zip(losses["routed"], losses["dense"])):
        _check(np.isfinite(a) and abs(a - b) <= LOSS_TOL * abs(b),
               f"multichip: MoE step {i} routed loss {a} vs dense "
               f"control {b} (tolerance {LOSS_TOL} relative)")
    params = steps["routed"].state["params"]
    stack = next(n for n in params if n.endswith("experts.w1"))
    held = {s.device for s in params[stack].addressable_shards}
    _check(len(held) == len(devices),
           f"multichip: expert stack on {len(held)} devices")
    return _emit("multichip_moe", t0, first_s["routed"], {
        "model": "gpt_moe", "mesh": {"ep": len(devices)},
        "losses_routed": losses["routed"], "losses_dense": losses["dense"],
        "loss_tolerance": LOSS_TOL, "expert_stack_devices": len(held)})


# -- entry --------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="run only the four-chip sharded paths and what "
                         "they are compared with (no one-chip phase)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    device = device_record()
    if device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {device} — no phase was "
              "run (the CPU rehearsal is tests/test_chip_smoke.py)",
              file=sys.stderr)
        return 1
    cache_dirs.enable_jax_compile_cache()
    if args.multichip:
        if device["count"] != 4:
            print(f"chip_smoke: --multichip needs 4 chips, JAX found "
                  f"{device['count']}", file=sys.stderr)
            return 1
        phase_multichip_train(FULL, jax.devices(), args.seed)
        phase_multichip_moe(FULL, jax.devices(), args.seed)
    else:
        phase_train(FULL, args.seed)
        served = phase_serve(FULL, args.seed)
        phase_layouts(FULL, args.seed)
        phase_hybrid(FULL, args.seed)
        phase_latent_forms(FULL, args.seed)
        phase_gated_delta(FULL, args.seed)
        phase_kernels(FULL, args.seed)
        phase_cache(FULL, served)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
