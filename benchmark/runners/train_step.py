"""Runner ``train_step``: a model family trained through
``init_mesh(<the configuration's train.mesh>)`` + ``parallel.TrainStep``.
The family's ``benchmark/models/<family>.py`` gives the model, the names
of its leaves and the feed that one batch of the traffic becomes.

Set-up builds ONE compiled step with its state, drives it from the seed
through its first three steps — through the window's own call and feed —
and hands that same object to the window.  The plain float32 reference
(``benchmark/reference/<family>.py``) follows the same three steps first,
before the program's state exists, and its seconds are not part of
``setup_s``.  Compared: each step's loss, the first gradient's norm by the
worst leaf (worked out from Adam's first moment after one step) and the
norm of the parameters' change after the three, by the worst leaf.
"""
from __future__ import annotations

import gc
import importlib
import json
import math
import time

import jax
import jax.numpy as jnp

from benchmark import harness
from benchmark.reference.common import (flatten_norms, flatten_sketches,
                                        sketch_salt, sketches)

CHECKED_STEPS = 3


@jax.jit
def _norms(tree):
    return {n: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
            for n, a in tree.items()}


def _delta_norms(params, start, to_program):
    """Per-leaf norms of ``params`` - ``start``; ``start`` is the canonical
    tree, sliced onto the program's leaves inside the program, so no second
    copy of it is made."""
    @jax.jit
    def f(params, start):
        mapped = to_program(start)
        return {n: jnp.sqrt(jnp.sum(jnp.square(
            params[n].astype(jnp.float32) - mapped[n].astype(jnp.float32))))
            for n in params}
    return f(params, start)


def _sketches(named: dict, ids: dict, seed: int, scale: float) -> dict:
    """Sketches of the program's leaves, viewed in the canonical layout."""
    sk = jax.jit(sketches)(harness.canonical_view(named, ids),
                           sketch_salt(seed))
    return {k: v * scale for k, v in flatten_sketches(sk).items()}


def _host(norms: dict, ids: dict, scale: float = 1.0) -> dict:
    return {ids[n]: float(v) * scale for n, v in norms.items()}


def run(cell: dict, seed: int, seconds: float, trace: bool,
        t_process_start: float) -> dict:
    cfg, traffic = cell["config"], cell["traffic"]
    family = cfg["family"]
    ref = importlib.import_module(f"benchmark.reference.{family}")
    models = importlib.import_module(f"benchmark.models.{family}")
    gen = importlib.import_module(f"benchmark.generators.{traffic['kind']}")
    tr = cfg["train"]
    checks = harness.Checks()

    phases = harness.Phases(t_process_start)
    pool = gen.make(traffic, cfg, seed)
    phases.mark("imports_and_batches")

    # -- the reference follows the first three steps (not set-up) ----------
    t_ref = time.monotonic()
    with jax.default_matmul_precision("highest"):
        ref_losses, (ref_g, ref_sk), ref_d = ref.train_steps(
            cfg, seed, pool[:CHECKED_STEPS],
            rows_per_block=cfg["reference"]["rows_per_block"],
            sketch_seed=seed)
        ref_g, ref_d = flatten_norms(ref_g), flatten_norms(ref_d)
        ref_sk = flatten_sketches(ref_sk)
    gc.collect()
    reference_s = time.monotonic() - t_ref
    phases.mark("reference(not set-up)")

    # -- the program: one TrainStep, built once -----------------------------
    import paddle_tpu as paddle
    from paddle_tpu.parallel import TrainStep, init_mesh
    from paddle_tpu.profiler import ledger

    w0 = ref.init_weights(cfg, seed)
    jax.block_until_ready(w0)
    phases.mark("weights")
    model = models.build(cfg, models.to_program(w0))
    del w0
    phases.mark("build_model")
    ids = models.leaf_ids(cfg)
    opt = paddle.optimizer.AdamW(
        parameters=model.parameters(), learning_rate=tr["learning_rate"],
        weight_decay=tr["weight_decay"], beta1=tr["beta1"], beta2=tr["beta2"],
        epsilon=tr["epsilon"])
    step = TrainStep(model, opt, mesh=init_mesh(dict(tr["mesh"])),
                     compute_dtype=jnp.dtype(cfg["dtype"]),
                     remat=tr["remat"], accumulate_steps=tr["accumulate_steps"],
                     seed=0)     # TrainStep bakes its seed into the program:
    #                              a seed per run would compile per run

    losses = []
    grad_norms = None
    for k in range(CHECKED_STEPS):
        with harness.span("train_step_call"):
            loss = step(models.feed(pool[k]))
        losses.append(float(loss))
        if k == 0:
            phases.mark("first_step(compile_or_load)")
            grad_norms = _host(_norms(step.state["opt"]["moment1"]), ids,
                               1.0 / (1.0 - tr["beta1"]))
            grad_sk = _sketches(step.state["opt"]["moment1"], ids, seed,
                                1.0 / (1.0 - tr["beta1"]))
    start = ref.init_weights(cfg, seed)
    delta_norms = _host(_delta_norms(step.state["params"], start,
                                     models.to_program), ids)
    del start
    gc.collect()
    phases.mark("checked_steps")

    lim = cfg["limits"]
    for k in range(CHECKED_STEPS):
        checks.add(f"loss_rel_step{k + 1}",
                   abs(losses[k] - ref_losses[k]) / abs(ref_losses[k]),
                   lim["loss_rel"])
    gap, leaf = harness.worst_leaf_gap(grad_norms, ref_g)
    checks.add("grad_norm_rel_worst_leaf", gap, lim["grad_norm_rel"], note=leaf)
    checks.add("grad_diff_rel", harness.sketch_difference(grad_sk, ref_sk, ref_g),
               lim["grad_diff_rel"])
    # a leaf whose true gradient is zero (the key bias: softmax does not see
    # it) is moved by Adam along rounding noise, in any precision; it takes
    # no part.  A leaf with no gradient at all (weight decay alone) does.
    floor = lim["delta_leaf_grad_floor_rel"] * sorted(ref_g.values())[len(ref_g) // 2]
    moved = [k for k, g in ref_g.items() if g == 0.0 or g >= floor]
    gap, leaf = harness.worst_leaf_gap(delta_norms, ref_d, moved)
    checks.add("delta_norm_rel_worst_leaf", gap, lim["delta_norm_rel"],
               note=f"{leaf}; {len(moved)} of {len(ref_g)} leaves")

    # -- the window ---------------------------------------------------------
    B, s = tr["batch"], tr["seq"]
    every = int(traffic["fetch_loss_every"])
    n_events = len(ledger.compile_events())
    phases.report()
    t_start = time.monotonic()
    setup_s = t_start - t_process_start - reference_s
    prof = harness.ProfilerSlice(trace, cell["bench_dir"], t_start, seconds,
                                 float(traffic.get("trace_slice_s", 3.0)))
    t_end = t_start + seconds
    steps, call_s, fetched = 0, 0.0, []
    longest = {"train_step_call": 0.0, "fetch_loss": 0.0}
    while True:
        now = time.monotonic()
        if now >= t_end:
            break
        with harness.span("make_batch"):
            feed = models.feed(pool[steps % len(pool)])
        with harness.span("train_step_call"):
            t0 = time.perf_counter()
            loss = step(feed)
            dt = time.perf_counter() - t0
            call_s += dt
            longest["train_step_call"] = max(longest["train_step_call"], dt)
        steps += 1
        if steps % every == 0:
            with harness.span("fetch_loss"):
                t0 = time.perf_counter()
                fetched.append(float(loss))
                longest["fetch_loss"] = max(longest["fetch_loss"],
                                            time.perf_counter() - t0)
    jax.block_until_ready(loss._value)
    t_stop = time.monotonic()
    memory = harness.device_bytes_now()
    prof.stop()
    window_s = t_stop - t_start
    fetched.append(float(loss))
    # a stall of the host or of the device shows as one long call or fetch
    print(f"window: {steps} steps in {window_s:.3f} s, the last fence "
          f"{t_stop - now:.3f} s; longest call "
          f"{longest['train_step_call']:.3f} s, longest fetch "
          f"{longest['fetch_loss']:.3f} s", flush=True)
    compiles = len(ledger.compile_events()) - n_events

    nonfinite = sum(1 for x in fetched if not math.isfinite(x))
    checks.add("window_nonfinite_losses", nonfinite, 0)

    return {
        "correct": checks.correct, "attempted": steps, "failed": nonfinite,
        "checks": checks.rows, "reference_s": reference_s,
        "setup_phases_s": phases.seconds,
        "memory_at_close_bytes": memory,
        "end_to_end": {"train_tokens_per_s": steps * B * s / window_s,
                       "setup_s": setup_s},
        "trace_dir": prof.dir,
        "span_names": harness.SPAN_NAMES,
        "ctx": {
            "config": cfg, "traffic": traffic, "family": family,
            "window": {"seconds": window_s, "steps": steps,
                       "tokens": steps * B * s},
            "counters": {"train_step_call_s": call_s,
                         "steady_compiles": compiles},
            "programs": {"step": "jit_step"},
        },
    }


def control(cell: dict, seeds, seconds: float = 0.0) -> list:
    """The control's readings, one row per seed: the reference computed in
    the nearest precision below the configuration's, put in the program's
    place and compared with the float32 reference exactly as a run compares
    the program.  No program and no window."""
    from benchmark.reference.common import CONTROL_PRECISION
    cfg, traffic = cell["config"], cell["traffic"]
    ref = importlib.import_module(f"benchmark.reference.{cfg['family']}")
    gen = importlib.import_module(f"benchmark.generators.{traffic['kind']}")
    low = CONTROL_PRECISION[cfg["dtype"]]
    rpb = cfg["reference"]["rows_per_block"]
    lim = cfg["limits"]
    rows = []
    for seed in seeds:
        pool = gen.make(traffic, cfg, seed)[:CHECKED_STEPS]
        with jax.default_matmul_precision("highest"):
            want = ref.train_steps(cfg, seed, pool, rows_per_block=rpb,
                                   sketch_seed=seed)
            got = ref.train_steps(cfg, seed, pool, precision=low,
                                  rows_per_block=rpb, sketch_seed=seed)
        ref_g, ref_d = flatten_norms(want[1][0]), flatten_norms(want[2])
        floor = lim["delta_leaf_grad_floor_rel"] * sorted(
            ref_g.values())[len(ref_g) // 2]
        moved = [k for k, g in ref_g.items() if g == 0.0 or g >= floor]
        row = {"seed": seed, "precision": low}
        for k in range(CHECKED_STEPS):
            row[f"loss_rel_step{k + 1}"] = abs(got[0][k] - want[0][k]) / abs(want[0][k])
        row["grad_norm_rel_worst_leaf"], row["grad_leaf"] = \
            harness.worst_leaf_gap(flatten_norms(got[1][0]), ref_g)
        row["grad_diff_rel"] = harness.sketch_difference(
            flatten_sketches(got[1][1]), flatten_sketches(want[1][1]), ref_g)
        row["delta_norm_rel_worst_leaf"], row["delta_leaf"] = \
            harness.worst_leaf_gap(flatten_norms(got[2]), ref_d, moved)
        rows.append(row)
        print("CONTROL " + json.dumps(row), flush=True)
    return rows
