#!/usr/bin/env python
"""Fault drill: exercise the fault-tolerant runtime end to end on a CPU mesh.

Runs the four fault kinds the deterministic harness
(``paddle_tpu.testing.faults``) can inject — rank kill, NaN gradients,
store connection drops, slow ranks — against the subsystems built to
survive them, and emits one JSON line per scenario::

    python tools/fault_drill.py --dry                # all scenarios
    python tools/fault_drill.py --dry nan_sentinel   # one scenario

Scenarios:

``torn_checkpoint``  interrupt/corrupt saves; the loader must fall back
                     to the previous complete step and an uncommitted
                     save must stay invisible (manifest = atomicity).
``nan_sentinel``     inject a NaN gradient in-graph; the numerics
                     sentinel must skip the step (params untouched),
                     back off the GradScaler, and keep training.
``store_drop``       sever the TCPStore connection mid-traffic; client
                     ops must retry/reconnect and ``add`` must not
                     double-count.
``slow_step``        a ``slow`` clause must stall the step hook
                     deterministically (the straggler the heartbeat
                     watchdog exists for).
``kill_resume``      SIGKILL a worker mid-run under ElasticLaunch; the
                     restarted gang must resume from the newest complete
                     checkpoint and finish with params identical to an
                     uninterrupted run.

``--dry`` keeps every scenario at toy scale (tier-1 CPU semantics); there
is currently no chip-scale wet mode, the flag exists for CLI symmetry and
future growth.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _emit(record):
    sys.stdout.write(json.dumps(record) + "\n")
    sys.stdout.flush()


# ---------------------------------------------------------------------------
def drill_torn_checkpoint(work):
    from paddle_tpu.checkpoint import CheckpointManager, complete_steps
    import numpy as np
    root = os.path.join(work, "ckpt_torn")
    m = CheckpointManager(root, keep=0)
    for s in (1, 2, 3):
        m.save(s, {"params": {"w": np.full((4,), float(s), np.float32)}})
    # tear the newest: corrupt its payload in place (manifest + size kept,
    # so only the checksum can catch it)
    step3 = os.path.join(root, "step_00000003")
    payload = [f for f in os.listdir(step3) if f.endswith(".pdparams")][0]
    with open(os.path.join(step3, payload), "r+b") as f:
        f.seek(10)
        f.write(b"\xde\xad\xbe\xef")
    step, state = m.load()
    fell_back = step == 2 and float(state["params"]["w"][0]) == 2.0
    # an interrupted save (payload written, manifest never committed)
    # must not be visible at all
    m4 = CheckpointManager(os.path.join(work, "ckpt_partial"), keep=0)
    m4.save(7, {"params": {"w": np.zeros(2, np.float32)}})
    os.remove(os.path.join(m4.root, "step_00000007", "MANIFEST.json"))
    invisible = complete_steps(m4.root) == []
    return {"ok": bool(fell_back and invisible), "fallback_step": step,
            "torn_visible": not fell_back, "partial_visible": not invisible}


# ---------------------------------------------------------------------------
def drill_nan_sentinel(work):
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.amp import GradScaler
    from paddle_tpu.parallel import TrainStep
    from paddle_tpu.testing.faults import FaultPlan, install_plan, clear_plan
    from paddle_tpu.utils.monitor import stat_get
    paddle.seed(0)
    net = nn.Linear(8, 4)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=net.parameters())
    scaler = GradScaler(enable=True, init_loss_scaling=1024.0,
                        decr_every_n_nan_or_inf=1)
    step = TrainStep(net, opt, loss_fn=nn.MSELoss(), sentinel=True,
                     grad_scaler=scaler)
    install_plan(FaultPlan.parse("nan_grad:step=2"))
    try:
        rng = np.random.RandomState(0)
        x = rng.randn(16, 8).astype("float32")
        y = rng.randn(16, 4).astype("float32")
        skipped0 = stat_get("train_skipped_steps")
        losses, snaps = [], []
        for _ in range(4):
            snaps.append(
                np.asarray(step.state["params"][
                    sorted(step.state["params"])[0]]).copy())
            losses.append(float(step((x,), y)))
        skipped = stat_get("train_skipped_steps") - skipped0
        p_name = sorted(step.state["params"])[0]
        # step 2 (the injected one) must commit nothing: the param value
        # before step 3 equals the value before step 2
        frozen = bool(np.array_equal(snaps[2], snaps[1]))
        moved_after = not np.array_equal(
            np.asarray(step.state["params"][p_name]), snaps[2])
        return {"ok": bool(skipped == 1 and frozen and moved_after
                           and scaler.get_loss_scaling() == 512.0
                           and np.isfinite(losses[3])),
                "skipped_steps": skipped, "params_frozen_on_bad_step": frozen,
                "scale_after": scaler.get_loss_scaling(),
                "trained_through": bool(moved_after)}
    finally:
        clear_plan()


# ---------------------------------------------------------------------------
def drill_store_drop(work):
    from paddle_tpu.distributed.fleet.base.tcp_store import TCPStore
    from paddle_tpu.testing.faults import FaultPlan, install_plan, clear_plan
    store = TCPStore("127.0.0.1", 0, is_master=True)
    install_plan(FaultPlan.parse(
        "store_drop:op=set,at=1; store_drop:op=add,at=2,count=2"))
    try:
        store.set("k", b"v1")               # drop #1: retried, must land
        ok_set = store.get("k", wait=False) == b"v1"
        total = 0
        for _ in range(4):                  # drops #2,#3 on the add path
            total = store.add("ctr", 1)
        ok_add = total == 4                 # retries must not double-count
        return {"ok": bool(ok_set and ok_add), "set_survived": ok_set,
                "add_total": total}
    finally:
        clear_plan()
        store.close()


# ---------------------------------------------------------------------------
def drill_slow_step(work):
    from paddle_tpu.testing.faults import (FaultPlan, install_plan,
                                           clear_plan, step_hook)
    install_plan(FaultPlan.parse("slow:rank=0,step=1,seconds=0.4"))
    try:
        t0 = time.perf_counter()
        step_hook(0, rank=0)
        fast = time.perf_counter() - t0
        t0 = time.perf_counter()
        step_hook(1, rank=0)
        slow = time.perf_counter() - t0
        return {"ok": bool(slow >= 0.4 and fast < 0.2),
                "stall_s": round(slow, 3)}
    finally:
        clear_plan()


# ---------------------------------------------------------------------------
_KILL_WORKER = r"""
import json, os, sys
import numpy as np
sys.path.insert(0, @REPO@)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.checkpoint import CheckpointManager
from paddle_tpu.parallel import TrainStep

work = sys.argv[1]
total_steps = int(sys.argv[2])
paddle.seed(0)
net = nn.Linear(8, 4)
opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=net.parameters())
step = TrainStep(net, opt, loss_fn=nn.MSELoss())
step.attach_checkpoint_manager(
    CheckpointManager(os.path.join(work, "ckpt"), rank=0, world_size=1))
try:
    step.restore_from_checkpoint()
except FileNotFoundError:
    pass
while int(step.state["step"]) < total_steps:
    s = int(step.state["step"])          # deterministic per-step batch
    rng = np.random.RandomState(1000 + s)
    x = rng.randn(16, 8).astype("float32")
    y = rng.randn(16, 4).astype("float32")
    step((x,), y)                        # fault step_hook fires in here
    step.save_checkpoint(wait=True)
out = {n: np.asarray(v).tolist() for n, v in step.state["params"].items()}
with open(os.path.join(work, "final.json"), "w") as f:
    json.dump({"step": int(step.state["step"]), "params": out}, f)
"""


def drill_kill_resume(work):
    import numpy as np
    from paddle_tpu.distributed.fleet.elastic import ElasticLaunch
    total_steps, kill_at = 6, 3
    script = os.path.join(work, "kill_worker.py")
    with open(script, "w") as f:
        f.write(_KILL_WORKER.replace("@REPO@", repr(REPO)))

    def run(tag, plan):
        wdir = os.path.join(work, tag)
        os.makedirs(wdir, exist_ok=True)
        supervisor = []

        def spawn(local):
            env = dict(os.environ, PADDLE_TRAINER_ID="0",
                       PADDLE_TRAINERS_NUM="1", JAX_PLATFORMS="cpu")
            gen = supervisor[0].generation if supervisor else 0
            if plan and gen == 0:
                # the fault lives in the FIRST incarnation only — the
                # restarted gang must run clean, like a real preemption
                env["PADDLE_TPU_FAULT_PLAN"] = plan
            else:
                env.pop("PADDLE_TPU_FAULT_PLAN", None)
            return subprocess.Popen(
                [sys.executable, script, wdir, str(total_steps)], env=env)

        el = ElasticLaunch(spawn, 1, max_restarts=2, poll_s=0.2, gang=True)
        supervisor.append(el)
        rc, restarts = el.run()
        with open(os.path.join(wdir, "final.json")) as f:
            return rc, restarts[0], json.load(f)

    rc_f, restarts, faulted = run(
        "faulted", f"kill:rank=0,step={kill_at}")
    rc_c, _, clean = run("clean", None)
    same = faulted["step"] == clean["step"] == total_steps and all(
        np.array_equal(np.asarray(faulted["params"][n]),
                       np.asarray(clean["params"][n]))
        for n in clean["params"])
    return {"ok": bool(rc_f == 0 and rc_c == 0 and restarts >= 1 and same),
            "restarts": restarts, "resumed_step": faulted["step"],
            "params_match_uninterrupted": bool(same)}


# ---------------------------------------------------------------------------
SCENARIOS = {
    "torn_checkpoint": drill_torn_checkpoint,
    "nan_sentinel": drill_nan_sentinel,
    "store_drop": drill_store_drop,
    "slow_step": drill_slow_step,
    "kill_resume": drill_kill_resume,
}


def main(argv=None):
    p = argparse.ArgumentParser("fault_drill")
    p.add_argument("--dry", action="store_true",
                   help="toy-scale CPU run (the only mode today)")
    p.add_argument("scenarios", nargs="*", choices=list(SCENARIOS) + [[]],
                   help="subset to run (default: all)")
    args = p.parse_args(argv)
    names = args.scenarios or list(SCENARIOS)
    work = tempfile.mkdtemp(prefix="fault_drill_")
    failed = 0
    try:
        for name in names:
            t0 = time.perf_counter()
            try:
                rec = SCENARIOS[name](work)
            except Exception as e:  # a drill crash is a failed drill
                rec = {"ok": False, "error": repr(e)}
            rec.update(scenario=name, dry=bool(args.dry),
                       wall_s=round(time.perf_counter() - t0, 2))
            _emit(rec)
            failed += 0 if rec["ok"] else 1
    finally:
        shutil.rmtree(work, ignore_errors=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
