"""Subprocess smoke for the sharded-embedding bit-match gate (slow-marked:
a fresh interpreter provisions its own 8-device virtual CPU mesh and pays
the trainer compiles twice — the repo convention for anything tier-1 must
not pay).

The CI lane of ISSUE 10's acceptance criterion at full test scale: the
wide_deep training trajectory over an 8-device mesh with the deep table
row-partitioned (FLAGS_sharded_embedding, device dedup + hot-row cache
on) must be BIT-IDENTICAL to the unsharded replicated control — losses
and flushed table rows — while victim/warm all-to-all routing provably
ran.
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, __REPO__)
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.rec.wide_deep import (WideDeep, WideDeepTrainer,
                                          synthetic_ctr_batch)

    VOCAB, BATCH, CAP = 6000, 128, 1536
    SEEDS = (0, 1, 2, 0, 3)

    def run(sharded):
        set_flags({"FLAGS_wide_deep_device_dedup": True})
        paddle.seed(42)
        m = WideDeep(hidden=(32,), emb_dim=4)
        t = WideDeepTrainer(m, device_cache=True, cache_capacity=CAP,
                            sharded_embedding=sharded,
                            sharded_vocab=VOCAB if sharded else None)
        losses, route = [], {"cold": 0, "warm": 0, "victims": 0}
        for seed in SEEDS:
            ids, dense, label = synthetic_ctr_batch(BATCH, vocab=VOCAB,
                                                    seed=seed)
            losses.append(float(t.step(ids, dense, label)))
            if sharded:
                for k in route:
                    route[k] += t._last_route_stats[k]
        t.flush()
        uniq = np.unique(synthetic_ctr_batch(BATCH, vocab=VOCAB,
                                             seed=0)[0])
        return losses, m.client.pull_sparse(1, uniq), route

    la, ra, _ = run(False)
    lb, rb, route = run(True)
    assert la == lb, ("loss trajectories diverged", la, lb)
    assert np.array_equal(ra, rb), "flushed deep-table rows diverged"
    assert route["victims"] > 0 and route["warm"] > 0, (
        "routing never ran", route)
    print("BITMATCH OK", len(la), "steps; route", route, flush=True)
""")


def _env(n=8):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    flags = " ".join(f for f in env.get("XLA_FLAGS", "").split()
                     if not f.startswith("--xla_force_host_platform"))
    env["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n}").strip()
    return env


@pytest.mark.slow
def test_sharded_bit_match_gate_8dev(tmp_path):
    script = tmp_path / "gate.py"
    script.write_text(_WORKER.replace("__REPO__", repr(REPO)))
    p = subprocess.run([sys.executable, str(script)], capture_output=True,
                       text=True, timeout=840, env=_env(8), cwd=REPO)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "BITMATCH OK" in p.stdout
