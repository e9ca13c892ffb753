"""Elastic heartbeat + bounded-restart launch tests.

Reference strategy parity: fleet/elastic tests — heartbeat staleness
detection and ElasticManager restart budgets.
"""
import os
import sys
import time

import pytest

from paddle_tpu.distributed.fleet.base.tcp_store import TCPStore
from paddle_tpu.distributed.fleet.elastic import (HeartbeatReporter,
                                                  HeartbeatMonitor,
                                                  ElasticLaunch)


def test_heartbeat_reporter_and_monitor():
    store = TCPStore("127.0.0.1", 0, is_master=True)
    try:
        mon = HeartbeatMonitor(store, world_size=2, stale_after=1.0)
        assert mon.stale_ranks() == [0, 1]        # nothing published yet
        hb = HeartbeatReporter(store, rank=0, interval=0.1).start()
        time.sleep(0.3)
        assert mon.stale_ranks() == [1]           # rank 0 alive
        hb.stop()
        time.sleep(1.2)
        assert mon.stale_ranks() == [0, 1]        # rank 0 went stale
    finally:
        store.close()


def test_elastic_launch_restarts_then_succeeds(tmp_path):
    """A rank that crashes twice then succeeds must be restarted within the
    budget and the job must exit 0."""
    marker = tmp_path / "attempts"

    def spawn(local):
        import subprocess
        code = (
            "import os, sys\n"
            f"p = r'{marker}'\n"
            "n = int(open(p).read()) if os.path.exists(p) else 0\n"
            "open(p, 'w').write(str(n + 1))\n"
            "sys.exit(0 if n >= 2 else 1)\n")
        return subprocess.Popen([sys.executable, "-c", code])

    rc, restarts = ElasticLaunch(spawn, 1, max_restarts=3,
                                 poll_s=0.05).run()
    assert rc == 0
    assert restarts[0] == 2
    assert marker.read_text() == "3"


def test_elastic_launch_budget_exceeded(tmp_path):
    def spawn(local):
        import subprocess
        return subprocess.Popen([sys.executable, "-c", "raise SystemExit(7)"])

    rc, restarts = ElasticLaunch(spawn, 1, max_restarts=1,
                                 poll_s=0.05).run()
    assert rc == 7
    assert restarts[0] == 1


def test_launcher_elastic_flag(tmp_path):
    """End-to-end through the CLI: --elastic_level 1 restarts a crashing
    script (test_launch.py pattern)."""
    import subprocess
    marker = tmp_path / "n"
    script = tmp_path / "train.py"
    script.write_text(
        "import os, sys\n"
        f"p = r'{marker}'\n"
        "n = int(open(p).read()) if os.path.exists(p) else 0\n"
        "open(p, 'w').write(str(n + 1))\n"
        "sys.exit(0 if n >= 1 else 3)\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.fleet.launch",
         "--nproc_per_node", "1", "--elastic_level", "1",
         "--max_restarts", "2", str(script)],
        capture_output=True, text=True, env=env,
        cwd="/root/repo", timeout=120)
    assert r.returncode == 0, r.stderr[-500:]
    assert marker.read_text() == "2"


def test_elastic_gang_restart(tmp_path):
    """Collective mode: one rank dying restarts the WHOLE gang (a lone
    rank cannot rejoin a live jax.distributed job)."""
    import subprocess

    def spawn(local):
        # rank 0 crashes on the first gang attempt, succeeds after;
        # attempt accounting is one exclusive file per attempt (atomic —
        # a read-modify-write raced with teardown under load)
        code = (
            "import os, sys, glob\n"
            f"d = r'{tmp_path}'\n"
            f"if {local} == 0:\n"
            "    n = len(glob.glob(os.path.join(d, 'attempt.*')))\n"
            "    open(os.path.join(d, f'attempt.{n}'), 'x').close()\n"
            "    sys.exit(0 if n >= 1 else 5)\n"
            "sys.exit(0)\n")
        return subprocess.Popen([sys.executable, "-c", code])

    rc, restarts = ElasticLaunch(spawn, 2, max_restarts=3,
                                 poll_s=0.05).run()   # gang default: n>1
    assert rc == 0
    assert restarts[0] >= 1       # at least one whole-gang restart
    import glob as _glob
    assert len(_glob.glob(str(tmp_path / "attempt.*"))) >= 2


def test_role_maker_auto_heartbeat(monkeypatch):
    """PADDLE_ELASTIC_HEARTBEAT_S (exported by the launcher when its
    watchdog is on) makes every worker publish liveness as soon as it has
    a store — no training-script changes."""
    from paddle_tpu.distributed.fleet.base.role_maker import \
        PaddleCloudRoleMaker
    import socket as _socket
    s = _socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    monkeypatch.setenv("PADDLE_ELASTIC_HEARTBEAT_S", "0.1")
    monkeypatch.setenv("PADDLE_TRAINER_ID", "0")
    monkeypatch.setenv("PADDLE_TRAINERS_NUM", "1")
    monkeypatch.setenv("PADDLE_STORE_PORT", str(port))
    rm = PaddleCloudRoleMaker(is_collective=True)
    store = rm._ensure_store()
    try:
        time.sleep(0.3)
        assert HeartbeatMonitor(store, 1, stale_after=1.0).stale_ranks() \
            == []
    finally:
        rm._heartbeat.stop()
        store.close()


def test_elastic_watchdog_real_heartbeats(tmp_path):
    """ISSUE 3 satellite E2E: a rank that hangs before ever reaching
    rendezvous (no heartbeat) is evicted by the launcher-side monitor and
    the whole gang relaunched — process polling alone would wait forever.
    Uses real HeartbeatReporter/TCPStore traffic, the lazy monitor
    factory the launch CLI uses, and SIGKILL eviction.

    The launcher counts its warm-up from the moment the gang is spawned,
    and a deployment sizes it for its workers' start-up.  Here importing
    the package takes anything from 3 to 15 s with the host's load, so
    the test owns that clock: a worker says when it has imported, the
    spawn of the gang's last rank returns once both have and lets them
    go, and the 1.5 s warm-up covers what it is meant to, a store and a
    first heartbeat.  (Counted from ``Popen`` it was over before either
    import, and a relaunched rank 1 whose first heartbeat came more than
    one poll behind rank 0's store was evicted a second time.)  A worker
    outlives the warm-up, so the watchdog looks at the healthy gang too."""
    import signal
    import socket as _socket
    import subprocess
    from paddle_tpu.distributed.fleet.base.tcp_store import TCPStore
    s = _socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()

    worker = (
        "import os, sys, time\n"
        "sys.path.insert(0, {repo!r})\n"
        "from paddle_tpu.distributed.fleet.base.tcp_store import TCPStore\n"
        "from paddle_tpu.distributed.fleet.elastic import HeartbeatReporter\n"
        "rank, port, attempt = (int(a) for a in sys.argv[1:4])\n"
        "print('imported', flush=True)\n"
        "sys.stdin.readline()           # the gang goes together\n"
        "if rank == 1 and attempt == 0:\n"
        "    time.sleep(120)            # hung before rendezvous: no store,"
        " no heartbeat\n"
        "store = TCPStore('127.0.0.1', port, is_master=(rank == 0),"
        " timeout=30.0)\n"
        "hb = HeartbeatReporter(store, rank, interval=0.1).start()\n"
        "time.sleep(3.0)\n"
        "hb.stop()\n"
        "raise SystemExit(0)\n").format(
            repo=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    script = tmp_path / "hb_worker.py"
    script.write_text(worker)

    supervisor = []
    spawned = []

    def spawn(local):
        attempt = supervisor[0].generation if supervisor else 0
        spawned.append(subprocess.Popen(
            [sys.executable, str(script), str(local), str(port),
             str(attempt)], stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            text=True))
        if local == 1:
            gang = spawned[-2:]
            for p in gang:
                assert p.stdout.readline() == "imported\n"
            for p in gang:
                p.stdin.write("go\n")
                p.stdin.flush()
        return spawned[-1]

    state = {}

    def monitor_factory():
        if "m" in state:
            return state["m"]
        try:
            client = TCPStore("127.0.0.1", port, timeout=1.0)
            state["m"] = HeartbeatMonitor(client, 2, stale_after=1.0)
        except Exception:
            return None
        return state["m"]

    el = ElasticLaunch(spawn, 2, max_restarts=2, poll_s=0.1, gang=True,
                       monitor=monitor_factory, watchdog_warmup=1.5)
    supervisor.append(el)
    t0 = time.time()
    try:
        rc, restarts = el.run()
    finally:
        for p in spawned:
            if p.poll() is None:
                p.kill()
            p.communicate()
    assert rc == 0
    assert restarts[0] == 1
    # evicted, not waited for: the hung rank died of the watchdog's
    # SIGKILL, well inside the 120 s it meant to sleep
    assert [p.returncode for p in spawned] == [-signal.SIGKILL] * 2 + [0, 0]
    assert time.time() - t0 < 120
    from paddle_tpu.utils.monitor import stat_get
    assert stat_get("elastic_restart_generation") >= 1
