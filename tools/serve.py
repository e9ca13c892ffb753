#!/usr/bin/env python
"""serve — export zoo models, warm a serving engine, drive traffic, check SLOs.

The CLI face of ``paddle_tpu.serving``: the whole deploy walkthrough
(export → warm-up → serve → SLO check) in one command, on whatever
backend JAX finds (``JAX_PLATFORMS=cpu`` for a run off the chip).

One process per chip: under ``--router`` / ``--ramp`` every replica is a
child process that needs a chip of its own, and the parent (router,
traffic, observer) never initialises a JAX backend.

    python tools/serve.py --model lenet --duration 2 --clients 4
    python tools/serve.py --model lenet --model bert --int8 --json
    python tools/serve.py --model resnet_block --p99-slo-ms 250 --json

Exit code is non-zero when any request errored, any steady-state XLA
compile was recorded after warm-up (the bucketed-batching invariant), or
a ``--p99-slo-ms`` bound was violated — so a CI lane can gate on it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


# What a client feeds each zoo model: (input specs, token vocab or None).
# The --router/--ramp parents read THIS and never build a layer: a parent
# that created a JAX array would hold the chip its replica children need.
_BLOCK_CH = _BLOCK_HW = 8
_BERT_SEQ, _BERT_VOCAB = 32, 128
ZOO_FEEDS = {
    "lenet": ([([None, 1, 28, 28], "float32")], None),
    "resnet_block": ([([None, _BLOCK_CH, _BLOCK_HW, _BLOCK_HW],
                       "float32")], None),
    "bert": ([([None, _BERT_SEQ], "int32")], _BERT_VOCAB),
}


def build_lenet():
    from paddle_tpu.vision.models import LeNet
    return LeNet(), ZOO_FEEDS["lenet"][0]


def build_resnet_block():
    import paddle_tpu.nn as nn
    ch = _BLOCK_CH

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

    return Block(), ZOO_FEEDS["resnet_block"][0]


def build_bert():
    from paddle_tpu.text.models.bert import BertConfig, BertModel
    cfg = BertConfig.tiny(vocab_size=_BERT_VOCAB, seq=_BERT_SEQ)
    m = BertModel(cfg)
    m._serve_vocab = cfg.vocab_size
    return m, ZOO_FEEDS["bert"][0]


ZOO = {
    "lenet": build_lenet,
    "resnet_block": build_resnet_block,
    "bert": build_bert,
}


def build_gpt_decode(vocab=128, seq=128):
    from paddle_tpu.text.models.gpt import GPTConfig, GPTModel
    m = GPTModel(GPTConfig.tiny(vocab_size=vocab, hidden_size=32,
                                layers=2, heads=2, seq=seq))
    m.eval()
    m._serve_vocab = vocab
    return m


def _decode_traffic(server, name, duration_s, clients, max_rows,
                    max_prompt, max_new, vocab, seed):
    """Concurrent mixed prefill/decode traffic: each client submits
    random-row requests of random-length prompts (spanning the prefill
    bucket ladder) with random generation budgets, and checks the result
    shape; per-client error capture."""
    errors = []
    deadline = time.perf_counter() + duration_s

    def client(i):
        rng = np.random.RandomState(seed + i)
        while time.perf_counter() < deadline:
            rows = int(rng.randint(1, max_rows + 1))
            prompts = [rng.randint(1, vocab,
                                   int(rng.randint(1, max_prompt + 1)))
                       for _ in range(rows)]
            mn = int(rng.randint(1, max_new + 1))
            try:
                out = server.submit_decode(
                    name, prompts, max_new_tokens=mn).result(timeout=60)
                if out[0].shape != (rows, mn):
                    raise AssertionError(
                        f"decode shape {out[0].shape} != ({rows}, {mn})")
            except Exception as e:   # noqa: BLE001 — reported per client
                errors.append(f"client{i}: {type(e).__name__}: {e}")
                return
    threads = [threading.Thread(target=client, args=(i,))
               for i in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return errors


def _random_inputs(rng, specs, rows, vocab=None):
    out = []
    for shape, dtype in specs:
        s = (rows,) + tuple(shape[1:])
        if np.issubdtype(np.dtype(dtype), np.integer):
            out.append(rng.randint(0, vocab or 100, s).astype(dtype))
        else:
            out.append(rng.randn(*s).astype(dtype))
    return out


def _traffic(server, name, specs, duration_s, clients, max_rows, vocab,
             seed):
    """Concurrent mixed-shape traffic: each client submits random-row
    requests until the deadline; per-client error capture."""
    errors = []
    deadline = time.perf_counter() + duration_s

    def client(i):
        rng = np.random.RandomState(seed + i)
        while time.perf_counter() < deadline:
            rows = int(rng.randint(1, max_rows + 1))
            try:
                fut = server.submit(
                    name, _random_inputs(rng, specs, rows, vocab))
                outs = fut.result(timeout=60)
                if outs[0].shape[0] != rows:
                    raise AssertionError(
                        f"padding leaked: {outs[0].shape[0]} != {rows}")
            except Exception as e:   # noqa: BLE001 — reported per client
                errors.append(f"client{i}: {type(e).__name__}: {e}")
                return
    threads = [threading.Thread(target=client, args=(i,))
               for i in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return errors


def _replica_child(cfg_path):
    """Replica-process entry (spawned by --router): build the configured
    models, start a Server, and serve RPC until killed.  Deterministic
    by construction — every replica seeds identically, so all replicas
    hold bit-identical weights and the router's answers do not depend
    on which replica served them."""
    with open(cfg_path) as f:
        cfg = json.load(f)
    import paddle_tpu as paddle
    from paddle_tpu import serving
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.serving.cluster import replica_main
    set_flags({"FLAGS_serving_role": cfg.get("role", "both"),
               "FLAGS_router_heartbeat_s": float(cfg["heartbeat_s"])})
    if cfg.get("session_store"):
        # stateful replica: slot-loop decode + prefix cache + parked-
        # session store.  The spill dir is SHARED across the fleet —
        # that is what makes the SIGKILL drill stateful: a survivor
        # restores the victim's parked conversations from disk.
        from paddle_tpu.framework.flags import flag as _flag
        sess = {"FLAGS_session_store": True,
                "FLAGS_session_store_dir":
                    cfg.get("session_store_dir") or "",
                "FLAGS_prefix_cache": True}
        if not int(_flag("decode_slots")):
            sess["FLAGS_decode_slots"] = 4
        set_flags(sess)
    if cfg.get("cache_dir"):
        set_flags({"FLAGS_executable_cache": "readwrite",
                   "FLAGS_executable_cache_dir": cfg["cache_dir"]})
    if cfg.get("trace") and cfg["trace"] != "off":
        # spans ship to the router through the scrape op's export
        # buffer — no per-replica trace dir needed
        set_flags({"FLAGS_trace": cfg["trace"]})
    if cfg.get("flight_dir"):
        set_flags({"FLAGS_flight_dir": cfg["flight_dir"],
                   "FLAGS_flight_interval_s":
                       float(cfg.get("flight_interval_s", 0.5))})
    paddle.seed(cfg["seed"])
    buckets = tuple(cfg["buckets"])
    server = serving.Server(serving.ServingConfig(
        workers=cfg.get("workers"), buckets=buckets,
        version=cfg.get("version")))
    for tenant, pol in (cfg.get("tenant_policies") or {}).items():
        server.set_tenant_policy(tenant, **pol)
    with tempfile.TemporaryDirectory() as d:
        for name in cfg["models"]:
            layer, specs = ZOO[name]()
            layer.eval()
            prefix = os.path.join(d, name)
            serving.export_for_serving(layer, prefix, specs,
                                       buckets=buckets)
            server.register(name, prefix, buckets=buckets)
        if cfg.get("decode"):
            seq_buckets = tuple(cfg["seq_buckets"])
            gpt = build_gpt_decode()
            server.register_decode(
                "gpt_decode", gpt, batch_buckets=buckets,
                seq_buckets=seq_buckets, max_new_tokens=cfg["max_new"],
                max_len=max(seq_buckets) + cfg["max_new"])
        replica_main(server, replica_id=cfg["id"],
                     store_host=cfg["store_host"],
                     store_port=cfg["store_port"],
                     port=int(cfg.get("port", 0)), block=True,
                     heldout=bool(cfg.get("heldout")))
    return 0


def _router_main(args):
    """--router mode: spawn FLAGS_serving_replicas replica subprocesses,
    rendezvous them through a TCPStore, route sustained traffic through
    the front-end Router, optionally SIGKILL one replica mid-traffic
    (--kill-one: the heartbeat evict + redistribution drill), and gate
    the exit code on traffic errors, per-replica steady-state compiles,
    SLOs, and the eviction actually happening."""
    import signal
    import subprocess

    from paddle_tpu.distributed.fleet.base.tcp_store import TCPStore
    from paddle_tpu.framework.flags import flag as _flag, set_flags
    from paddle_tpu.serving.cluster import ClusterObserver, Router, \
        serve_cluster_metrics

    n = args.replicas if args.replicas is not None \
        else int(_flag("serving_replicas"))
    if args.disaggregate and (not args.decode or n < 2):
        print("--disaggregate needs --decode and --replicas >= 2",
              file=sys.stderr)
        return 2
    names = list(dict.fromkeys(
        args.model or ([] if args.decode else ["lenet"])))
    buckets = tuple(int(b) for b in args.buckets.split(",") if b.strip())
    seq_buckets = tuple(int(b) for b in args.seq_buckets.split(",")
                        if b.strip())
    report = {"router": True, "replicas": n,
              "disaggregate": bool(args.disaggregate),
              "duration_s": args.duration, "clients": args.clients,
              "models": {}, "replica_stats": {}}
    rc = 0
    trace_mode = "off"
    if args.trace_dir:
        # the router's own route/dispatch spans need tracing ON; they
        # reach the merged JSONL through the observer's export-buffer
        # drain, NOT a per-process trace dir (that would double-write)
        if str(_flag("trace")).lower() == "off":
            set_flags({"FLAGS_trace": "full"})
        trace_mode = str(_flag("trace")).lower()
        report["trace_dir"] = args.trace_dir
        report["trace_mode"] = trace_mode
    if args.flight_dir:
        os.makedirs(args.flight_dir, exist_ok=True)
        report["flight_dir"] = args.flight_dir
    store = TCPStore("127.0.0.1", 0, is_master=True)
    children, router = [], None
    obs = cluster_metrics_srv = sess_traffic = None
    cfg_dir = tempfile.mkdtemp(prefix="serve_router_")
    sess_dir = ""
    if args.sessions:
        sess_dir = os.path.join(cfg_dir, "sessions")
        os.makedirs(sess_dir, exist_ok=True)
        report["sessions_dir"] = sess_dir
    try:
        for i in range(n):
            role = "both"
            if args.disaggregate:
                # alternate so both pools exist at every cluster size
                role = "prefill" if i % 2 == 0 else "decode"
            cfg = {"id": f"replica{i}", "role": role, "seed": args.seed,
                   "session_store": bool(args.sessions),
                   "session_store_dir": sess_dir,
                   "models": names, "decode": bool(args.decode),
                   "buckets": list(buckets),
                   "seq_buckets": list(seq_buckets),
                   "max_new": args.max_new, "workers": args.workers,
                   "store_host": "127.0.0.1", "store_port": store.port,
                   "heartbeat_s": float(_flag("router_heartbeat_s")),
                   "cache_dir": args.cache_dir,
                   "trace": trace_mode,
                   "flight_dir": args.flight_dir,
                   "flight_interval_s": 0.5}
            path = os.path.join(cfg_dir, f"replica{i}.json")
            with open(path, "w") as f:
                json.dump(cfg, f)
            children.append(subprocess.Popen(
                [sys.executable, os.path.abspath(__file__),
                 "--replica-config", path],
                stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT))
        router = Router(store=store)
        # the cluster observability plane: federation + trace assembly
        # + ClusterSignals, driven by the router's watch loop
        obs = ClusterObserver(router, trace_dir=args.trace_dir)
        router.attach_observer(obs)
        if args.metrics_port is not None:
            cluster_metrics_srv = serve_cluster_metrics(
                obs, port=args.metrics_port)
            report["metrics_port"] = cluster_metrics_srv.port
        t0 = time.perf_counter()
        deadline = t0 + 300
        while router.replicas_live() < n:
            if time.perf_counter() > deadline:
                report["error"] = (f"only {router.replicas_live()}/{n} "
                                   "replicas joined within 300s")
                return _router_report(report, args, 1)
            for p in children:
                if p.poll() not in (None, 0):
                    report["error"] = \
                        f"replica exited rc={p.returncode} during warm-up"
                    return _router_report(report, args, 1)
            time.sleep(0.2)
        report["warmup_s"] = round(time.perf_counter() - t0, 3)

        killed = {"id": None}
        if args.kill_one:
            # kill mid-traffic from a side thread: the drill is traffic
            # REDISTRIBUTING, not a clean restart
            def killer():
                time.sleep(max(0.2, args.duration / 3))
                victim = children[-1]
                killed["id"] = f"replica{n - 1}"
                victim.send_signal(signal.SIGKILL)
            threading.Thread(target=killer, daemon=True).start()

        errors = []
        if args.decode and args.sessions:
            # stateful leg rides ALONGSIDE the one-shot traffic (mixed
            # workload); with --kill-one the SIGKILL lands mid-turn and
            # the gate below demands zero lost sessions anyway
            sess_traffic = _SessionTraffic(
                router, "gpt_decode", seq_buckets, args.max_new,
                clients=max(2, args.clients // 2),
                seed=args.seed + 31).start()
        if args.decode:
            errors += _decode_traffic(
                router, "gpt_decode", args.duration, args.clients,
                args.max_request_rows, max(seq_buckets), args.max_new,
                128, args.seed)
        for name in names:
            specs, vocab = ZOO_FEEDS[name]
            errors += _traffic(router, name, specs, args.duration,
                               args.clients, args.max_request_rows,
                               vocab, args.seed)
        report["traffic_errors"] = errors
        if errors:
            rc = 1
        if sess_traffic is not None:
            sess_traffic.stop()
            report["sessions"] = sess_traffic.report()
            rc = _gate_sessions(report, args, rc)

        if args.kill_one:
            # the dead replica must be EVICTED by heartbeat, traffic
            # already redistributed (no errors above past the ack)
            stale = float(_flag("router_stale_after_s"))
            hb = float(_flag("router_heartbeat_s"))
            evict_deadline = time.perf_counter() + stale + 4 * hb + 10
            while router.replicas_live() > n - 1:
                if time.perf_counter() > evict_deadline:
                    break
                time.sleep(0.2)
            report["kill_one"] = {
                "victim": killed["id"],
                "evicted": router.replicas_live() == n - 1}
            if not report["kill_one"]["evicted"]:
                rc = 1
            if args.flight_dir and killed["id"]:
                # SIGKILL leaves no exit path — the victim's evidence is
                # whatever its flight recorder last persisted atomically
                pm = os.path.join(args.flight_dir,
                                  f"postmortem_{killed['id']}.json")
                report["kill_one"]["postmortem"] = pm
                report["kill_one"]["postmortem_exists"] = \
                    os.path.exists(pm)

        steady_total = 0
        for h in router.handles():
            if not h.alive:
                continue
            try:
                st = h.model_stats()
                hl = h.health()
            except Exception as e:   # noqa: BLE001 — reported, gated
                report["replica_stats"][h.id] = \
                    {"error": f"{type(e).__name__}: {e}"}
                rc = 1
                continue
            steady_total += int(hl.get("steady_compiles", 0))
            report["replica_stats"][h.id] = st
            if args.p99_slo_ms is not None:
                worst = max((m["p99_ms"] for m in st.values()
                             if m.get("completed")), default=0.0)
                if worst > args.p99_slo_ms:
                    rc = 1
        report["steady_compiles"] = steady_total
        if steady_total:
            rc = 1
        report["router_stats"] = router.stats()
        # final federation round on OUR clock: drain the last spans and
        # dumps so the merged trace / textfile include end-of-run state
        sig = obs.poll()
        report["cluster_signals"] = sig.to_dict()
        report["observer"] = obs.stats()
        if cluster_metrics_srv is not None:
            import urllib.request
            try:
                with urllib.request.urlopen(
                        "http://127.0.0.1:"
                        f"{cluster_metrics_srv.port}/metrics",
                        timeout=10) as resp:
                    body = resp.read().decode()
                report["metrics_scrape_ok"] = (
                    resp.status == 200
                    and "cluster_signals_replicas_live" in body)
            except Exception as e:   # noqa: BLE001 — reported, gated
                report["metrics_scrape_ok"] = False
                report["metrics_scrape_error"] = \
                    f"{type(e).__name__}: {e}"
            if not report["metrics_scrape_ok"]:
                rc = 1
        if args.metrics_textfile:
            report["metrics_textfile"] = \
                obs.write_textfile(args.metrics_textfile)
    finally:
        if sess_traffic is not None:
            sess_traffic.stop()
        if cluster_metrics_srv is not None:
            cluster_metrics_srv.close()
        if obs is not None:
            obs.close()
        if router is not None:
            router.close()
        for p in children:
            if p.poll() is None:
                p.terminate()
        for p in children:
            try:
                p.wait(timeout=10)
            except Exception:   # noqa: BLE001 — last resort
                p.kill()
        store.close()
    return _router_report(report, args, rc)


def _free_port():
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class _BgTraffic:
    """Open-loop background clients that run until told to stop — the
    ramp drill's phases (scale-up, drain-down, rollout legs) have no
    fixed traffic deadline, so the deadline-based _traffic helpers do
    not fit.  Each success is wall-stamped so the report can compute
    windowed p99s (the tenant-isolation control/burst comparison);
    quota rejections (UnavailableError with a retry_after hint, when
    ``count_rejections``) are tallied, not fatal — every other client
    exception is a drill-failing error."""

    def __init__(self, router, dense, decode, seq_buckets, max_new,
                 clients, seed, tenant="default", vocab=128, max_rows=2,
                 timeout=120.0, count_rejections=False):
        self._router = router
        self._dense = dense              # [(name, specs, vocab), ...]
        self._decode = bool(decode)
        self._max_prompt = max(seq_buckets)
        self._max_new = max_new
        self._clients = int(clients)
        self._seed = int(seed)
        self.tenant = str(tenant)
        self._vocab = int(vocab)
        self._max_rows = int(max_rows)
        self._timeout = float(timeout)
        self._count_rejections = bool(count_rejections)
        self._stop = threading.Event()
        self._threads = []
        self._lock = threading.Lock()
        self.errors = []
        self.rejections = 0
        self.latencies = []              # (wall_ts, seconds) per success

    def _client(self, i):
        from paddle_tpu.framework.enforce import UnavailableError
        rng = np.random.RandomState(self._seed + i)
        while not self._stop.is_set():
            rows = int(rng.randint(1, self._max_rows + 1))
            use_decode = self._decode and (not self._dense
                                           or rng.rand() < 0.5)
            t0 = time.perf_counter()
            try:
                if use_decode:
                    prompts = [rng.randint(
                        1, self._vocab,
                        int(rng.randint(1, self._max_prompt + 1)))
                        for _ in range(rows)]
                    mn = int(rng.randint(1, self._max_new + 1))
                    out = self._router.submit_decode(
                        "gpt_decode", prompts, max_new_tokens=mn,
                        timeout=self._timeout,
                        tenant=self.tenant).result(timeout=self._timeout)
                    if out[0].shape != (rows, mn):
                        raise AssertionError(
                            f"decode shape {out[0].shape} != ({rows},{mn})")
                else:
                    name, specs, vocab = \
                        self._dense[rng.randint(len(self._dense))]
                    outs = self._router.submit(
                        name, _random_inputs(rng, specs, rows, vocab),
                        timeout=self._timeout,
                        tenant=self.tenant).result(timeout=self._timeout)
                    if outs[0].shape[0] != rows:
                        raise AssertionError(
                            f"padding leaked: {outs[0].shape[0]} != {rows}")
                with self._lock:
                    self.latencies.append(
                        (time.time(), time.perf_counter() - t0))
            except UnavailableError as e:
                if self._count_rejections \
                        and getattr(e, "retry_after_s", None) is not None:
                    with self._lock:
                        self.rejections += 1
                    self._stop.wait(min(1.0, float(e.retry_after_s)))
                    continue
                with self._lock:
                    self.errors.append(
                        f"{self.tenant}/client{i}: "
                        f"{type(e).__name__}: {e}")
                return
            except Exception as e:   # noqa: BLE001 — reported, gated
                with self._lock:
                    self.errors.append(
                        f"{self.tenant}/client{i}: "
                        f"{type(e).__name__}: {e}")
                return

    def start(self):
        self._threads = [
            threading.Thread(target=self._client, args=(i,), daemon=True)
            for i in range(self._clients)]
        for t in self._threads:
            t.start()
        return self

    def stop(self):
        self._stop.set()
        for t in self._threads:
            t.join(timeout=self._timeout + 30)

    def p99_ms(self, t0=None, t1=None):
        with self._lock:
            lats = [s for (ts, s) in self.latencies
                    if (t0 is None or ts >= t0)
                    and (t1 is None or ts <= t1)]
        if not lats:
            return None
        return round(float(np.percentile(
            np.asarray(lats) * 1e3, 99)), 3)


class _SessionTraffic:
    """Multi-turn conversation clients (the --sessions traffic mode).

    Each client keeps extending conversations: a turn submits the FULL
    transcript so far plus a fresh user suffix under a stable
    ``session_id``, appends whatever the server generated, and comes
    back for the next turn until the transcript no longer fits the
    prompt ladder (then that conversation ends and a new one starts).
    Every ``verify_every``-th follow-up turn the same transcript is
    ALSO submitted WITHOUT a session_id — the stateless prefill is the
    bit-exactness oracle: a session restore that is not bit-identical
    to plain serving is an error, not a slowdown.

    A turn that bounces with a retryable UnavailableError (drain park,
    replica death mid-flight) retries until the turn deadline; a turn
    that never lands counts as a LOST session — the stateful drills
    gate rc on zero of those.  Works against a Server or a Router:
    both expose ``submit_decode(..., session_id=...) -> Future``.
    """

    def __init__(self, target, model, seq_buckets, max_new, clients,
                 seed, vocab=128, verify_every=4, turn_timeout=120.0):
        self._target = target
        self._model = model
        self._max_prompt = max(seq_buckets)
        self._max_new = int(max_new)
        self._clients = int(clients)
        self._seed = int(seed)
        self._vocab = int(vocab)
        self._verify_every = max(1, int(verify_every))
        self._timeout = float(turn_timeout)
        self._stop = threading.Event()
        self._threads = []
        self._lock = threading.Lock()
        self.errors = []
        self.lost = 0
        self.turns = 0
        self.follow_ups = 0
        self.conversations = 0
        self.verified = 0
        self.mismatches = 0
        self.latencies = []              # (wall_ts, seconds, turn_idx)

    def _decode(self, prompt, sid):
        fut = self._target.submit_decode(
            self._model, [prompt], max_new_tokens=self._max_new,
            timeout=self._timeout, session_id=sid)
        return np.asarray(fut.result(timeout=self._timeout)[0])[0]

    def _turn(self, prompt, sid):
        from paddle_tpu.framework.enforce import UnavailableError
        deadline = time.monotonic() + self._timeout
        while True:
            try:
                return self._decode(prompt, sid)
            except UnavailableError as e:
                # drain bounce / parked mid-flight: the transcript is
                # client-held state, so the turn is safely retryable
                if time.monotonic() > deadline or self._stop.is_set():
                    raise
                time.sleep(min(1.0,
                               float(getattr(e, "retry_after_s", None)
                                     or 0.05)))

    def _client(self, i):
        rng = np.random.RandomState(self._seed + 7919 * (i + 1))
        transcript, sid, turn, conv = None, None, 0, 0
        while not self._stop.is_set():
            if transcript is None:
                conv += 1
                sid = f"client{i}-conv{conv}"
                turn = 0
                transcript = rng.randint(
                    1, self._vocab,
                    int(rng.randint(2, max(3, self._max_prompt // 4)))
                ).astype(np.int32)
                with self._lock:
                    self.conversations += 1
            else:
                transcript = np.concatenate(
                    [transcript, rng.randint(1, self._vocab,
                                             int(rng.randint(1, 5))
                                             ).astype(np.int32)])
            if transcript.size > self._max_prompt:
                transcript = None        # conversation outgrew the
                continue                 # ladder — retire it
            t0 = time.perf_counter()
            try:
                got = self._turn(transcript, sid)
            except Exception as e:   # noqa: BLE001 — a lost session
                with self._lock:
                    self.errors.append(f"{sid} turn{turn}: "
                                       f"{type(e).__name__}: {e}")
                    self.lost += 1
                transcript = None
                continue
            with self._lock:
                self.turns += 1
                self.follow_ups += bool(turn)
                self.latencies.append(
                    (time.time(), time.perf_counter() - t0, turn))
                check = turn and self.follow_ups % self._verify_every == 0
            if check:
                try:
                    want = self._turn(transcript, None)
                except Exception:   # noqa: BLE001 — the oracle leg
                    pass            # bounced; it only counts when run
                else:
                    with self._lock:
                        self.verified += 1
                        if not np.array_equal(got, want):
                            self.mismatches += 1
                            self.errors.append(
                                f"{sid} turn{turn}: session continuation"
                                " != stateless prefill")
            transcript = np.concatenate(
                [transcript, np.asarray(got, np.int32)])
            turn += 1

    def start(self):
        self._threads = [
            threading.Thread(target=self._client, args=(i,), daemon=True)
            for i in range(self._clients)]
        for t in self._threads:
            t.start()
        return self

    def stop(self):
        self._stop.set()
        for t in self._threads:
            t.join(timeout=self._timeout + 30)

    @staticmethod
    def _p99(lats):
        if not lats:
            return None
        return round(float(np.percentile(np.asarray(lats) * 1e3, 99)), 3)

    def report(self):
        with self._lock:
            return {"turns": self.turns, "follow_ups": self.follow_ups,
                    "conversations": self.conversations,
                    "lost_sessions": self.lost,
                    "verified_turns": self.verified,
                    "bit_mismatches": self.mismatches,
                    "p99_ms": self._p99(
                        [s for (_, s, _) in self.latencies]),
                    "follow_up_p99_ms": self._p99(
                        [s for (_, s, t) in self.latencies if t]),
                    "errors": list(self.errors)}


def _gate_sessions(report, args, rc):
    """Shared rc gate of the stateful traffic modes: zero lost
    sessions, zero bit-exactness mismatches, zero client errors, at
    least one follow-up turn actually exercised, and (when set) the
    p99 SLO over whole turns."""
    sess = report["sessions"]
    if sess["errors"] or sess["lost_sessions"] or sess["bit_mismatches"] \
            or not sess["follow_ups"]:
        rc = 1
    if args.p99_slo_ms is not None and sess["p99_ms"] is not None \
            and sess["p99_ms"] > args.p99_slo_ms:
        sess["p99_slo_violated"] = True
        rc = 1
    return rc


def _ramp_main(args):
    """--ramp N: the elastic-lifecycle drill.  One seed replica boots,
    sustained mixed traffic starts and NEVER stops; the cluster then
    scales 1 -> N -> 1 through the AutoscaleController (scale-down is
    graceful drain — rc gates on every retirement reporting drained,
    zero heartbeat evictions, zero client errors, zero steady-state
    compiles).  A tenant-burst window measures per-tenant admission
    isolation, and --rollout adds zero-downtime rolling-update legs:
    happy path behind the canary bit-match gate, an optional mid-rollout
    SIGKILL (--rollout-kill, journal-resume + postmortem gates), and a
    fault-forced canary rollback that must leave the old version
    serving."""
    import signal
    import subprocess

    from paddle_tpu.distributed.fleet.base.tcp_store import TCPStore
    from paddle_tpu.framework.flags import flag as _flag
    from paddle_tpu.profiler.metrics import default_registry
    from paddle_tpu.serving.cluster import (AutoscaleController,
                                            ClusterObserver, RemoteReplica,
                                            RollingUpdate, Router, RpcClient)
    from paddle_tpu.testing import faults as _faults

    n_top = int(args.ramp)
    if n_top < 2:
        print("--ramp needs N >= 2", file=sys.stderr)
        return 2
    names = list(dict.fromkeys(
        args.model or ([] if args.decode else ["lenet"])))
    buckets = tuple(int(b) for b in args.buckets.split(",") if b.strip())
    seq_buckets = tuple(int(b) for b in args.seq_buckets.split(",")
                        if b.strip())
    report = {"ramp": n_top, "duration_s": args.duration,
              "clients": args.clients, "models": names,
              "decode": bool(args.decode), "replica_stats": {}}
    rc = 0
    if args.flight_dir:
        os.makedirs(args.flight_dir, exist_ok=True)
        report["flight_dir"] = args.flight_dir
    store = TCPStore("127.0.0.1", 0, is_master=True)
    cfg_dir = tempfile.mkdtemp(prefix="serve_ramp_")
    # a shared executable cache is what makes elastic scale-up viable:
    # the seed replica compiles once, every later spawn boots O(load)
    from paddle_tpu.utils.cache_dirs import executable_cache_dir
    cache_dir = args.cache_dir or executable_cache_dir("serve_ramp")
    os.makedirs(cache_dir, exist_ok=True)
    sess_dir = ""
    if args.sessions:
        # one spill dir for the WHOLE fleet: every spawn (including
        # rollout canaries) sees the same parked sessions, so a
        # SIGKILLed replica's conversations outlive it on disk
        sess_dir = os.path.join(cfg_dir, "sessions")
        os.makedirs(sess_dir, exist_ok=True)
        report["sessions_dir"] = sess_dir
    children = {}                        # replica id -> Popen
    router = obs = traffic = burst_router = sess_traffic = None

    def _cfg_for(rid, version=None, store_on=True, port=0,
                 heldout=False):
        return {"id": rid, "role": "both", "seed": args.seed,
                "heldout": heldout,
                "session_store": bool(args.sessions),
                "session_store_dir": sess_dir,
                "models": names, "decode": bool(args.decode),
                "buckets": list(buckets),
                "seq_buckets": list(seq_buckets),
                "max_new": args.max_new, "workers": args.workers,
                "store_host": "127.0.0.1" if store_on else None,
                "store_port": store.port, "port": port,
                "heartbeat_s": float(_flag("router_heartbeat_s")),
                "cache_dir": cache_dir, "trace": "off",
                "flight_dir": args.flight_dir,
                "flight_interval_s": 0.5, "version": version,
                # per-tenant admission for the burst drill: the bursty
                # tenant gets a tight pending quota + bottom priority,
                # the steady tenant a high priority class
                "tenant_policies": {
                    "burst": {"max_pending": 2, "priority": 0},
                    "steady": {"priority": 5}}}

    def _spawn_child(cfg):
        path = os.path.join(cfg_dir, f"{cfg['id']}.json")
        with open(path, "w") as f:
            json.dump(cfg, f)
        p = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--replica-config", path],
            stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT)
        children[cfg["id"]] = p
        return p

    def spawn(rid, version):
        # ElasticLaunch-style: the controller holds the Popen token and
        # the replica joins through the rendezvous store
        return _spawn_child(_cfg_for(rid, version=version))

    def spawn_heldout(rid, version):
        # canary: NO rendezvous record (held out of rotation — discovery
        # can't find it), but it DOES heartbeat, so once RollingUpdate
        # promotes it via add_replica the router's liveness verdict
        # holds; fixed RPC port, dialed directly once it answers ping
        port = _free_port()
        _spawn_child(_cfg_for(rid, version=version, port=port,
                              heldout=True))
        deadline = time.monotonic() + 600
        while True:
            try:
                c = RpcClient("127.0.0.1", port, timeout=5.0)
                c.request("ping", {})
                c.close()
                break
            except Exception:   # noqa: BLE001 — still booting
                if children[rid].poll() is not None:
                    raise RuntimeError(
                        f"held-out replica {rid} exited "
                        f"rc={children[rid].returncode}")
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.2)
        return RemoteReplica(rid, "127.0.0.1", port, role="both",
                             version=version)

    def _evictions():
        m = default_registry().get("router_evictions_total")
        return float(m.value) if m is not None else 0.0

    try:
        router = Router(store=store)
        obs = ClusterObserver(router, trace_dir=args.trace_dir)
        router.attach_observer(obs)
        ctrl = AutoscaleController(router, spawn, min_replicas=1,
                                   max_replicas=max(n_top, 4),
                                   version="v1")
        t0 = time.perf_counter()
        ctrl.spawn_replica("r0", version="v1")
        if not ctrl.wait_live(1, timeout_s=600):
            report["error"] = "seed replica never joined"
            return _router_report(report, args, 1)
        report["boot_s"] = round(time.perf_counter() - t0, 3)

        dense = [(name,) + ZOO_FEEDS[name] for name in names]
        traffic = _BgTraffic(router, dense, args.decode, seq_buckets,
                             args.max_new, clients=args.clients,
                             seed=args.seed, tenant="steady").start()
        if args.sessions:
            # conversations run through EVERY leg — ramp, drain-down,
            # rollout, the mid-rollout SIGKILL — and the exit gate
            # demands none of them were lost or answered differently
            sess_traffic = _SessionTraffic(
                router, "gpt_decode", seq_buckets, args.max_new,
                clients=max(2, args.clients // 2),
                seed=args.seed + 31).start()

        # -- tenant admission: control window, then a burst window ------
        tc0 = time.time()
        time.sleep(args.duration)
        tc1 = time.time()
        burst_router = Router(store=store)   # the burst tenant's own
        burst = _BgTraffic(burst_router, dense, args.decode, seq_buckets,
                           args.max_new, clients=max(4, args.clients),
                           seed=args.seed + 1000, tenant="burst",
                           timeout=max(8.0, args.duration),
                           count_rejections=True).start()
        tb0 = time.time()
        time.sleep(args.duration)
        tb1 = time.time()
        burst.stop()
        burst_router.close()
        burst_router = None
        p99_ctrl = traffic.p99_ms(tc0, tc1)
        p99_burst = traffic.p99_ms(tb0, tb1)
        report["tenant"] = {
            "steady_p99_ms_control": p99_ctrl,
            "steady_p99_ms_under_burst": p99_burst,
            "burst_p99_ms": burst.p99_ms(tb0, tb1),
            "burst_rejections": burst.rejections,
            "burst_completed": len(burst.latencies),
            "burst_errors": burst.errors}
        if burst.errors:
            rc = 1
        if p99_ctrl is not None and p99_burst is not None \
                and p99_burst > max(10.0 * p99_ctrl, p99_ctrl + 2000.0):
            report["tenant"]["isolation_violated"] = True
            rc = 1

        # -- ramp 1 -> N -> 1 under traffic ------------------------------
        ev0 = _evictions()
        up0 = time.perf_counter()
        ctrl.scale_to(n_top, version="v1")
        if not ctrl.wait_live(n_top, timeout_s=600):
            report["error"] = f"never reached {n_top} live replicas"
            return _router_report(report, args, 1)
        report["ramp_up_s"] = round(time.perf_counter() - up0, 3)
        time.sleep(args.duration)        # sustain at N
        down0 = time.perf_counter()
        ctrl.scale_to(1)
        report["ramp_down_s"] = round(time.perf_counter() - down0, 3)
        retires = [d for d in ctrl.decisions
                   if d.get("action") == "retire"]
        report["scale_down"] = [
            {"replica": d.get("replica"),
             "drained": d.get("drained"),
             "duration_s": d.get("duration_s"),
             "escalated": d.get("escalated")} for d in retires]
        report["scale_down_evictions"] = _evictions() - ev0
        if len(retires) != n_top - 1 \
                or not all(d.get("drained") for d in retires) \
                or report["scale_down_evictions"]:
            rc = 1

        # -- rolling update legs -----------------------------------------
        if args.rollout:
            ctrl.scale_to(2, version="v1")
            ctrl.wait_live(2, timeout_s=600)
            rng = np.random.RandomState(12345)
            canary_reqs = []
            for name, specs, vocab in dense:
                canary_reqs.append(
                    {"op": "infer", "model": name,
                     "inputs": _random_inputs(rng, specs, 1, vocab)})
            if args.decode:
                canary_reqs.append(
                    {"op": "decode", "model": "gpt_decode",
                     "prompts": [rng.randint(1, 128, 6)],
                     "max_new": args.max_new})
            journal = os.path.join(cfg_dir, "rollout.json")
            ru = RollingUpdate(ctrl, spawn_heldout, canary_reqs,
                               journal_path=journal)
            out = ru.run("v2", wait_live_s=600)
            out["versions"] = sorted(h.version for h in router.handles()
                                     if h.alive)
            report["rollout"] = out
            if out.get("rolled_back") \
                    or out["versions"] != ["v2"] * len(out["versions"]):
                rc = 1

            if args.rollout_kill:
                # mid-rollout SIGKILL: once the v3 canary is promoted
                # (journal says so), the old replica that would be
                # replaced LAST dies hard; the rollout must finish, the
                # journal must stay consistent, traffic must not error
                victim = max(h.id for h in router.handles() if h.alive)
                def _killer():
                    deadline = time.monotonic() + 600
                    while time.monotonic() < deadline:
                        try:
                            with open(journal) as f:
                                if json.load(f).get("promoted"):
                                    break
                        except (OSError, ValueError):
                            pass
                        time.sleep(0.05)
                    p = children.get(victim)
                    if p is not None and p.poll() is None:
                        p.send_signal(signal.SIGKILL)
                kt = threading.Thread(target=_killer, daemon=True)
                kt.start()
                out = RollingUpdate(ctrl, spawn_heldout, canary_reqs,
                                    journal_path=journal).run(
                                        "v3", wait_live_s=600)
                kt.join(timeout=30)
                with open(journal) as f:
                    jstate = json.load(f)
                out["victim"] = victim
                out["journal"] = jstate
                out["versions"] = sorted(
                    h.version for h in router.handles() if h.alive)
                if args.flight_dir:
                    pm = os.path.join(args.flight_dir,
                                      f"postmortem_{victim}.json")
                    out["postmortem_exists"] = os.path.exists(pm)
                    if not out["postmortem_exists"]:
                        rc = 1
                report["rollout_kill"] = out
                if out.get("rolled_back") or not jstate.get("done") \
                        or victim not in jstate.get("replaced", ()) \
                        or set(out["versions"]) != {"v3"}:
                    rc = 1

            # forced rollback: the canary_mismatch fault clause fires in
            # THIS process (the comparison runs router-side), the canary
            # must die before rotation and the old version keep serving
            prev = sorted(h.version for h in router.handles() if h.alive)
            _faults.install_plan(_faults.FaultPlan.parse("canary_mismatch:"))
            try:
                out = RollingUpdate(ctrl, spawn_heldout, canary_reqs,
                                    journal_path=journal).run(
                                        "v9", wait_live_s=600)
            finally:
                _faults.clear_plan()
            out["versions"] = sorted(h.version for h in router.handles()
                                     if h.alive)
            report["rollback"] = out
            if not out.get("rolled_back") or out["versions"] != prev:
                rc = 1
            ctrl.scale_to(1)

        if sess_traffic is not None:
            sess_traffic.stop()
            report["sessions"] = sess_traffic.report()
            rc = _gate_sessions(report, args, rc)
            if args.rollout_kill and report["sessions"]["lost_sessions"]:
                report["sessions"]["kill_lost_sessions"] = True
        traffic.stop()
        report["traffic_errors"] = traffic.errors
        report["traffic_completed"] = len(traffic.latencies)
        if traffic.errors or not traffic.latencies:
            rc = 1

        steady_total = 0
        for h in router.handles():
            if not h.alive:
                continue
            try:
                hl = h.health()
                report["replica_stats"][h.id] = h.model_stats()
            except Exception as e:   # noqa: BLE001 — reported, gated
                report["replica_stats"][h.id] = \
                    {"error": f"{type(e).__name__}: {e}"}
                rc = 1
                continue
            steady_total += int(hl.get("steady_compiles", 0))
        report["steady_compiles"] = steady_total
        if steady_total:
            rc = 1
        report["decisions"] = ctrl.decisions
        report["router_stats"] = router.stats()
        sig = obs.poll()
        report["cluster_signals"] = sig.to_dict()
    finally:
        if sess_traffic is not None:
            sess_traffic.stop()
        if traffic is not None:
            traffic.stop()
        if burst_router is not None:
            burst_router.close()
        if obs is not None:
            obs.close()
        if router is not None:
            router.close()
        for p in children.values():
            if p.poll() is None:
                p.terminate()
        for p in children.values():
            try:
                p.wait(timeout=10)
            except Exception:   # noqa: BLE001 — last resort
                p.kill()
        store.close()
    return _router_report(report, args, rc)


def _router_report(report, args, rc):
    report["rc"] = rc
    if args.as_json:
        print(json.dumps(report, indent=1))
    else:
        for rid, st in report.get("replica_stats", {}).items():
            if "error" in st:
                print(f"{rid:>10}: ERROR {st['error']}")
                continue
            for name, m in st.items():
                print(f"{rid:>10} {name:>12}: {m['qps']:>8.1f} qps  "
                      f"p50 {m['p50_ms']:>8.2f} ms  "
                      f"p99 {m['p99_ms']:>8.2f} ms  "
                      f"completed {m['completed']}")
        if "sessions" in report:
            s = report["sessions"]
            print(f"sessions: {s['turns']} turns "
                  f"({s['follow_ups']} follow-ups / "
                  f"{s['conversations']} conversations), "
                  f"lost {s['lost_sessions']}, verified "
                  f"{s['verified_turns']} (mismatches "
                  f"{s['bit_mismatches']}), p99 {s['p99_ms']} ms")
        print(f"router: {report.get('router_stats', {}).get('replicas_live')}"
              f" live, steady compiles {report.get('steady_compiles')} "
              f"(must be 0), rc={rc}")
    return rc


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="serve",
        description="export zoo models, warm the serving engine, drive "
                    "sustained traffic, report QPS/p50/p99 + the "
                    "zero-steady-state-recompile check")
    ap.add_argument("--model", action="append", choices=sorted(ZOO),
                    help="serve one zoo model (repeatable; default: all "
                         "dense models, or none under --decode)")
    ap.add_argument("--decode", action="store_true",
                    help="additionally serve a GPT autoregressive-decode "
                         "model (KV-cache generate through the bucketed "
                         "prefill/decode executables) and drive mixed "
                         "prompt-length decode traffic at it")
    ap.add_argument("--max-new", type=int, default=4,
                    help="decode model: max generated tokens per request")
    ap.add_argument("--seq-buckets", default="8,16",
                    help="decode model: prompt-length bucket ladder")
    ap.add_argument("--int8", action="store_true",
                    help="serve frozen int8 exports (PTQ + freeze)")
    ap.add_argument("--duration", type=float, default=2.0,
                    help="seconds of sustained traffic per run")
    ap.add_argument("--clients", type=int, default=4,
                    help="concurrent client threads")
    ap.add_argument("--workers", type=int, default=None,
                    help="serving worker threads (default: flag)")
    ap.add_argument("--buckets", default="1,2,4",
                    help="batch bucket ladder, e.g. '1,2,4,8'")
    ap.add_argument("--max-request-rows", type=int, default=2,
                    help="clients submit 1..N rows per request")
    ap.add_argument("--p99-slo-ms", type=float, default=None,
                    help="fail (rc!=0) when any model's p99 exceeds this")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve GET /metrics (Prometheus text) from a "
                         "stdlib http endpoint on this port while "
                         "traffic runs (0 = ephemeral; the bound port "
                         "lands in the report).  The report records a "
                         "self-scrape so CI can gate on exposition "
                         "health without its own scraper.  Under "
                         "--router this is the FEDERATED cluster "
                         "endpoint: replica-labeled families + "
                         "cluster_* rollups")
    ap.add_argument("--metrics-textfile", default=None, metavar="PATH",
                    help="atomically write the final Prometheus "
                         "exposition to PATH (textfile-collector "
                         "convention — scrape-less CI; the federated "
                         "cluster exposition under --router)")
    ap.add_argument("--trace-dir", default=None, metavar="DIR",
                    help="stream request spans as LogWriter JSONL into "
                         "DIR (sets FLAGS_trace=full unless FLAGS_trace "
                         "/ PADDLE_TPU_TRACE already enabled a mode); "
                         "join with tools/obs_report.py.  Under "
                         "--router the replicas ship their spans to the "
                         "router over the scrape RPC and DIR holds ONE "
                         "merged skew-corrected cluster trace "
                         "(obs_report.py --cluster)")
    ap.add_argument("--flight-dir", default=None, metavar="DIR",
                    help="under --router: arm every replica's flight "
                         "recorder (FLAGS_flight_dir) so each process "
                         "keeps an atomically-rewritten "
                         "postmortem_<id>.json of its recent spans / "
                         "compile ledger / metrics; with --kill-one the "
                         "report records the SIGKILL victim's artifact "
                         "(read it with obs_report.py --postmortem)")
    ap.add_argument("--cache-dir", default=None, metavar="DIR",
                    help="persistent executable cache: warm-up loads "
                         "serialized executables from DIR instead of "
                         "compiling, and stores what it compiles "
                         "(FLAGS_executable_cache=readwrite + "
                         "FLAGS_executable_cache_dir).  The report "
                         "gains exec_cache hit/miss tallies and a "
                         "warm-up compile-kind census — a warm boot "
                         "shows warmup_fresh_compiles == 0")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit one JSON report instead of text")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--router", action="store_true",
                    help="cluster mode: spawn --replicas serving "
                         "subprocesses behind the front-end Router "
                         "(TCPStore rendezvous + heartbeat eviction) "
                         "and drive the traffic through it; rc gates "
                         "additionally on per-replica steady compiles "
                         "and (with --kill-one) the eviction drill")
    ap.add_argument("--replicas", type=int, default=None,
                    help="replica subprocess count under --router "
                         "(default: FLAGS_serving_replicas)")
    ap.add_argument("--disaggregate", action="store_true",
                    help="under --router --decode: split replicas into "
                         "prefill/decode worker pools; decode requests "
                         "route prefill-pool → KV handoff → decode-pool")
    ap.add_argument("--kill-one", action="store_true", dest="kill_one",
                    help="under --router: SIGKILL one replica "
                         "mid-traffic and require heartbeat eviction + "
                         "traffic redistribution (rc!=0 otherwise)")
    ap.add_argument("--ramp", type=int, default=None, metavar="N",
                    help="elastic-lifecycle drill: boot ONE replica, "
                         "start sustained traffic that never stops, "
                         "scale 1 -> N -> 1 through the autoscaling "
                         "controller (scale-down is graceful drain), "
                         "and run a tenant-burst admission window; rc "
                         "gates on zero client errors, zero steady "
                         "compiles, every retirement drained (no "
                         "eviction), and tenant isolation")
    ap.add_argument("--rollout", action="store_true",
                    help="under --ramp: add zero-downtime rolling-"
                         "update legs at scale 2 — canary bit-match "
                         "gate then replica-by-replica replacement, "
                         "plus a fault-forced canary rollback that "
                         "must leave the old version serving")
    ap.add_argument("--rollout-kill", action="store_true",
                    dest="rollout_kill",
                    help="under --ramp --rollout: SIGKILL one old "
                         "replica mid-rollout (after canary "
                         "promotion); the rollout must still converge, "
                         "the journal stay consistent, and the victim "
                         "leave a flight-recorder postmortem")
    ap.add_argument("--sessions", action="store_true",
                    help="stateful multi-turn traffic (needs --decode): "
                         "clients grow conversations under stable "
                         "session_ids through the prefix/session KV "
                         "cache (FLAGS_session_store + "
                         "FLAGS_prefix_cache + the slot decode loop), "
                         "and a sampled oracle re-submits each "
                         "transcript statelessly, demanding "
                         "bit-identical output.  rc additionally "
                         "gates on zero lost sessions / mismatches; "
                         "under --ramp --rollout-kill this is the "
                         "stateful SIGKILL drill — parked sessions "
                         "spill to a fleet-shared dir and must "
                         "survive the victim")
    ap.add_argument("--replica-config", default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.replica_config:
        return _replica_child(args.replica_config)
    if args.sessions and not args.decode:
        print("--sessions needs --decode", file=sys.stderr)
        return 2
    if args.sessions and args.disaggregate:
        print("--sessions needs unified replicas (the slot decode loop "
              "is per-replica); drop --disaggregate", file=sys.stderr)
        return 2
    if args.ramp is not None:
        return _ramp_main(args)
    if args.router:
        return _router_main(args)

    from paddle_tpu import serving
    from paddle_tpu.framework.flags import flags_restore, flags_snapshot, \
        set_flags

    names = list(dict.fromkeys(
        args.model or ([] if args.decode else sorted(ZOO))))
    buckets = tuple(int(b) for b in args.buckets.split(",") if b.strip())
    seq_buckets = tuple(int(b) for b in args.seq_buckets.split(",")
                        if b.strip())
    snap = flags_snapshot()
    report = {"int8": args.int8, "buckets": list(buckets),
              "duration_s": args.duration, "clients": args.clients,
              "models": {}}
    rc = 0
    metrics_srv = None
    try:
        if args.int8:
            set_flags({"FLAGS_use_int8_inference": True})
        if args.sessions:
            from paddle_tpu.framework.flags import flag as _flag
            sess_dir = tempfile.mkdtemp(prefix="serve_sessions_")
            report["sessions_dir"] = sess_dir
            sess_flags = {"FLAGS_session_store": True,
                          "FLAGS_session_store_dir": sess_dir,
                          "FLAGS_prefix_cache": True}
            if not int(_flag("decode_slots")):
                sess_flags["FLAGS_decode_slots"] = 4
            set_flags(sess_flags)
        if args.trace_dir:
            from paddle_tpu.framework.flags import flag as _flag
            from paddle_tpu.profiler import tracing as _tracing
            if str(_flag("trace")).lower() == "off":
                set_flags({"FLAGS_trace": "full"})
            _tracing.set_trace_dir(args.trace_dir)
            report["trace_dir"] = args.trace_dir
            report["trace_mode"] = str(_flag("trace")).lower()
        if args.metrics_port is not None:
            from paddle_tpu.profiler.metrics import serve_metrics
            metrics_srv = serve_metrics(port=args.metrics_port)
            report["metrics_port"] = metrics_srv.port
        if args.cache_dir:
            os.makedirs(args.cache_dir, exist_ok=True)
            set_flags({"FLAGS_executable_cache": "readwrite",
                       "FLAGS_executable_cache_dir": args.cache_dir})
            report["cache_dir"] = args.cache_dir
        with tempfile.TemporaryDirectory() as d:
            # deterministic builds: the exported program (and so the
            # cache identity and the served outputs) must match across
            # cold/warm runs of this CLI
            import paddle_tpu as _paddle
            _paddle.seed(args.seed)
            server = serving.Server(serving.ServingConfig(
                workers=args.workers, buckets=buckets))
            model_meta = {}
            for name in names:
                layer, specs = ZOO[name]()
                layer.eval()
                if args.int8:
                    import paddle_tpu as paddle
                    from paddle_tpu.quantization import \
                        PostTrainingQuantization
                    rng = np.random.RandomState(args.seed)
                    cal = _random_inputs(rng, specs, buckets[0],
                                         getattr(layer, "_serve_vocab",
                                                 None))

                    def loader():
                        for _ in range(4):
                            yield tuple(paddle.to_tensor(a) for a in cal)

                    PostTrainingQuantization(model=layer,
                                             data_loader=loader(),
                                             batch_nums=4).quantize()
                prefix = os.path.join(d, name)
                manifest = serving.export_for_serving(
                    layer, prefix, specs, buckets=buckets, int8=args.int8)
                server.register(name, prefix, buckets=buckets)
                model_meta[name] = (specs,
                                    getattr(layer, "_serve_vocab", None),
                                    manifest["mode"])
            if args.decode:
                gpt = build_gpt_decode()
                server.register_decode(
                    "gpt_decode", gpt, batch_buckets=buckets,
                    seq_buckets=seq_buckets, max_new_tokens=args.max_new,
                    max_len=max(seq_buckets) + args.max_new)
            t0 = time.perf_counter()
            server.start()
            warmup_s = round(time.perf_counter() - t0, 3)
            if args.cache_dir:
                # warm-up compile census: a warm boot over a filled
                # cache dir must show ONLY cache_load events (zero
                # fresh XLA compiles) at the server-owned sites
                from collections import Counter
                from paddle_tpu.jit import persistent_cache as _pcache
                from paddle_tpu.profiler import ledger as _pledger
                kinds = Counter()
                for site, mark in server._warmup_marks.items():
                    for e in _pledger.compile_events(site)[:mark]:
                        kinds[e.get("kind", "?")] += 1
                report["exec_cache"] = _pcache.stats()
                report["warmup_compile_kinds"] = dict(kinds)
                report["warmup_fresh_compiles"] = sum(
                    n for k, n in kinds.items() if k != "cache_load")
            if args.decode:
                strf = None
                if args.sessions:
                    # the stateful clients run ALONGSIDE the one-shot
                    # traffic: restores and plain prefills share slots
                    strf = _SessionTraffic(
                        server, "gpt_decode", seq_buckets, args.max_new,
                        clients=args.clients, seed=args.seed + 31,
                        vocab=gpt._serve_vocab).start()
                errors = _decode_traffic(
                    server, "gpt_decode", args.duration, args.clients,
                    args.max_request_rows, max(seq_buckets),
                    args.max_new, gpt._serve_vocab, args.seed)
                st = server.stats("gpt_decode")
                st["export_mode"] = "live_layer"
                st["traffic_errors"] = errors
                if errors or st["errors"]:
                    rc = 1
                if strf is not None:
                    strf.stop()
                    sess = strf.report()
                    sl = server.stats("gpt_decode").get("slot_loop") or {}
                    for k in ("restored", "parked", "prefix_hit_tokens"):
                        sess[k] = sl.get(k)
                    report["sessions"] = sess
                    rc = _gate_sessions(report, args, rc)
                    if not sess.get("restored"):
                        # mixed-mode without a single KV restore means
                        # the session plane silently never engaged
                        sess["restore_never_engaged"] = True
                        rc = 1
                if args.p99_slo_ms is not None:
                    st["p99_slo_ms"] = args.p99_slo_ms
                    st["slo_met"] = st["p99_ms"] <= args.p99_slo_ms
                    if not st["slo_met"]:
                        rc = 1
                report["models"]["gpt_decode"] = st
            for name in names:
                specs, vocab, mode = model_meta[name]
                errors = _traffic(server, name, specs, args.duration,
                                  args.clients, args.max_request_rows,
                                  vocab, args.seed)
                st = server.stats(name)
                st["export_mode"] = mode
                st["traffic_errors"] = errors
                if errors or st["errors"]:
                    rc = 1
                if args.p99_slo_ms is not None:
                    st["p99_slo_ms"] = args.p99_slo_ms
                    st["slo_met"] = st["p99_ms"] <= args.p99_slo_ms
                    if not st["slo_met"]:
                        rc = 1
                report["models"][name] = st
            server.stop()
            steady = server.compile_events_since_warmup()
            report["warmup_s"] = warmup_s
            report["steady_compiles"] = len(steady)
            if steady:
                rc = 1
                report["steady_compile_events"] = [
                    {"site": e["site"], "kind": e.get("kind"),
                     "diff": e["diff"]} for e in steady[:8]]
            if metrics_srv is not None:
                # self-scrape: the endpoint must serve parseable
                # Prometheus text while the process is still up
                import urllib.request
                try:
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{metrics_srv.port}/metrics",
                            timeout=10) as resp:
                        body = resp.read().decode()
                    report["metrics_scrape_ok"] = (
                        resp.status == 200
                        and "serving_queue_wait_seconds_bucket" in body)
                except Exception as e:   # noqa: BLE001 — reported, gated
                    report["metrics_scrape_ok"] = False
                    report["metrics_scrape_error"] = \
                        f"{type(e).__name__}: {e}"
                if not report["metrics_scrape_ok"]:
                    rc = 1
            if args.metrics_textfile:
                from paddle_tpu.profiler.metrics import write_textfile
                report["metrics_textfile"] = \
                    write_textfile(args.metrics_textfile)
    finally:
        if metrics_srv is not None:
            metrics_srv.close()
        if args.trace_dir:
            from paddle_tpu.profiler import tracing as _tracing
            _tracing.set_trace_dir(None)
        flags_restore(snap)

    if args.as_json:
        print(json.dumps(report, indent=1))
    else:
        for name, st in report["models"].items():
            print(f"{name:>14}: {st['qps']:>8.1f} qps  "
                  f"p50 {st['p50_ms']:>8.2f} ms  "
                  f"p99 {st['p99_ms']:>8.2f} ms  "
                  f"batches {st['batches']}  "
                  f"avg rows {st['avg_batch_rows']}  "
                  f"[{st['backend']}/{st['export_mode']}]")
        if "sessions" in report:
            s = report["sessions"]
            print(f"      sessions: {s['turns']} turns "
                  f"({s['follow_ups']} follow-ups), restored "
                  f"{s.get('restored')}, parked {s.get('parked')}, "
                  f"prefix-hit tokens {s.get('prefix_hit_tokens')}, "
                  f"lost {s['lost_sessions']}, mismatches "
                  f"{s['bit_mismatches']}")
        print(f"serve: warm-up {report['warmup_s']}s, steady-state "
              f"compiles {report['steady_compiles']} (must be 0), rc={rc}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
