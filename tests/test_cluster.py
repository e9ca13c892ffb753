"""Multi-host disaggregated serving (serving/cluster): router dispatch /
backoff / eviction, prefill/decode worker pools, sharded replicas, the
RPC layer, retry-after backpressure hints, and the cluster flags."""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.distributed.fleet.base.tcp_store import TCPStore
from paddle_tpu.distributed.fleet.elastic import HeartbeatMonitor
from paddle_tpu.framework.enforce import (PreconditionNotMetError,
                                          UnavailableError)
from paddle_tpu.framework.flags import define_flag, flag, flags_restore, \
    flags_snapshot, set_flags
from paddle_tpu.profiler import ledger
from paddle_tpu.profiler.metrics import default_registry
from paddle_tpu.serving.cluster import (LocalReplica, RemoteReplica,
                                        Replica, ReplicaHandle, Router,
                                        RpcClient, RpcError, RpcServer)
from paddle_tpu.serving.scheduler import Request, RequestQueue
from paddle_tpu.text.generation import Generator
from paddle_tpu.text.models.gpt import GPTConfig, GPTModel

V = 64


def _gpt(seed=21, hidden=32, heads=2):
    paddle.seed(seed)
    m = GPTModel(GPTConfig.tiny(vocab_size=V, hidden_size=hidden, layers=2,
                                heads=heads, seq=64))
    m.eval()
    return m


def _decode_server(steps=4, seed=21, seq=(8, 16), **kw):
    srv = serving.Server(serving.ServingConfig(workers=1))
    srv.register_decode("gpt", _gpt(seed), batch_buckets=(1, 2),
                        seq_buckets=seq, max_new_tokens=steps,
                        max_len=32, **kw)
    return srv.start()


_ORACLES = {}


def _oracle_tokens(prompts, steps=4, seed=21):
    # one compiled oracle per seed for the whole module — repeat calls
    # are ledgered cache hits, not fresh grids
    oracle = _ORACLES.get(seed)
    if oracle is None:
        oracle = _ORACLES[seed] = Generator(_gpt(seed),
                                            seq_buckets=(8, 16),
                                            max_len=32)
    return np.concatenate(
        [np.asarray(oracle.generate(p[None, :], max_new_tokens=steps))
         for p in prompts], axis=0)


def _prompts(rng, lens):
    return [rng.randint(1, V, int(n)).astype(np.int32) for n in lens]


@pytest.fixture(scope="module")
def two_servers():
    """Two started decode servers shared by every routed test in the
    module (warm-up grids compile once; tests only read/serve)."""
    a, b = _decode_server(), _decode_server()
    yield a, b
    a.stop()
    b.stop()


# ---------------------------------------------------------------------------
# retry-after backpressure hint (satellite 1)
# ---------------------------------------------------------------------------

def test_queue_rejection_carries_retry_after_hint():
    q = RequestQueue(capacity=1)
    q.put(Request(model="m", inputs=(), rows=1))
    with pytest.raises(UnavailableError) as ei:
        q.put(Request(model="m", inputs=(), rows=1), timeout=0.01)
    assert isinstance(ei.value.retry_after_s, float)
    assert 0.01 <= ei.value.retry_after_s <= 5.0
    # a closed queue is gone, not busy: no hint
    q.close()
    with pytest.raises(UnavailableError) as ei:
        q.put(Request(model="m", inputs=(), rows=1), timeout=0.01)
    assert ei.value.retry_after_s is None


def test_queue_hint_tracks_drain_rate():
    q = RequestQueue(capacity=4)
    assert q.suggest_retry_after() == pytest.approx(0.1)  # nothing drained
    for _ in range(3):
        q.put(Request(model="m", inputs=(), rows=1))
        q.next_batch(lambda m: 8, lambda m, r: 8, 0.0)
        time.sleep(0.01)
    hint = q.suggest_retry_after()
    assert 0.01 <= hint <= 5.0


def test_server_submit_honors_rejection_accounting(two_servers):
    """A backpressure rejection propagates the hint AND is accounted:
    the request's error counter bumps and its trace span closes."""
    srv = two_servers[0]
    rt = srv._models["gpt"]
    before = rt.counters["errors"]

    def full_put(req, timeout=None):
        raise UnavailableError("queue full", retry_after_s=0.25)

    srv._queue.put, orig = full_put, srv._queue.put
    try:
        with pytest.raises(UnavailableError) as ei:
            srv.submit_decode("gpt", [np.array([1, 2], np.int32)])
        assert ei.value.retry_after_s == 0.25
    finally:
        srv._queue.put = orig
    assert rt.counters["errors"] == before + 1


# ---------------------------------------------------------------------------
# RPC layer
# ---------------------------------------------------------------------------

def test_rpc_roundtrip_arrays_and_error_taxonomy():
    from paddle_tpu.serving.cluster.rpc import decode_arrays, encode_arrays

    def echo(meta, parts):
        return {"echo": meta["x"], "arrays": meta.get("arrays", [])}, \
            list(parts)

    def reject(meta, parts):
        raise UnavailableError("busy", retry_after_s=0.5)

    server = RpcServer({"echo": echo, "reject": reject})
    try:
        client = RpcClient("127.0.0.1", server.port)
        a = np.arange(12, dtype=np.float32).reshape(3, 4)
        ameta, parts = encode_arrays([a])
        meta, rparts = client.request("echo", {"x": 1, "arrays": ameta},
                                      parts)
        assert meta["echo"] == 1
        assert np.array_equal(decode_arrays(meta["arrays"], rparts)[0], a)
        # UNAVAILABLE crosses the wire as UnavailableError + hint
        with pytest.raises(UnavailableError) as ei:
            client.request("reject", {})
        assert ei.value.retry_after_s == 0.5
        # unknown op is an RpcError, connection survives
        with pytest.raises(RpcError):
            client.request("nope", {})
        meta, _ = client.request("echo", {"x": 2})
        assert meta["echo"] == 2
        client.close()
    finally:
        server.close()


# ---------------------------------------------------------------------------
# router dispatch policy
# ---------------------------------------------------------------------------

class _FakeReplica(ReplicaHandle):
    def __init__(self, rid, fail=(), role="both"):
        super().__init__(rid, role)
        self.calls = 0
        self._fail = list(fail)

    def submit_decode(self, model, prompts, max_new=None, trace_id=None,
                      timeout=60.0, tenant="default", priority=None):
        self.calls += 1
        if self._fail:
            raise self._fail.pop(0)
        return np.full((len(prompts), 2), ord(self.id[0]), np.int32)

    def health(self):
        return {"id": self.id, "queue_depth": self.queue_depth}


def test_router_backs_off_on_retry_after_instead_of_evicting():
    busy = _FakeReplica("a", fail=[UnavailableError("full",
                                                    retry_after_s=30.0)])
    calm = _FakeReplica("b")
    r = Router(replicas=(busy, calm))
    try:
        out = r.run_decode("m", [np.array([1], np.int32)])[0]
        assert out[0, 0] == ord("b")
        assert busy.alive and busy.backoff_until > time.monotonic()
        assert calm.calls == 1
        # while 'a' backs off, traffic keeps flowing to 'b'
        r.run_decode("m", [np.array([1], np.int32)])
        assert calm.calls == 2 and busy.calls == 1
    finally:
        r.close()


def test_router_waits_out_backoff_when_no_alternative():
    flaky = _FakeReplica("a", fail=[UnavailableError("full",
                                                     retry_after_s=0.1)])
    r = Router(replicas=(flaky,))
    try:
        t0 = time.monotonic()
        out = r.run_decode("m", [np.array([1], np.int32)], timeout=5.0)[0]
        assert out[0, 0] == ord("a") and flaky.calls == 2
        assert time.monotonic() - t0 >= 0.1
    finally:
        r.close()


def test_router_redispatches_on_transport_error_nothing_lost():
    dead = _FakeReplica("a", fail=[ConnectionError("boom")])
    live = _FakeReplica("b")
    r = Router(replicas=(dead, live))
    try:
        out = r.run_decode("m", [np.array([1], np.int32)])[0]
        assert out[0, 0] == ord("b")          # re-dispatched, not lost
        assert dead.backoff_until > time.monotonic()   # suspect
    finally:
        r.close()


def test_router_least_loaded_prefers_idle_replica():
    a, b = _FakeReplica("a"), _FakeReplica("b")
    r = Router(replicas=(a, b))
    try:
        with a._lock:
            a.inflight = 5                    # busy
        r.run_decode("m", [np.array([1], np.int32)])
        assert b.calls == 1 and a.calls == 0
    finally:
        r.close()


def test_router_no_live_replica_raises_unavailable():
    r = Router(replicas=())
    try:
        with pytest.raises(UnavailableError):
            r.run_decode("m", [np.array([1], np.int32)], timeout=0.2)
    finally:
        r.close()


# ---------------------------------------------------------------------------
# routed serving over real local replicas
# ---------------------------------------------------------------------------

def test_routed_decode_bit_matches_single_replica(two_servers):
    srv_a, srv_b = two_servers
    r = Router(replicas=(LocalReplica(srv_a, "a"),
                         LocalReplica(srv_b, "b")))
    reg = default_registry()
    dispatch = reg.get("router_dispatch_total")
    try:
        # both servers share the ledger site in-process: steady state is
        # "no compile events at all past the second warm-up"
        warmed = len(ledger.compile_events("serving:gpt"))
        rng = np.random.RandomState(5)
        futs, wants = [], []
        for _ in range(6):
            prompts = _prompts(rng, rng.randint(1, 16, rng.randint(1, 3)))
            futs.append(r.submit_decode("gpt", prompts, max_new_tokens=4))
            wants.append(_oracle_tokens(prompts))
        for fut, want in zip(futs, wants):
            assert np.array_equal(fut.result(timeout=120)[0], want)
        assert len(ledger.compile_events("serving:gpt")) == warmed
        srv_b.assert_zero_steady_state_recompiles()
        # both replicas took traffic and the counters saw it
        per = {h.id: h.dispatched for h in r.handles()}
        assert sum(per.values()) == 6
        assert dispatch.labels("a").value + dispatch.labels("b").value >= 6
    finally:
        r.close()


def test_disaggregated_pools_bit_match_and_grid_split():
    """Role-split pools: the prefill replica warms ONLY the prefill
    grid, the decode replica ONLY the decode grid, and a routed decode
    (prefill → handoff → decode across the pools) still bit-matches
    the in-process generate() control."""
    snap = flags_snapshot()
    try:
        ledger.clear()
        set_flags({"FLAGS_serving_role": "prefill"})
        pre = _decode_server()
        kinds_pre = {e["kind"] for e in ledger.compile_events("serving:gpt")}
        ledger.clear()
        set_flags({"FLAGS_serving_role": "decode"})
        dec = _decode_server()
        kinds_dec = {e["kind"] for e in ledger.compile_events("serving:gpt")}
        assert kinds_pre == {"generate_prefill"}
        assert kinds_dec == {"generate_decode"}
        # a pool replica refuses full decode requests up front
        with pytest.raises(PreconditionNotMetError):
            pre.submit_decode("gpt", [np.array([1], np.int32)])
        r = Router(replicas=(LocalReplica(pre, "pre", role="prefill"),
                             LocalReplica(dec, "dec", role="decode")))
        try:
            warmed = len(ledger.compile_events("serving:gpt"))
            rng = np.random.RandomState(7)
            prompts = _prompts(rng, (5, 11))
            toks = r.run_decode("gpt", prompts, max_new_tokens=4)[0]
            assert np.array_equal(toks, _oracle_tokens(prompts))
            # shared in-process ledger site: steady state is "no new
            # compile events past the second pool's warm-up"
            assert len(ledger.compile_events("serving:gpt")) == warmed
            dec.assert_zero_steady_state_recompiles()
        finally:
            r.close()
            pre.stop()
            dec.stop()
    finally:
        flags_restore(snap)


def test_trace_id_propagates_router_to_replica(two_servers):
    from paddle_tpu.profiler import tracing
    snap = flags_snapshot()
    srv = two_servers[0]
    try:
        set_flags({"FLAGS_trace": "full"})
        tracing.clear()
        r = Router(replicas=(LocalReplica(srv, "a"),))
        try:
            r.run_decode("gpt", [np.array([1, 2, 3], np.int32)],
                         max_new_tokens=2)
        finally:
            r.close()
        spans = tracing.finished_spans()
        routes = [s for s in spans if s["name"] == "route"]
        requests = [s for s in spans if s["name"] == "request"]
        assert routes and requests
        assert requests[-1]["trace_id"] == routes[-1]["trace_id"]
        names = {s["name"] for s in spans
                 if s["trace_id"] == routes[-1]["trace_id"]}
        assert "dispatch" in names        # the router's child span
    finally:
        flags_restore(snap)


# ---------------------------------------------------------------------------
# store rendezvous + heartbeat eviction (RPC replicas, in-process)
# ---------------------------------------------------------------------------

def test_rendezvous_join_dispatch_and_heartbeat_evict(two_servers):
    snap = flags_snapshot()
    store = TCPStore("127.0.0.1", 0, world_size=1, is_master=True)
    reps, r = [], None
    try:
        set_flags({"FLAGS_router_heartbeat_s": 0.2})
        for rid, srv in zip(("a", "b"), two_servers):
            reps.append(Replica(srv, replica_id=rid, store=store).start())
        r = Router(store=store, stale_after_s=1.2, watch=False)
        r.poll()
        assert r.replicas_live() == 2
        assert all(isinstance(h, RemoteReplica) for h in r.handles())
        rng = np.random.RandomState(9)
        prompts = _prompts(rng, (5, 9))
        toks = r.run_decode("gpt", prompts, max_new_tokens=4)[0]
        assert np.array_equal(toks, _oracle_tokens(prompts))
        # silence replica b's heartbeat (its process "died")
        evictions = default_registry().get("router_evictions_total")
        before = evictions.value
        reps[1]._reporter.stop()
        reps[1]._rpc.close()
        deadline = time.monotonic() + 10
        while r.replicas_live() > 1 and time.monotonic() < deadline:
            time.sleep(0.2)
            r.poll()
        assert r.replicas_live() == 1
        assert evictions.value == before + 1
        # traffic redistributes to the survivor; nothing is lost
        toks = r.run_decode("gpt", prompts, max_new_tokens=4)[0]
        assert np.array_equal(toks, _oracle_tokens(prompts))
    finally:
        if r is not None:
            r.close()
        for rep in reps:
            # close the RPC endpoints + reporters only: the module
            # servers are shared and keep serving
            if rep._reporter is not None:
                rep._reporter.stop()
            if rep._rpc is not None:
                rep._rpc.close()
        store.close()
        flags_restore(snap)


def test_rejoin_same_id_updates_endpoint(two_servers):
    store = TCPStore("127.0.0.1", 0, world_size=1, is_master=True)
    srv = two_servers[0]
    r = None
    try:
        rep1 = Replica(srv, replica_id="a", store=store).start()
        r = Router(store=store, watch=False)
        r.poll()
        assert r.replicas_live() == 1
        first = r.handles()[0]
        # the "restarted" replica re-registers under the same id
        rep1._rpc.close()
        rep2 = Replica(srv, replica_id="a", store=store).start()
        r.poll()
        assert r.replicas_live() == 1          # rejoined, not twinned
        current = [h for h in r.handles() if h.alive]
        assert len(current) == 1
        assert current[0].port == rep2.port != first.port
        rep2._reporter.stop()
        rep2._rpc.close()
        rep1._reporter.stop()
    finally:
        if r is not None:
            r.close()
        store.close()


def test_heartbeat_monitor_watches_arbitrary_ids():
    store = TCPStore("127.0.0.1", 0, world_size=1, is_master=True)
    try:
        mon = HeartbeatMonitor(store, stale_after=5.0,
                               ranks=["replica:x", "replica:y"])
        assert mon.watched() == ["replica:x", "replica:y"]
        store.set("__hb/replica:x", repr(time.time()).encode())
        assert mon.stale_ranks() == ["replica:y"]
        mon.set_ranks(["replica:x"])
        assert mon.stale_ranks() == []
    finally:
        store.close()


# ---------------------------------------------------------------------------
# sharded replicas
# ---------------------------------------------------------------------------

def _mesh(axes):
    from paddle_tpu.parallel.mesh import make_mesh
    return make_mesh(axes)


def test_sharded_decode_replica_matches_control():
    """A decode model sharded dp4×mp2 by the autoshard transformer
    rules serves the same tokens as the unsharded control, with the KV
    planes pinned to the cluster layout and zero steady recompiles;
    ledger keys carry the mesh label so sharded/unsharded grids never
    collide."""
    snap = flags_snapshot()
    try:
        set_flags({"FLAGS_hlo_audit": "warn"})   # admission audit runs
        mesh = _mesh({"dp": 4, "mp": 2})
        ledger.clear()
        srv = serving.Server(serving.ServingConfig(workers=1))
        # 16 heads of 16: the ring planes pack 8 heads per lane row, so
        # axis 1 holds 2 head GROUPS, which mp=2 divides (the module's
        # 2-head model packs into ONE group and would replicate)
        model = _gpt(hidden=256, heads=16)
        srv.register_decode("gpt", model, batch_buckets=(1, 2),
                            seq_buckets=(8,), max_new_tokens=4,
                            max_len=16, mesh=mesh)
        srv.start()
        try:
            keys = [str(e["key"])
                    for e in ledger.compile_events("serving:gpt")]
            assert keys and all("arg:mesh" in k and "dp4xmp2" in k
                                for k in keys)
            rng = np.random.RandomState(11)
            prompts = _prompts(rng, (5, 7))
            out = srv.run_decode("gpt", prompts, max_new_tokens=4)[0]
            control = Generator(model, seq_buckets=(8, 16), max_len=32)
            want = np.concatenate(
                [np.asarray(control.generate(p[None], max_new_tokens=4)
                            .numpy()) for p in prompts])
            assert np.array_equal(out, want)
            # KV planes carry the pinned head-groups-by-mp layout
            h = srv.prefill_handoff("gpt", prompts, 4)
            assert tuple(h.cache[0][0].shape)[1::2] == (2, 128)
            assert "mp" in str(h.cache[0][0].sharding.spec)
            got = srv.decode_from_handoff("gpt", h.to_bytes())
            assert np.array_equal(got, out)
            srv.assert_zero_steady_state_recompiles()
        finally:
            srv.stop()
    finally:
        flags_restore(snap)


class _Mlp(paddle.nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc1 = paddle.nn.Linear(8, 16)
        self.fc2 = paddle.nn.Linear(16, 4)

    def forward(self, x):
        return self.fc2(paddle.nn.functional.relu(self.fc1(x)))


def _mlp_rules():
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.analysis.autoshard import PartitionRules, Rule
    return PartitionRules(
        [Rule(role="col", pattern=r"fc1\.weight$", spec=P(None, "mp"),
              ndim=2),
         Rule(role="row", pattern=r"fc2\.weight$", spec=P("mp", None),
              ndim=2)], name="mlp_test")


def test_sharded_dense_runtime_serves_and_audits():
    from paddle_tpu.serving.cluster import ShardedModelSpec
    snap = flags_snapshot()
    try:
        set_flags({"FLAGS_hlo_audit": "warn"})
        mesh = _mesh({"dp": 4, "mp": 2})
        paddle.seed(31)
        layer = _Mlp()
        paddle.seed(31)
        control = _Mlp()
        control.eval()
        ledger.clear()
        srv = serving.Server(serving.ServingConfig(workers=1))
        srv.register(ShardedModelSpec(
            name="mlp", layer=layer, input_specs=[([None, 8], "float32")],
            mesh=mesh, rules=_mlp_rules(), buckets=(1, 4)))
        srv.start()
        try:
            evs = ledger.compile_events("serving:mlp")
            assert {e["kind"] for e in evs} <= {"serving_aot",
                                               "cache_load"}
            assert len(evs) == 2                      # one per bucket
            rt = srv._models["mlp"]
            assert "mp" in str(rt.param_specs.get("fc1.weight"))
            rng = np.random.RandomState(13)
            x = rng.randn(3, 8).astype(np.float32)
            out = srv.run("mlp", [x])[0]
            want = np.asarray(control(paddle.to_tensor(x)).numpy())
            np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-6)
            srv.assert_zero_steady_state_recompiles()
        finally:
            srv.stop()
    finally:
        flags_restore(snap)


def test_shard_admission_audit_refuses_dropped_axes():
    """The containment contract: a compiled program whose input layout
    replicated a param the rules sharded is refused at admission."""
    import jax
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.serving.cluster import shard_admission_audit
    snap = flags_snapshot()
    try:
        set_flags({"FLAGS_hlo_audit": "warn"})
        mesh = _mesh({"dp": 4, "mp": 2})

        def f(params, x):
            return x @ params["w"]

        avals = ({"w": jax.ShapeDtypeStruct((8, 16), np.float32)},
                 jax.ShapeDtypeStruct((2, 8), np.float32))
        compiled = jax.jit(f).lower(*avals).compile()
        with pytest.raises(PreconditionNotMetError) as ei:
            shard_admission_audit(compiled, site="serving:t", mesh=mesh,
                                  param_specs={"w": P(None, "mp")},
                                  mesh_label="dp4xmp2")
        assert "lost its sharded axes" in str(ei.value)
        # audit off: one branch, no refusal
        set_flags({"FLAGS_hlo_audit": "off"})
        shard_admission_audit(compiled, site="serving:t", mesh=mesh,
                              param_specs={"w": P(None, "mp")})
    finally:
        flags_restore(snap)


# ---------------------------------------------------------------------------
# flags discipline (satellite 4)
# ---------------------------------------------------------------------------

def test_cluster_flags_validators_and_snapshot_restore():
    snap = flags_snapshot()
    try:
        for name, bad in (("FLAGS_serving_replicas", 0),
                          ("FLAGS_serving_role", "router"),
                          ("FLAGS_router_heartbeat_s", 0),
                          ("FLAGS_router_stale_after_s", -1),
                          ("FLAGS_router_retry_backoff_s", -0.5)):
            with pytest.raises(ValueError):
                set_flags({name: bad})
        set_flags({"FLAGS_serving_replicas": 4,
                   "FLAGS_serving_role": "prefill",
                   "FLAGS_router_heartbeat_s": 1.5,
                   "FLAGS_router_stale_after_s": 3.0,
                   "FLAGS_router_retry_backoff_s": 0.2})
        assert flag("serving_replicas") == 4
        assert flag("serving_role") == "prefill"
    finally:
        flags_restore(snap)
    assert flag("serving_role") == snap["serving_role"]
    assert flag("serving_replicas") == snap["serving_replicas"]


def test_cluster_flags_idempotent_reregistration():
    define_flag("serving_role", "both")            # same default: no-op
    with pytest.raises(ValueError):
        define_flag("serving_role", "prefill")     # different: loud
    define_flag("router_heartbeat_s", float(
        __import__("os").environ.get("PADDLE_TPU_ROUTER_HEARTBEAT_S",
                                     "2.0")))
