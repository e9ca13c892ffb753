"""Persistent executable cache tests (ISSUE 13, paddle_tpu.jit.
persistent_cache): digest discipline, atomic+checksummed entries with
poisoned-entry fallback, warm-start ZERO-fresh-compile acceptance across
every wired compile path (@to_static, Executor, TrainStep.aot_compile,
serving dense grid, Generator decode + speculative grids) with
bit-identical outputs vs a cold-compiled control, flags coverage, the
tools/exec_cache.py CLI, and a slow subprocess warm-load round trip
through tools/serve.py --cache-dir."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.framework.flags import (flags_restore, flags_snapshot,
                                        set_flags)
from paddle_tpu.jit import persistent_cache as pcache
from paddle_tpu.profiler import ledger

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def flags_guard():
    snap = flags_snapshot()
    yield
    flags_restore(snap)


@pytest.fixture()
def cache_dir(tmp_path, flags_guard):
    d = str(tmp_path / "exec_cache")
    os.makedirs(d)
    set_flags({"FLAGS_executable_cache": "readwrite",
               "FLAGS_executable_cache_dir": d})
    yield d


def _compile_tiny(mul=2.0):
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda x: jnp.tanh(x) * mul).lower(
        np.ones((4, 8), np.float32)).compile()


def _events_since(site, mark):
    return ledger.compile_events(site)[mark:]


# ---------------------------------------------------------------------------
# digest + entry format
# ---------------------------------------------------------------------------

def test_digest_stable_and_sensitive(flags_guard):
    k = (("arg:bucket", 4),)
    d0 = pcache.digest_for(k, extra_key=("m", "abc"))
    assert d0 == pcache.digest_for(k, extra_key=("m", "abc"))
    assert d0 != pcache.digest_for(k, extra_key=("m", "xyz"))
    assert d0 != pcache.digest_for((("arg:bucket", 8),),
                                   extra_key=("m", "abc"))
    # a lowering flag flip (kv cache dtype changes compiled programs)
    # must move EVERY digest — stale executables can never load
    set_flags({"FLAGS_kv_cache_dtype": "int8"})
    assert d0 != pcache.digest_for(k, extra_key=("m", "abc"))


def test_store_load_round_trip(cache_dir):
    import jax
    c = pcache.cache_at(cache_dir)
    compiled = _compile_tiny()
    digest = pcache.digest_for(("k",), extra_key="prog")
    assert c.store(digest, compiled, key=("k",), site="s", kind="test")
    # entry layout: payload + manifest, sha verified, no temp debris
    assert os.path.exists(os.path.join(cache_dir, digest + ".pjrt"))
    ok, reason = c.verify_entry(digest)
    assert ok, reason
    assert not [f for f in os.listdir(cache_dir) if ".tmp" in f]
    loaded = c.load(digest)
    assert loaded is not None
    x = np.random.RandomState(0).randn(4, 8).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(compiled(x)),
                                  np.asarray(loaded(x)))
    (m,) = [e for e in c.entries() if e["digest"] == digest]
    assert m["kind"] == "test" and m["site"] == "s" and m["hits"] == 1


def test_poisoned_entry_falls_back_to_compile_and_store(cache_dir):
    """A truncated/corrupted payload must NEVER load: checksum mismatch
    counts as an invalidation, deletes the entry, and load_or_compile
    heals it by compiling and re-storing (acceptance criterion)."""
    c = pcache.cache_at(cache_dir)
    digest = pcache.digest_for(("k2",), extra_key="prog2")
    c.store(digest, _compile_tiny(), key=("k2",), site="s", kind="test")
    payload = os.path.join(cache_dir, digest + ".pjrt")
    with open(payload, "r+b") as f:          # poison: truncate the blob
        f.truncate(os.path.getsize(payload) // 2)
    before = pcache.stats()
    assert c.load(digest) is None            # refused, not served corrupt
    after = pcache.stats()
    assert after["invalidations"] == before["invalidations"] + 1
    assert not os.path.exists(payload)       # entry removed
    # compile-and-store heals: the next load_or_compile round trips
    compiled, loaded = pcache.load_or_compile(
        _compile_tiny, site="test:poison", kind="test",
        key=("k2",), extra_key="prog2")
    assert not loaded
    x = np.ones((4, 8), np.float32)
    ok, reason = c.verify_entry(digest)
    assert ok, reason
    compiled2, loaded2 = pcache.load_or_compile(
        _compile_tiny, site="test:poison", kind="test",
        key=("k2",), extra_key="prog2")
    assert loaded2
    np.testing.assert_array_equal(np.asarray(compiled(x)),
                                  np.asarray(compiled2(x)))


def test_torn_manifest_is_a_miss(cache_dir):
    c = pcache.cache_at(cache_dir)
    digest = pcache.digest_for(("k3",), extra_key="prog3")
    c.store(digest, _compile_tiny(), key=("k3",), site="s", kind="test")
    with open(os.path.join(cache_dir, digest + ".json"), "w") as f:
        f.write("{ torn json")
    assert c.load(digest) is None


def test_read_mode_never_writes(cache_dir):
    set_flags({"FLAGS_executable_cache": "read"})
    compiled, loaded = pcache.load_or_compile(
        _compile_tiny, site="test:ro", kind="test", key=("ro",),
        extra_key="ro")
    assert not loaded
    assert not os.listdir(cache_dir)         # read mode stored nothing


def test_cache_load_is_ledgered(cache_dir):
    site = "test:ledgered"
    mark = len(ledger.compile_events(site))
    pcache.load_or_compile(_compile_tiny, site=site, kind="test",
                           key=("l",), extra_key="l")
    pcache.load_or_compile(_compile_tiny, site=site, kind="test",
                           key=("l",), extra_key="l")
    evs = _events_since(site, mark)
    assert [e["kind"] for e in evs] == ["test", "cache_load"]
    assert evs[1]["orig_kind"] == "test"     # the avoided compile kind
    assert "digest" in evs[1]


# ---------------------------------------------------------------------------
# warm-start acceptance: every wired compile path
# ---------------------------------------------------------------------------

def test_generator_warm_start_zero_fresh_compiles(cache_dir):
    """A fresh Generator over a filled cache loads its whole grid: all
    ledger events are kind cache_load, zero fresh XLA compiles, and the
    generated tokens are bit-identical to the cold-compiled control."""
    from paddle_tpu.text.generation import Generator
    from paddle_tpu.text.models.gpt import GPTConfig, GPTModel
    paddle.seed(7)
    m = GPTModel(GPTConfig.tiny(vocab_size=64, hidden_size=16, layers=1,
                                heads=2, seq=32))
    ids = np.random.RandomState(0).randint(1, 64, (2, 5))

    # cold-compiled control with the cache OFF
    set_flags({"FLAGS_executable_cache": "off"})
    control = np.asarray(Generator(
        m, site="generate:ec_ctl", seq_buckets=(8, 16),
        max_len=32).generate(paddle.to_tensor(ids), max_new_tokens=4))

    set_flags({"FLAGS_executable_cache": "readwrite"})
    g_cold = Generator(m, site="generate:ec_cold", seq_buckets=(8, 16),
                       max_len=32)
    out_cold = np.asarray(g_cold.generate(paddle.to_tensor(ids),
                                          max_new_tokens=4))
    kinds_cold = [e["kind"]
                  for e in ledger.compile_events("generate:ec_cold")]
    assert "generate_prefill" in kinds_cold \
        and "generate_decode" in kinds_cold

    g_warm = Generator(m, site="generate:ec_warm", seq_buckets=(8, 16),
                       max_len=32)
    out_warm = np.asarray(g_warm.generate(paddle.to_tensor(ids),
                                          max_new_tokens=4))
    kinds_warm = [e["kind"]
                  for e in ledger.compile_events("generate:ec_warm")]
    assert kinds_warm and all(k == "cache_load" for k in kinds_warm), \
        kinds_warm                                  # ZERO fresh compiles
    np.testing.assert_array_equal(out_cold, control)
    np.testing.assert_array_equal(out_warm, control)   # bit-identical


def test_speculative_warm_start_cache_load(cache_dir):
    """The speculative grid (joint spec_prefill + spec_decode programs)
    warm-loads too, bit-identical to its own cold run (which is itself
    bit-identical to greedy — PR 12's contract)."""
    from paddle_tpu.text.models.gpt import GPTConfig, GPTModel
    from paddle_tpu.text.speculative import SpeculativeGenerator
    paddle.seed(3)
    cfg = dict(vocab_size=32, hidden_size=16, layers=1, heads=2, seq=32)
    target = GPTModel(GPTConfig.tiny(**cfg))
    draft = GPTModel(GPTConfig.tiny(**cfg))
    ids = np.random.RandomState(1).randint(1, 32, (1, 4))

    g1 = SpeculativeGenerator(target, draft, site="generate:ec_spec1",
                              seq_buckets=(8, 16), max_len=32, gamma=2)
    out1 = np.asarray(g1.generate(paddle.to_tensor(ids),
                                  max_new_tokens=3))
    g2 = SpeculativeGenerator(target, draft, site="generate:ec_spec2",
                              seq_buckets=(8, 16), max_len=32, gamma=2)
    out2 = np.asarray(g2.generate(paddle.to_tensor(ids),
                                  max_new_tokens=3))
    kinds2 = [e["kind"]
              for e in ledger.compile_events("generate:ec_spec2")]
    assert kinds2 and all(k == "cache_load" for k in kinds2), kinds2
    np.testing.assert_array_equal(out1, out2)
    # a different gamma is a different program: never a false hit
    g3 = SpeculativeGenerator(target, draft, site="generate:ec_spec3",
                              seq_buckets=(8, 16), max_len=32, gamma=3)
    g3.generate(paddle.to_tensor(ids), max_new_tokens=3)
    kinds3 = [e["kind"]
              for e in ledger.compile_events("generate:ec_spec3")]
    assert any(k != "cache_load" for k in kinds3), kinds3


def test_serving_warm_start_zero_fresh_compiles(cache_dir, tmp_path):
    """A restarted Server over the same artifacts + cache dir loads its
    whole bucket grid (every warm-up event kind cache_load), serves
    bit-identical outputs, and the steady-state invariant holds."""
    from paddle_tpu import serving
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    net.eval()
    prefix = str(tmp_path / "m")
    serving.export_for_serving(net, prefix, [([None, 4], "float32")],
                               buckets=(1, 2))
    x = np.random.RandomState(0).randn(2, 4).astype("float32")

    def boot():
        srv = serving.Server(serving.ServingConfig(buckets=(1, 2),
                                                   workers=1))
        srv.register("m", prefix, buckets=(1, 2))
        srv.start()
        return srv

    srv1 = boot()
    mark = len(ledger.compile_events("serving:m"))
    out1 = srv1.run("m", [x])
    srv1.stop()
    srv2 = boot()
    warm = ledger.compile_events("serving:m")[mark:]
    assert warm and all(e["kind"] == "cache_load" for e in warm), \
        [e["kind"] for e in warm]
    out2 = srv2.run("m", [x])
    srv2.assert_zero_steady_state_recompiles()
    srv2.stop()
    np.testing.assert_array_equal(out1[0], out2[0])


def test_to_static_warm_start_and_backward(cache_dir):
    """A second StaticFunction over the same source loads its forward
    executable (kind cache_load), returns bit-identical values, and the
    backward still traces correctly through the seeded executable."""
    def build():
        @paddle.jit.to_static
        def f(x):
            return paddle.nn.functional.relu(x) * 3
        return f

    x = paddle.to_tensor(np.array([-2.0, 5.0], "float32"),
                         stop_gradient=False)
    f1 = build()
    y1 = f1(x)
    f2 = build()
    x2 = paddle.to_tensor(np.array([-2.0, 5.0], "float32"),
                          stop_gradient=False)
    y2 = f2(x2)
    np.testing.assert_array_equal(y1.numpy(), y2.numpy())
    site_evs = [e for e in ledger.compile_events()
                if e["site"].startswith("jit:")
                and "warm_start_and_backward" in e["site"]]
    assert [e["kind"] for e in site_evs] == ["jit", "cache_load"]
    y2.sum().backward()                      # grad through the warm exec
    np.testing.assert_allclose(x2.grad.numpy(), [0.0, 3.0])


def test_executor_global_flag_cache(cache_dir):
    """The static Executor consults the FLAGS-configured cache when no
    per-predictor optim dir is set: a second Executor over the same
    program loads (no new STAT_executor_compiles; event kind
    cache_load)."""
    from paddle_tpu.utils.monitor import stat_get
    import paddle_tpu.static as static
    paddle.enable_static()
    try:
        main, startup = static.Program(), static.Program()
        with static.program_guard(main, startup):
            x = static.data("x", [2, 4], "float32")
            out = static.nn.fc(x, 3)
        exe0 = static.Executor()
        exe0.run(startup)
        xd = np.random.RandomState(0).randn(2, 4).astype("float32")
        c0 = stat_get("STAT_executor_compiles")
        exe1 = static.Executor()
        r1 = exe1.run(main, feed={"x": xd}, fetch_list=[out])
        assert stat_get("STAT_executor_compiles") == c0 + 1
        exe2 = static.Executor()
        mark = len(ledger.compile_events(f"executor:{main._uid}"))
        r2 = exe2.run(main, feed={"x": xd}, fetch_list=[out])
        assert stat_get("STAT_executor_compiles") == c0 + 1   # loaded
        evs = ledger.compile_events(f"executor:{main._uid}")[mark:]
        assert [e["kind"] for e in evs] == ["cache_load"]
        np.testing.assert_array_equal(r1[0], r2[0])
    finally:
        paddle.disable_static()


def test_train_step_aot_compile_cached(cache_dir):
    """TrainStep.aot_compile (the HLO audit's lowering path) serves the
    XLA compile from the cache when a second step lowers to the same
    StableHLO; the loaded executable keeps the audit surface
    (as_text/cost_analysis/memory_analysis)."""
    from paddle_tpu.parallel.train_step import TrainStep

    def loss_fn(pred, label):
        return ((pred - label) ** 2).mean()

    def make_step():
        m = nn.Linear(8, 4)
        opt = paddle.optimizer.SGD(parameters=m.parameters(),
                                   learning_rate=0.1)
        return TrainStep(m, opt, loss_fn)

    x = np.random.RandomState(1).randn(8, 8).astype("float32")
    y = np.random.RandomState(2).randn(8, 4).astype("float32")
    make_step().aot_compile((x,), y)         # cold: compiles + stores
    ts2 = make_step()
    site = f"train_step:Linear:{id(ts2):#x}"
    mark = len(ledger.compile_events(site))
    c2 = ts2.aot_compile((x,), y)
    evs = ledger.compile_events(site)[mark:]
    assert [e["kind"] for e in evs] == ["cache_load"], \
        [e["kind"] for e in evs]
    assert c2.as_text() and c2.cost_analysis() is not None


@pytest.mark.parametrize("on_tpu", [False, True], ids=["xla", "fused"])
def test_train_step_event_counts_attention_forms(on_tpu, monkeypatch):
    """The ``train_step`` compile event's ``attention_form`` counts the
    un-cached attention call sites of the traced step by the form each
    took: a two-layer encoder has two, all XLA here, all fused where the
    dispatch sees a TPU (steered; the kernel interpreted)."""
    from paddle_tpu.nn.functional import attention as A
    from paddle_tpu.ops.pallas.flash_attention import MIN_SEQ_SINGLE_BLOCK
    from paddle_tpu.parallel.mesh import MeshGuard, make_mesh
    from paddle_tpu.parallel.train_step import TrainStep
    import jax
    monkeypatch.setattr(A, "_on_tpu", lambda: on_tpu)
    S = max(MIN_SEQ_SINGLE_BLOCK, 128)
    enc = nn.TransformerEncoder(
        nn.TransformerEncoderLayer(128, 2, 256, dropout=0.0), 2)
    opt = paddle.optimizer.SGD(parameters=enc.parameters(),
                               learning_rate=0.1)
    x = np.random.RandomState(1).randn(2, S, 128).astype("float32")
    mark = len(ledger.compile_events())
    # one device: a mesh of several keeps every site on the XLA path
    with MeshGuard(make_mesh({"dp": 1}, jax.devices()[:1])) as mesh:
        ts = TrainStep(enc, opt,
                       lambda pred, label: ((pred - label) ** 2).mean(),
                       mesh=mesh)
        ts(x, x)
        evs = [e for e in ledger.compile_events()[mark:]
               if e["kind"] == "train_step"]
        assert len(evs) == 1
        assert evs[0]["attention_form"] == (
            {"fused": 2, "xla": 0} if on_tpu else {"fused": 0, "xla": 2})
        ts(x, x)          # a step that compiles nothing counts nothing
        assert [e for e in ledger.compile_events()[mark:]
                if e["kind"] == "train_step"] == evs


# ---------------------------------------------------------------------------
# GC + CLI
# ---------------------------------------------------------------------------

def _fill(cache_dir, n):
    c = pcache.cache_at(cache_dir)
    digests = []
    for i in range(n):
        d = pcache.digest_for((f"gc{i}",), extra_key=i)
        c.store(d, _compile_tiny(1.0 + i), key=(f"gc{i}",),
                site="s", kind="test")
        digests.append(d)
    return c, digests


def test_gc_by_size_evicts_lru(cache_dir):
    c, digests = _fill(cache_dir, 3)
    c.load(digests[0])                        # most-recently-used
    one = os.path.getsize(os.path.join(cache_dir,
                                       digests[0] + ".pjrt"))
    removed = c.gc(max_bytes=2 * one + one // 2)
    assert removed and digests[0] not in removed   # LRU went, MRU stayed
    assert c.load(digests[0]) is not None


def test_gc_by_age_and_orphans(cache_dir):
    c, digests = _fill(cache_dir, 2)
    # age one entry far into the past
    mp = os.path.join(cache_dir, digests[0] + ".json")
    m = json.load(open(mp))
    m["last_used"] = m["created"] = 1.0
    with open(mp, "w") as f:
        json.dump(m, f)
    # and drop an orphan payload (a dead writer's debris)
    orphan = os.path.join(cache_dir, "f" * 64 + ".pjrt")
    with open(orphan, "wb") as f:
        f.write(b"junk")
    removed = c.gc(max_age_s=3600)
    assert digests[0] in removed and digests[1] not in removed
    assert not os.path.exists(orphan)


def test_auto_gc_on_store_respects_max_gb(cache_dir):
    set_flags({"FLAGS_executable_cache_max_gb": 32 / (1 << 30)})  # 32 B
    c, digests = _fill(cache_dir, 2)
    assert c.total_bytes() <= 32 or \
        len([f for f in os.listdir(cache_dir)
             if f.endswith(".pjrt")]) <= 1


def _cli(argv):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import exec_cache as tool
    finally:
        sys.path.pop(0)
    return tool


def test_cli_list_verify_gc(cache_dir, capsys):
    tool = _cli(None)
    c, digests = _fill(cache_dir, 2)
    assert tool.main(["list", "--dir", cache_dir, "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["entries"] == 2 and len(rep["rows"]) == 2
    assert {"digest", "kind", "size", "hits"} <= set(rep["rows"][0])
    assert tool.main(["verify", "--dir", cache_dir, "--json"]) == 0
    capsys.readouterr()
    # poison one payload: verify must fail loudly (rc != 0)
    p = os.path.join(cache_dir, digests[0] + ".pjrt")
    with open(p, "ab") as f:
        f.write(b"x")
    assert tool.main(["verify", "--dir", cache_dir, "--json"]) == 1
    rep = json.loads(capsys.readouterr().out)
    assert rep["corrupt"] and not rep["ok"]
    assert tool.main(["gc", "--dir", cache_dir, "--max-gb",
                      "0.000001"]) == 0     # ~1 KiB cap: evicts all
    capsys.readouterr()
    assert tool.main(["list", "--dir", cache_dir, "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["entries"] == 0 and rep["total_payload_bytes"] <= 1074


# ---------------------------------------------------------------------------
# flags discipline (satellite)
# ---------------------------------------------------------------------------

def test_exec_cache_flags_validators(flags_guard):
    set_flags({"FLAGS_executable_cache": "readwrite"})
    set_flags({"FLAGS_executable_cache": "off"})
    with pytest.raises(ValueError):
        set_flags({"FLAGS_executable_cache": "always"})
    set_flags({"FLAGS_executable_cache_max_gb": 2.5})
    with pytest.raises(ValueError):
        set_flags({"FLAGS_executable_cache_max_gb": -1})


def test_exec_cache_flags_idempotent_reregistration():
    from paddle_tpu.framework.flags import define_flag, flag
    define_flag("executable_cache_max_gb",
                float(os.environ.get("PADDLE_TPU_EXEC_CACHE_MAX_GB",
                                     "0") or 0), "doc")  # same default: ok
    with pytest.raises(ValueError):
        define_flag("executable_cache_max_gb", 7.0, "doc")


def test_exec_cache_flags_snapshot_restore(flags_guard):
    from paddle_tpu.framework.flags import flag
    snap = flags_snapshot()
    set_flags({"FLAGS_executable_cache": "read",
               "FLAGS_executable_cache_dir": "/tmp/somewhere"})
    assert pcache.mode() == "read" and pcache.enabled() is True
    flags_restore(snap)
    assert flag("executable_cache") == snap["executable_cache"]
    assert flag("executable_cache_dir") == snap["executable_cache_dir"]


def test_off_path_is_inert(flags_guard, tmp_path):
    """With the flag off (the tier-1 default), load_or_compile is a
    straight compile + ledger passthrough and touches no filesystem."""
    set_flags({"FLAGS_executable_cache": "off",
               "FLAGS_executable_cache_dir": str(tmp_path / "never")})
    site = "test:off"
    mark = len(ledger.compile_events(site))
    compiled, loaded = pcache.load_or_compile(
        _compile_tiny, site=site, kind="test", key=("off",))
    assert not loaded and not os.path.exists(str(tmp_path / "never"))
    assert [e["kind"] for e in ledger.compile_events(site)[mark:]] \
        == ["test"]


# ---------------------------------------------------------------------------
# slow subprocess smoke: the one-host-compiles / restart-loads story
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_serve_cli_warm_load_round_trip(tmp_path):
    """tools/serve.py --cache-dir twice (fresh process each time): the
    second boot loads EVERY zoo+decode executable (all warm-up ledger
    events kind cache_load, warmup_fresh_compiles == 0), serves with
    zero steady-state recompiles, and boots much faster — then
    tools/exec_cache.py verifies every manifest."""
    cache = str(tmp_path / "cache")

    def boot():
        p = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "serve.py"),
             "--model", "lenet", "--decode", "--duration", "0.3",
             "--clients", "2", "--buckets", "1,2",
             "--seq-buckets", "8,16", "--max-new", "4",
             "--cache-dir", cache, "--json"],
            capture_output=True, text=True, timeout=480,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
        return json.loads(p.stdout)

    cold = boot()
    assert cold["steady_compiles"] == 0
    assert cold["warmup_fresh_compiles"] > 0
    assert cold["exec_cache"]["stores"] == cold["warmup_fresh_compiles"]
    warm = boot()
    assert warm["steady_compiles"] == 0
    assert warm["warmup_fresh_compiles"] == 0          # O(load) startup
    assert set(warm["warmup_compile_kinds"]) == {"cache_load"}
    assert warm["exec_cache"]["hits"] \
        == cold["warmup_fresh_compiles"]
    assert warm["warmup_s"] < cold["warmup_s"]
    v = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "exec_cache.py"),
         "verify", "--dir", cache, "--json"],
        capture_output=True, text=True, timeout=120)
    assert v.returncode == 0, v.stdout + v.stderr
    assert json.loads(v.stdout)["ok"] is True
