"""Persistent on-disk AOT executable cache: startup is O(load), not O(compile).

Warm-up cost in this framework scales with grid size — serving compiles
(batch-buckets × seq-buckets) executables per model per process, decode
adds a prefill grid plus a decode grid (speculative adds a second pair),
the HLO audit adds one more compile per signature, and at pod scale every
host repeats identical work.  This module makes each of those compiles a
one-time event per CLUSTER instead of per process: compiled executables
are serialized (``jax.experimental.serialize_executable``) into a shared
directory, keyed so that a load can never silently substitute a different
program, and every fresh-compile path the recompile ledger already
instruments consults the cache first —

  * ``@to_static`` dispatch (``jit.StaticFunction.__call__``),
  * the static ``Executor`` (both the legacy per-predictor
    ``set_aot_cache_dir`` seat and the global flag),
  * ``TrainStep.aot_compile`` (and through it every HLO-audit lowering),
  * serving warm-up: the dense bucket grid (``_ModelRuntime.warmup``) and
    the decode/speculative grids (``text.generation.Generator._compile``).

Key discipline (what makes a load safe):

  * the caller's **ledger labeled-leaf cache key** — the exact key the
    recompile ledger diffs (PR 1), so the manifest stays human-readable
    and the graph-lint ``cache-key-hygiene`` pass can reason about entry
    churn in the same vocabulary;
  * an **extra identity key** per call site — the Executor's AOT digest
    (program ops + attr values + IO signature, PR 4), the serving
    artifact's serialized-StableHLO hash, the Generator's architecture
    identity (config + state avals), or the TrainStep's lowered-HLO
    sha256 — whatever pins *which program* the key names across process
    restarts;
  * the **runtime fingerprint** — jax/jaxlib versions, backend platform
    and version, device kind, device and process counts — a jaxlib
    upgrade or a different topology can never replay a stale executable;
  * the **lowering flags** — every FLAGS_* value that changes what a
    given program lowers to (Pallas kernels, KV-cache dtype, int8
    inference, sentinel, speculative gamma).

Entry layout under ``FLAGS_executable_cache_dir``::

    <digest>.pjrt   pickled (blob, in_tree, out_tree, device_ids): what
                    serialize() returns plus the ids of the devices the
                    executable was compiled for, in assignment order
    <digest>.json   manifest: sha256 of the payload + key/kind/site/
                    fingerprint provenance + hit count

Writes use the checkpoint subsystem's atomic discipline (same-dir temp →
flush → fsync → ``os.replace`` → dir fsync, ``checkpoint.atomic``), and
the manifest is committed only AFTER its payload — a torn write leaves a
payload with no manifest (ignored) or nothing, never a loadable lie.
The loader re-hashes the payload against the manifest before
deserializing; any mismatch (truncation, bit rot, a poisoned entry)
counts as an invalidation, deletes the entry, and falls back to
compile-and-store.  Serialization failures (backends without executable
serialization) degrade the same way: compile proceeds, nothing caches.

Gating: ``FLAGS_executable_cache`` off|read|readwrite (env
``PADDLE_TPU_EXEC_CACHE``) + ``FLAGS_executable_cache_dir``
(``PADDLE_TPU_EXEC_CACHE_DIR``); the off-path is one Python branch per
fresh compile and nothing per steady-state step.  ``read`` lets N hosts
load from a dir one ``readwrite`` host fills.  Loads are ledgered as a
new ``cache_load`` kind at the caller's site, so
``assert_zero_steady_state_recompiles()`` and the tracing auto-attach
keep working unchanged — a warm start shows a full grid of
``cache_load`` events and ZERO fresh XLA compiles.
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..framework import flags as _flags
from ..profiler import ledger as _ledger
from ..profiler.metrics import default_registry as _registry

__all__ = [
    "ExecutableCache", "enabled", "mode", "cache_dir", "cache_at",
    "get_cache", "digest_for", "load_or_compile", "runtime_fingerprint",
    "lowering_flags", "stats", "reset_stats",
]

_PAYLOAD_SUFFIX = ".pjrt"
_MANIFEST_SUFFIX = ".json"

# typed metrics (docs/METRICS.md inventory): cache effectiveness and the
# load-vs-compile time split the startup bench quantifies
_HITS = _registry().counter(
    "exec_cache_hits_total",
    "Persistent-executable-cache loads that replaced a fresh XLA "
    "compile, by ledger kind of the avoided compile.",
    labels=("kind",))
_MISSES = _registry().counter(
    "exec_cache_misses_total",
    "Persistent-executable-cache probes that fell through to a fresh "
    "XLA compile, by ledger kind.",
    labels=("kind",))
_INVALIDATIONS = _registry().counter(
    "exec_cache_invalidations_total",
    "Cache entries rejected at load time (checksum mismatch, torn or "
    "unreadable manifest, deserialization failure) — each one fell "
    "back to compile-and-store.",
    labels=("reason",))
_LOAD_SECONDS = _registry().histogram(
    "exec_cache_load_seconds",
    "Wall seconds to verify + deserialize one cached executable (the "
    "warm-start replacement for its XLA compile).")

# plain process-local tallies for cheap report embedding (tools/serve.py,
# bench startup block) — the typed counters above are the durable surface
_TALLY = {"hits": 0, "misses": 0, "invalidations": 0, "stores": 0}


def stats() -> Dict[str, int]:
    """Process-local hit/miss/invalidation/store tallies (reports)."""
    return dict(_TALLY)


def note_hit(kind: str, seconds: float) -> None:
    """Metric bumps for a verified load (sites that cannot route through
    :func:`load_or_compile` — the Executor owns its own ledger timing)."""
    _HITS.labels(kind=kind).inc()
    _TALLY["hits"] += 1
    _LOAD_SECONDS.observe(seconds)


def note_miss(kind: str) -> None:
    _MISSES.labels(kind=kind).inc()
    _TALLY["misses"] += 1


def reset_stats() -> None:
    for k in _TALLY:
        _TALLY[k] = 0


# ---------------------------------------------------------------------------
# Gating + key material
# ---------------------------------------------------------------------------

def mode() -> str:
    try:
        return str(_flags.flag("executable_cache")).lower()
    except KeyError:
        return "off"


def cache_dir() -> str:
    try:
        return str(_flags.flag("executable_cache_dir") or "")
    except KeyError:
        return ""


def enabled() -> bool:
    """One-branch off-path: the flag is off or no dir is configured."""
    return mode() in ("read", "readwrite") and bool(cache_dir())


def runtime_fingerprint() -> Tuple[str, ...]:
    """Device/topology + toolchain identity folded into every digest: a
    jaxlib/XLA upgrade, a different backend, device kind or count, or a
    different process count invalidates by construction."""
    import jax
    import jaxlib
    devs = jax.devices()
    d0 = devs[0]
    return (
        "jax=" + jax.__version__,
        "jaxlib=" + getattr(jaxlib.version, "__version__", "?"),
        "backend=" + jax.default_backend(),
        "platform_version=" + str(
            getattr(d0.client, "platform_version", "")),
        "device_kind=" + str(getattr(d0, "device_kind", "")),
        "n_devices=" + str(len(devs)),
        "n_processes=" + str(jax.process_count()),
    )


# FLAGS that change what a given program LOWERS to: two processes with
# different values must never share an executable.  Flags that only
# change host-side behavior (serving knobs, trace/lint modes) stay out —
# including them would fragment the cache for identical programs.
_LOWERING_FLAGS = (
    "use_pallas_kernels", "use_pallas_fused_bn", "use_pallas_fused_conv",
    "kv_cache_dtype", "use_int8_inference",
    "train_sentinel", "spec_decode", "spec_gamma", "static_executor_mode",
    "wide_deep_device_dedup",
)


def lowering_flags() -> Tuple[Tuple[str, str], ...]:
    out = []
    for name in _LOWERING_FLAGS:
        try:
            out.append((name, repr(_flags.flag(name))))
        except KeyError:
            pass
    return tuple(out)


def digest_for(key: Any, extra_key: Any = None) -> str:
    """sha256 entry digest over (ledger key, per-site identity key,
    runtime fingerprint, lowering flags)."""
    h = hashlib.sha256()
    for part in (key, extra_key, runtime_fingerprint(), lowering_flags()):
        h.update(repr(part).encode())
        h.update(b"\x1f")
    return h.hexdigest()


# ---------------------------------------------------------------------------
# The on-disk cache
# ---------------------------------------------------------------------------

class ExecutableCache:
    """One cache directory: verified load / atomic store / listing / GC.

    All methods are best-effort against filesystem races (concurrent
    cold-starting processes sharing one dir): a load that loses a race
    is a miss, a store that loses one is a no-op (``os.replace`` keeps
    whichever writer finished last — both wrote the same program).
    """

    def __init__(self, directory: str):
        self.dir = os.path.abspath(directory)

    def _payload(self, digest: str) -> str:
        return os.path.join(self.dir, digest + _PAYLOAD_SUFFIX)

    def _manifest(self, digest: str) -> str:
        return os.path.join(self.dir, digest + _MANIFEST_SUFFIX)

    # -- load ----------------------------------------------------------------
    def _read_manifest(self, digest: str) -> Optional[dict]:
        try:
            with open(self._manifest(digest)) as f:
                m = json.load(f)
            if not isinstance(m, dict) or "sha256" not in m:
                return None
            return m
        except (OSError, ValueError):
            return None

    def _invalidate(self, digest: str, reason: str) -> None:
        _INVALIDATIONS.labels(reason=reason).inc()
        _TALLY["invalidations"] += 1
        for p in (self._payload(digest), self._manifest(digest)):
            try:
                os.unlink(p)
            except OSError:
                pass

    def load(self, digest: str):
        """Verified load: manifest present, payload sha256 matches, blob
        deserializes — anything else is a miss (corrupt entries are
        invalidated so the subsequent compile-and-store heals them).
        Returns the loaded ``jax.stages.Compiled`` or None."""
        path = self._payload(digest)
        if not os.path.exists(path):
            return None
        m = self._read_manifest(digest)
        if m is None:
            # payload with no (readable) manifest: a writer died between
            # the two commits, or the manifest itself is torn
            self._invalidate(digest, "manifest")
            return None
        from ..checkpoint.atomic import sha256_file
        try:
            actual = sha256_file(path)
        except OSError:
            return None
        if actual != m["sha256"]:
            self._invalidate(digest, "checksum")
            return None
        try:
            from jax.experimental.serialize_executable import (
                deserialize_and_load)
            import jax
            with open(path, "rb") as f:
                blob, in_tree, out_tree, device_ids = pickle.load(f)
            # load onto the executable's own devices: the default is ALL
            # local devices, which breaks a one-device executable on a
            # multi-device host at its first call
            by_id = {d.id: d for d in jax.devices()}
            compiled = deserialize_and_load(
                blob, in_tree, out_tree,
                execution_devices=[by_id[i] for i in device_ids])
        except Exception:
            # jaxlib moved underneath the fingerprint, or the pickle is
            # subtly poisoned: heal by recompiling
            self._invalidate(digest, "deserialize")
            return None
        self._touch(digest, m)
        return compiled

    def _touch(self, digest: str, manifest: dict) -> None:
        """Bump the hit count + last-used stamp (best-effort: the CLI's
        listing and age-based GC read these; a lost update is harmless)."""
        try:
            manifest = dict(manifest)
            manifest["hits"] = int(manifest.get("hits", 0)) + 1
            manifest["last_used"] = time.time()
            from ..checkpoint.atomic import atomic_write_bytes
            atomic_write_bytes(self._manifest(digest),
                               json.dumps(manifest).encode(),
                               durable=False)
        except Exception:
            pass

    # -- store ---------------------------------------------------------------
    def store(self, digest: str, compiled, *, key: Any = None,
              site: Optional[str] = None, kind: Optional[str] = None,
              extra_key: Any = None) -> bool:
        """Serialize + commit one executable; payload first, manifest
        second, both atomic — returns False (and caches nothing) when
        the backend cannot serialize."""
        try:
            from jax.experimental.serialize_executable import serialize
            blob, in_tree, out_tree = serialize(compiled)
            device_ids = [d.id for d in compiled._executable
                          ._unloaded_executable.device_list]
            payload = pickle.dumps((blob, in_tree, out_tree, device_ids),
                                   protocol=4)
        except Exception:
            return False            # unsupported backend: compile-only
        from ..checkpoint.atomic import atomic_write_bytes
        try:
            sha = atomic_write_bytes(self._payload(digest), payload)
            manifest = {
                "sha256": sha, "size": len(payload),
                "key": repr(key), "extra_key": repr(extra_key),
                "site": site, "kind": kind,
                "created": time.time(), "last_used": time.time(),
                "hits": 0,
                "fingerprint": list(runtime_fingerprint()),
                "lowering_flags": [list(kv) for kv in lowering_flags()],
            }
            atomic_write_bytes(self._manifest(digest),
                               json.dumps(manifest, indent=1).encode())
        except OSError:
            return False
        _TALLY["stores"] += 1
        self._auto_gc()
        return True

    def _auto_gc(self) -> None:
        try:
            cap_gb = float(_flags.flag("executable_cache_max_gb"))
        except KeyError:
            cap_gb = 0.0
        if cap_gb > 0:
            self.gc(max_bytes=int(cap_gb * (1 << 30)))

    # -- introspection + GC (tools/exec_cache.py) ----------------------------
    def entries(self) -> List[dict]:
        """Manifest rows (digest, size, age, hits, key, kind, site),
        newest-created first; unreadable manifests are skipped."""
        out = []
        try:
            names = os.listdir(self.dir)
        except OSError:
            return out
        now = time.time()
        for n in sorted(names):
            if not n.endswith(_MANIFEST_SUFFIX):
                continue
            digest = n[:-len(_MANIFEST_SUFFIX)]
            m = self._read_manifest(digest)
            if m is None:
                continue
            m["digest"] = digest
            m["age_s"] = round(now - float(m.get("created", now)), 1)
            out.append(m)
        out.sort(key=lambda m: -float(m.get("created", 0)))
        return out

    def verify_entry(self, digest: str) -> Tuple[bool, str]:
        """(ok, reason) without loading: manifest readable, payload
        present, sha256 matches."""
        m = self._read_manifest(digest)
        if m is None:
            return False, "manifest missing/unreadable"
        path = self._payload(digest)
        if not os.path.exists(path):
            return False, "payload missing"
        from ..checkpoint.atomic import sha256_file
        if sha256_file(path) != m["sha256"]:
            return False, "checksum mismatch"
        return True, "ok"

    def total_bytes(self) -> int:
        total = 0
        try:
            for n in os.listdir(self.dir):
                if n.endswith(_PAYLOAD_SUFFIX):
                    total += os.path.getsize(os.path.join(self.dir, n))
        except OSError:
            pass
        return total

    def gc(self, max_bytes: Optional[int] = None,
           max_age_s: Optional[float] = None) -> List[str]:
        """Evict entries past ``max_age_s`` (by last use), then the
        least-recently-used until the payload total fits ``max_bytes``.
        Returns evicted digests.  Orphan payloads (no manifest — a dead
        writer's debris) always go."""
        removed = []
        rows = self.entries()
        now = time.time()
        alive = []
        for m in rows:
            if max_age_s is not None and \
                    now - float(m.get("last_used", m.get("created", now))) \
                    > max_age_s:
                self._invalidate(m["digest"], "gc_age")
                removed.append(m["digest"])
            else:
                alive.append(m)
        # orphan payloads: a manifest-less .pjrt is never loadable
        try:
            known = {m["digest"] for m in rows}
            for n in os.listdir(self.dir):
                if n.endswith(_PAYLOAD_SUFFIX) \
                        and n[:-len(_PAYLOAD_SUFFIX)] not in known:
                    os.unlink(os.path.join(self.dir, n))
        except OSError:
            pass
        if max_bytes is not None:
            alive.sort(key=lambda m: float(
                m.get("last_used", m.get("created", 0))))
            total = self.total_bytes()
            for m in alive:
                if total <= max_bytes:
                    break
                total -= int(m.get("size", 0))
                self._invalidate(m["digest"], "gc_size")
                removed.append(m["digest"])
        return removed


# one ExecutableCache per directory (the Executor's legacy per-predictor
# optim-cache dirs and the global flag dir coexist)
_CACHES: Dict[str, ExecutableCache] = {}


def cache_at(directory: str) -> ExecutableCache:
    d = os.path.abspath(directory)
    c = _CACHES.get(d)
    if c is None:
        c = _CACHES[d] = ExecutableCache(d)
    return c


def get_cache() -> Optional[ExecutableCache]:
    """The flag-configured cache, or None when disabled."""
    if not enabled():
        return None
    return cache_at(cache_dir())


# ---------------------------------------------------------------------------
# The one integration helper every compile path calls
# ---------------------------------------------------------------------------

def load_or_compile(lower: Callable[[], Any], *, site: str, kind: str,
                    key: Any, extra_key: Any = None,
                    extra: Optional[dict] = None,
                    ledger_miss: bool = True,
                    cache: Optional[ExecutableCache] = None,
                    writable: Optional[bool] = None,
                    events: Optional[list] = None,
                    hlo_text: Optional[Callable[[], Any]] = None):
    """Consult the cache, else compile (and store under readwrite).

    ``lower`` runs the cold path: () -> ``jax.stages.Compiled``.  On a
    verified hit the load is ledgered at ``site`` as kind ``cache_load``
    (the steady-state-recompile checks and span auto-attach see it like
    any compile event); on a miss the fresh compile is ledgered under
    the caller's ``kind`` unless ``ledger_miss=False`` (sites that never
    ledgered their AOT compiles, e.g. ``TrainStep.aot_compile``, keep
    that contract).  Returns ``(compiled, loaded)``.

    ``cache``/``writable`` override the flag-configured cache — the
    Executor's legacy per-predictor optim-cache dir passes its own.
    ``events``, a list, receives the ledger event this call records (a
    caller that learns more about the program later adds it there).
    ``hlo_text`` goes to that event as it is
    (``profiler.ledger.record_compile``).
    """
    def ledger(kind_, ms, extra_):
        ev = _ledger.record_compile(site, kind_, key, ms, extra=extra_,
                                    hlo_text=hlo_text)
        if events is not None:
            events.append(ev)

    c = cache if cache is not None else get_cache()
    if c is None:                      # the one off-path branch
        t0 = time.perf_counter()
        compiled = lower()
        if ledger_miss:
            ledger(kind, (time.perf_counter() - t0) * 1e3, extra)
        return compiled, False
    digest = digest_for(key, extra_key)
    t0 = time.perf_counter()
    loaded = c.load(digest)
    if loaded is not None:
        dt = time.perf_counter() - t0
        note_hit(kind, dt)
        ex = dict(extra or {})
        ex.update({"orig_kind": kind, "digest": digest[:16]})
        ledger("cache_load", dt * 1e3, ex)
        return loaded, True
    note_miss(kind)
    t0 = time.perf_counter()
    compiled = lower()
    if ledger_miss:
        ledger(kind, (time.perf_counter() - t0) * 1e3, extra)
    w = writable if writable is not None else (mode() == "readwrite")
    if w:
        c.store(digest, compiled, key=key, site=site, kind=kind,
                extra_key=extra_key)
    return compiled, False
