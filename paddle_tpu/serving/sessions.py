"""Parked-session KV store: multi-turn conversations without re-prefill.

A multi-turn chat spends most of its life idle between turns.  Keeping
the conversation's ring-cache planes pinned in a decode slot for that
idle time wastes the scarcest resource (slot HBM); re-prefilling the
whole history on the next turn wastes the second scarcest (prefill
compute).  The session store takes the third road, the KVHandoff
discipline applied to conversations:

  * **park** — when a turn completes (or a replica drains), the slot
    loop pulls the row's valid columns ``[start, pos)`` to host RAM as a
    :class:`SessionSnapshot`: the token transcript, the resume payload
    (next-token logits for the plain loop, committed next token for the
    speculative loop), the remaining budget, and the raw KV planes
    (bf16 and int8+scales move as exact storage bytes).
  * **restore** — the next turn looks the session id up, pushes the
    snapshot's planes back into a joining row's validity window (the
    PR-7 relative-position invariance makes the columns bit-portable
    across slot rows and window shifts) and chunk-prefills only the NEW
    turn's tokens.  Decoding continues bit-identically to a full
    re-prefill of the whole history.
  * **spill** — with ``FLAGS_session_store_dir`` set, snapshots write to
    disk under the sha256-atomic-manifest discipline (PR 3/13):
    ``atomic_write_bytes`` + a manifest JSON recording the digest, so a
    torn write is detected (CheckpointCorrupt → treated as absent, the
    turn falls back to plain prefill) and a replica restarted after
    SIGKILL finds its parked sessions intact.  ``park_after_ms == 0``
    writes through at park time (the mode that survives SIGKILL);
    ``> 0`` keeps hot sessions in RAM and lazily spills the idle tail.

The store is the unit of migration too: ``export_bytes`` /
``import_bytes`` move a session between replicas through the Router
when the owner drains (cluster/router.py session affinity), and a
shared spill directory doubles as a zero-copy migration transport.
"""
from __future__ import annotations

import hashlib
import json
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

from ..checkpoint.atomic import (CheckpointCorruptError, atomic_write_bytes,
                                 sha256_file)
from ..profiler.metrics import default_registry as _registry
from .cluster.handoff import deserialize_session, serialize_session

__all__ = ["SessionSnapshot", "SessionStore"]

SESSION_PARK = _registry().counter(
    "session_park_total",
    "Conversations parked into the session store (turn-complete parks "
    "and drain-time mid-generation parks alike).")
SESSION_RESTORE = _registry().counter(
    "session_restore_total",
    "Parked conversations restored into a decode slot (KV planes pushed "
    "back instead of re-prefilling the transcript).")
SESSION_STORE_BYTES = _registry().gauge(
    "session_store_bytes",
    "Bytes currently held by the session store (host-RAM snapshots plus "
    "disk-spilled blobs).")


def _tree_nbytes(tree) -> int:
    if tree is None:
        return 0
    if isinstance(tree, (list, tuple)):
        return sum(_tree_nbytes(x) for x in tree)
    return int(np.asarray(tree).nbytes)


@dataclass
class SessionSnapshot:
    """One parked conversation, complete enough to resume bit-exactly.

    ``tokens`` is the committed transcript (prompt ++ emitted so far);
    ``planes`` the host KV pytree for columns ``[0, len(tokens))`` in
    relative position (None when the validity window was narrower than
    one chunk — the restore path then falls back to re-prefill, still
    bit-exact).  ``remaining > 0`` marks a mid-generation park (drain):
    the restore resumes decoding with that budget; ``remaining == 0`` is
    a completed turn awaiting a follow-up.  ``logits`` (plain loop) /
    ``cur`` (speculative loop) carry the resume payload the slot loop's
    activation would otherwise derive from a final prefill chunk.
    """

    session_id: str
    model: str
    tokens: List[int]
    remaining: int = 0
    emitted: List[int] = field(default_factory=list)
    planes: Any = None
    logits: Optional[np.ndarray] = None
    cur: Optional[int] = None
    kv_dtype: str = "bfloat16"
    spec: bool = False
    t_park: float = 0.0
    meta: dict = field(default_factory=dict)

    def nbytes(self) -> int:
        n = _tree_nbytes(self.planes)
        if self.logits is not None:
            n += int(np.asarray(self.logits).nbytes)
        return n + 8 * len(self.tokens)

    def to_payload(self) -> dict:
        return {
            "session_id": self.session_id, "model": self.model,
            "tokens": [int(t) for t in self.tokens],
            "remaining": int(self.remaining),
            "emitted": [int(t) for t in self.emitted],
            "cur": None if self.cur is None else int(self.cur),
            "kv_dtype": self.kv_dtype, "spec": bool(self.spec),
            "t_park": float(self.t_park), "meta": dict(self.meta),
            "planes": self.planes, "logits": self.logits,
        }

    @classmethod
    def from_payload(cls, p: dict) -> "SessionSnapshot":
        logits = p.get("logits")
        return cls(session_id=p["session_id"], model=p["model"],
                   tokens=[int(t) for t in p["tokens"]],
                   remaining=int(p.get("remaining", 0)),
                   emitted=[int(t) for t in p.get("emitted", ())],
                   planes=p.get("planes"),
                   logits=None if logits is None
                   else np.asarray(logits, np.float32),
                   cur=p.get("cur"),
                   kv_dtype=p.get("kv_dtype", "bfloat16"),
                   spec=bool(p.get("spec", False)),
                   t_park=float(p.get("t_park", 0.0)),
                   meta=dict(p.get("meta") or {}))

    def to_bytes(self) -> bytes:
        return serialize_session(self.to_payload())

    @classmethod
    def from_bytes(cls, blob: bytes) -> "SessionSnapshot":
        return cls.from_payload(deserialize_session(blob))


class SessionStore:
    """Host-RAM session snapshots with optional sha256-manifested disk
    spill.  Thread-safe; a snapshot has exactly one consumer (``take``
    removes it from RAM and disk — the restoring slot either completes
    the turn, which re-parks, or fails, which re-prefills next time)."""

    def __init__(self, spill_dir: str = "", park_after_ms: int = 0):
        self._dir = str(spill_dir or "")
        self._park_after_ms = int(park_after_ms)
        self._ram: Dict[str, SessionSnapshot] = {}  # guarded-by: _lock
        self._lock = threading.Lock()
        self._ram_bytes = 0                         # guarded-by: _lock
        self._disk_bytes: Dict[str, int] = {}       # guarded-by: _lock
        if self._dir:
            os.makedirs(self._dir, exist_ok=True)
            self._scan_disk()

    # -- naming / manifests --------------------------------------------------
    def _stem(self, sid: str) -> str:
        return hashlib.sha256(sid.encode()).hexdigest()[:32]

    def _paths(self, sid: str):
        stem = self._stem(sid)
        return (os.path.join(self._dir, stem + ".ptss"),
                os.path.join(self._dir, stem + ".json"))

    def _scan_disk(self):
        for name in os.listdir(self._dir):
            if not name.endswith(".json"):
                continue
            try:
                with open(os.path.join(self._dir, name)) as f:
                    man = json.load(f)
                self._disk_bytes[man["session_id"]] = int(man["bytes"])
            except (OSError, ValueError, KeyError):
                continue
        self._publish_bytes()

    def _publish_bytes(self):
        SESSION_STORE_BYTES.set(self._ram_bytes
                                + sum(self._disk_bytes.values()))

    # -- spill ---------------------------------------------------------------
    def _spill_locked(self, sid: str, snap: SessionSnapshot,
                      drop_ram: bool) -> None:
        blob = snap.to_bytes()
        blob_path, man_path = self._paths(sid)
        digest = atomic_write_bytes(blob_path, blob)
        man = json.dumps({"session_id": sid,
                          "file": os.path.basename(blob_path),
                          "sha256": digest, "bytes": len(blob),
                          "t_park": snap.t_park}).encode()
        atomic_write_bytes(man_path, man)
        self._disk_bytes[sid] = len(blob)
        if drop_ram and sid in self._ram:
            self._ram_bytes -= self._ram.pop(sid).nbytes()

    def _drop_disk_locked(self, sid: str) -> None:
        blob_path, man_path = self._paths(sid)
        for p in (blob_path, man_path):
            try:
                os.unlink(p)
            except OSError:
                pass
        self._disk_bytes.pop(sid, None)

    def _load_disk_locked(self, sid: str) -> Optional[SessionSnapshot]:
        blob_path, man_path = self._paths(sid)
        try:
            with open(man_path) as f:
                man = json.load(f)
            if sha256_file(blob_path) != man["sha256"]:
                raise CheckpointCorruptError(
                    f"session spill {os.path.basename(blob_path)} does "
                    "not match its manifest digest")
            with open(blob_path, "rb") as f:
                return SessionSnapshot.from_bytes(f.read())
        except (OSError, ValueError, KeyError, CheckpointCorruptError):
            # a torn or missing spill is a cache miss, never a crash —
            # the turn falls back to a plain (bit-identical) re-prefill
            self._drop_disk_locked(sid)
            return None

    def _sweep_locked(self) -> None:
        if not self._dir or self._park_after_ms <= 0:
            return
        now = time.time()
        idle = [sid for sid, s in self._ram.items()
                if (now - s.t_park) * 1000.0 >= self._park_after_ms]
        for sid in idle:
            self._spill_locked(sid, self._ram[sid], drop_ram=True)

    # -- public API ----------------------------------------------------------
    def put(self, snap: SessionSnapshot) -> None:
        """Park a snapshot.  Write-through mode (``park_after_ms == 0``
        with a spill dir) persists immediately AND keeps the RAM copy
        hot — the disk blob is the SIGKILL survivor, the RAM copy the
        fast path; lazy mode spills older parks on each put."""
        with self._lock:
            sid = snap.session_id
            if sid in self._ram:
                self._ram_bytes -= self._ram[sid].nbytes()
            snap.t_park = snap.t_park or time.time()
            self._ram[sid] = snap
            self._ram_bytes += snap.nbytes()
            if self._dir and self._park_after_ms == 0:
                self._spill_locked(sid, snap, drop_ram=False)
            else:
                self._sweep_locked()
            self._publish_bytes()
        SESSION_PARK.inc()

    def take(self, sid: str) -> Optional[SessionSnapshot]:
        """Claim a parked session for restore (removes every copy)."""
        with self._lock:
            snap = self._ram.pop(sid, None)
            if snap is not None:
                self._ram_bytes -= snap.nbytes()
            elif self._dir:
                snap = self._load_disk_locked(sid)
            if self._dir:
                self._drop_disk_locked(sid)
            self._publish_bytes()
        if snap is not None:
            SESSION_RESTORE.inc()
        return snap

    def peek_ids(self) -> List[str]:
        with self._lock:
            return sorted(set(self._ram) | set(self._disk_bytes))

    def __contains__(self, sid: str) -> bool:
        with self._lock:
            return sid in self._ram or sid in self._disk_bytes

    def __len__(self) -> int:
        with self._lock:
            return len(set(self._ram) | set(self._disk_bytes))

    def nbytes(self) -> int:
        with self._lock:
            return self._ram_bytes + sum(self._disk_bytes.values())

    # -- migration transport -------------------------------------------------
    def export_bytes(self, sid: str) -> Optional[bytes]:
        """Move semantics: serialize-and-remove, for router-driven
        migration off a draining replica."""
        with self._lock:
            snap = self._ram.pop(sid, None)
            if snap is not None:
                self._ram_bytes -= snap.nbytes()
            elif self._dir:
                snap = self._load_disk_locked(sid)
            if self._dir:
                self._drop_disk_locked(sid)
            self._publish_bytes()
        return None if snap is None else snap.to_bytes()

    def import_bytes(self, blob: bytes) -> Optional[str]:
        """Ingest a migrated session.  Keep-newer: an already-parked
        copy with a later ``t_park`` wins (a stale migration replay must
        not clobber a fresher turn)."""
        snap = SessionSnapshot.from_bytes(blob)
        with self._lock:
            prev = self._ram.get(snap.session_id)
            if prev is not None and prev.t_park > snap.t_park:
                return None
        self.put(snap)
        return snap.session_id


# -- declared protocol: the parked-session state machine ---------------------
# put/take above are ``park``/``restore``; export_bytes/import_bytes the
# ``export``/``import`` migration legs (move semantics + the t_park
# keep-newer rule).  Verified by analysis/protocol: exactly one owner
# (RAM copy, wire blob or decode slot) at all times, and an import
# never clobbers a fresher park.
from ..analysis.protocol.spec import ProtocolSpec, register_protocol

SESSION_SPEC = register_protocol(ProtocolSpec(
    name="session",
    description="A multi-turn conversation across park, restore, and "
                "router-driven migration between replicas.",
    module=__name__,
    states=("active", "parked", "migrating", "restored"),
    initial="active",
    transitions=(
        ("active", "park", "parked"),
        ("parked", "restore", "restored"),
        ("restored", "park", "parked"),
        ("parked", "export", "migrating"),
        ("migrating", "import", "parked"),
    ),
    invariants=(
        ("one-owner",
         "a session never has two owners, and loses its last owner "
         "only through documented SIGKILL degradation"),
        ("no-stale-clobber",
         "an import never overwrites a fresher parked copy"),
    ),
))


def require_kv_planes(kinds) -> None:
    """Raise ``InvalidArgumentError`` naming the plane kinds of a model
    that this module cannot cut (anything but uniform K/V planes)."""
    from ..text.generation import require_kv_planes as _require
    _require(kinds, "the session store (it parks a row's validity window of every plane)")
