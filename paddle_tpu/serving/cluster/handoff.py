"""KV-cache handoff between prefill and decode worker pools.

A prefill pool's product is exactly the decode pool's working set: the
per-layer ring-cache planes (bf16 ``(k, v)`` or int8 ``(k, v, k_scale,
v_scale)`` — PR 12's quantized planes ride unchanged), the next-token
logits, and the validity-window metadata (``cache_position`` to resume
at, per-row ``start`` offsets).  Two transports:

  * **device** — both pools share one process/mesh: the handoff is the
    device arrays themselves, zero copies (the decode executable's input
    shardings match the prefill executable's pinned output shardings,
    sharding.py's KV layout rule);
  * **wire** — pools in different processes: planes serialize to one
    contiguous blob (JSON header + raw row-major plane bytes, exact to
    the bit — bf16/int8 planes move as their raw 2/1-byte payloads, so a
    deserialized cache is byte-identical and decode resumes
    bit-identically to the in-process continuation).

Both transports feed the ``kv_handoff_bytes_total`` counter and the
``kv_handoff_seconds`` histogram (docs/METRICS.md inventory).
"""
from __future__ import annotations

import io
import json
import struct
import time
from dataclasses import dataclass, field
from typing import Any, List, Tuple

import numpy as np

from ...framework.enforce import InvalidArgumentError
from ...profiler.metrics import default_registry as _registry

__all__ = ["KVHandoff", "serialize_kv", "deserialize_kv",
           "serialize_session", "deserialize_session"]

_MAGIC = b"PTKV1\n"
_SS_MAGIC = b"PTSS1\n"

_HANDOFF_BYTES = _registry().counter(
    "kv_handoff_bytes_total",
    "KV-cache plane bytes moved between the prefill and decode pools, "
    "by transport (wire = serialized cross-process blob, device = "
    "same-mesh device-to-device pass-through).",
    labels=("transport",))
_HANDOFF_SECONDS = _registry().histogram(
    "kv_handoff_seconds",
    "Wall time of one prefill→decode KV-cache handoff leg (serialize, "
    "deserialize, or device pass-through).",
    buckets=(0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0))


def _np_dtype(name: str) -> np.dtype:
    """np.dtype by name, resolving the ml_dtypes extension types
    (bfloat16, float8_*) numpy itself does not know."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


def _host(plane) -> np.ndarray:
    return np.ascontiguousarray(np.asarray(plane))


@dataclass
class KVHandoff:
    """One prefill result in flight to a decode pool.

    ``cache`` is the Generator-shape plane list (one tuple of 2 or 4
    planes per attention layer), ``logits0`` the [B, V] next-token
    logits, ``start`` the per-row first-valid-cache-column offsets and
    ``pos`` the traced ``cache_position`` decode resumes at (== the
    prompt bucket the prefill ran).  ``meta`` carries request context
    across the process boundary (model name, max_new, eos, trace_id).
    """

    cache: List[Tuple[Any, ...]]
    logits0: Any
    start: Any
    pos: int
    meta: dict = field(default_factory=dict)

    def nbytes(self) -> int:
        n = sum(_nbytes(p) for c in self.cache for p in c)
        return n + (_nbytes(self.logits0) if self.logits0 is not None else 0)

    # -- transports ----------------------------------------------------------
    def to_bytes(self) -> bytes:
        return serialize_kv(self)

    @classmethod
    def from_bytes(cls, blob: bytes) -> "KVHandoff":
        return deserialize_kv(blob)

    def device(self, kv_sharding_of=None) -> "KVHandoff":
        """Place every plane on device (the decode pool's ingest step).
        ``kv_sharding_of(shape)`` maps a plane's shape to its sharding
        (sharded replicas pin the KV layout; None = default device).
        Metered as the device transport leg."""
        import jax
        t0 = time.monotonic()
        put = (jax.device_put if kv_sharding_of is None
               else lambda p: jax.device_put(p, kv_sharding_of(np.shape(p))))
        cache = [tuple(put(np.asarray(p)) for p in c) for c in self.cache]
        logits = None if self.logits0 is None \
            else jax.device_put(np.asarray(self.logits0))
        out = KVHandoff(cache=cache, logits0=logits,
                        start=np.asarray(self.start, np.int32),
                        pos=self.pos, meta=dict(self.meta))
        _HANDOFF_BYTES.labels("device").inc(out.nbytes())
        _HANDOFF_SECONDS.observe(time.monotonic() - t0)
        return out


def _nbytes(plane) -> int:
    sz = int(np.prod(np.shape(plane))) if np.ndim(plane) else 1
    return sz * _np_dtype(str(np.asarray(plane).dtype
                              if isinstance(plane, np.ndarray)
                              else plane.dtype)).itemsize


def serialize_kv(h: KVHandoff) -> bytes:
    """One contiguous blob: magic + length-prefixed JSON header + raw
    row-major plane bytes (layer-major, plane order, then logits).  The
    payload is the planes' exact storage bytes — bf16 rows, int8 rows
    and f32 scale planes alike — so the roundtrip is bit-exact."""
    t0 = time.monotonic()
    planes_meta, buf = [], io.BytesIO()
    for c in h.cache:
        layer_meta = []
        for p in c:
            a = _host(p)
            layer_meta.append({"shape": list(a.shape),
                               "dtype": str(a.dtype)})
            buf.write(a.tobytes())
        planes_meta.append(layer_meta)
    logits_meta = None
    if h.logits0 is not None:
        a = _host(h.logits0)
        logits_meta = {"shape": list(a.shape), "dtype": str(a.dtype)}
        buf.write(a.tobytes())
    start = np.asarray(h.start, np.int32).reshape(-1)
    header = json.dumps({
        "version": 1, "planes": planes_meta, "logits": logits_meta,
        "start": start.tolist(), "pos": int(h.pos),
        "meta": dict(h.meta),
    }).encode()
    out = _MAGIC + struct.pack("<I", len(header)) + header + buf.getvalue()
    _HANDOFF_BYTES.labels("wire").inc(len(out))
    _HANDOFF_SECONDS.observe(time.monotonic() - t0)
    return out


def _encode_tree(tree, buf) -> Any:
    """Descriptor of an arbitrary list/tuple pytree of arrays, appending
    each leaf's raw storage bytes to ``buf``.  Container kinds are part
    of the descriptor — the slot-cache pytree structure (a LIST of
    per-layer TUPLEs; the speculative pair is a tuple of two such lists)
    must survive the roundtrip exactly or jax.tree_util would see a
    different treedef on restore."""
    if tree is None:
        return None
    if isinstance(tree, (list, tuple)):
        return {"t": "list" if isinstance(tree, list) else "tuple",
                "c": [_encode_tree(x, buf) for x in tree]}
    a = _host(tree)
    buf.write(a.tobytes())
    return {"shape": list(a.shape), "dtype": str(a.dtype)}


def _decode_tree(desc, take) -> Any:
    if desc is None:
        return None
    if "t" in desc:
        kids = [_decode_tree(d, take) for d in desc["c"]]
        return kids if desc["t"] == "list" else tuple(kids)
    return take(desc)


def serialize_session(payload: dict) -> bytes:
    """Parked-session snapshot wire format (magic ``PTSS1\\n``): same
    length-prefixed-JSON + raw-plane-bytes discipline as
    :func:`serialize_kv`, but the plane container is an arbitrary
    list/tuple pytree (plain slot caches and speculative (target, draft)
    pairs alike) and the scalar session state (tokens, resume payload,
    budget) rides the header.  ``payload['planes']`` and
    ``payload['logits']`` are array pytrees (or None); every other key
    must be JSON-serializable.  Bit-exact roundtrip — a restored session
    decodes byte-identically."""
    t0 = time.monotonic()
    buf = io.BytesIO()
    header_doc = {"version": 1}
    for k, v in payload.items():
        if k in ("planes", "logits"):
            header_doc[k] = _encode_tree(v, buf)
        else:
            header_doc[k] = v
    header = json.dumps(header_doc).encode()
    out = _SS_MAGIC + struct.pack("<I", len(header)) + header \
        + buf.getvalue()
    _HANDOFF_BYTES.labels("session").inc(len(out))
    _HANDOFF_SECONDS.observe(time.monotonic() - t0)
    return out


def deserialize_session(blob: bytes) -> dict:
    """Inverse of :func:`serialize_session`; plane leaves come back as
    host np.frombuffer views with the original container structure."""
    t0 = time.monotonic()
    if not blob.startswith(_SS_MAGIC):
        raise InvalidArgumentError(
            "not a session snapshot blob (bad magic); refusing to parse")
    off = len(_SS_MAGIC)
    (hlen,) = struct.unpack_from("<I", blob, off)
    off += 4
    header = json.loads(blob[off:off + hlen].decode())
    if header.get("version") != 1:
        raise InvalidArgumentError(
            f"session snapshot version {header.get('version')!r} is not "
            "supported (this build speaks version 1)")
    off += hlen

    def take(meta):
        nonlocal off
        dt = _np_dtype(meta["dtype"])
        shape = tuple(meta["shape"])
        n = int(np.prod(shape)) * dt.itemsize if shape else dt.itemsize
        a = np.frombuffer(blob, dtype=dt,
                          count=max(1, int(np.prod(shape))),
                          offset=off).reshape(shape)
        off += n
        return a

    out = {}
    for k, v in header.items():
        if k == "version":
            continue
        out[k] = _decode_tree(v, take) if k in ("planes", "logits") else v
    _HANDOFF_SECONDS.observe(time.monotonic() - t0)
    return out


def deserialize_kv(blob: bytes) -> KVHandoff:
    """Inverse of :func:`serialize_kv`; returns host-resident planes
    (np.frombuffer views reshaped — call :meth:`KVHandoff.device` to
    ingest onto the decode pool's mesh)."""
    t0 = time.monotonic()
    if not blob.startswith(_MAGIC):
        raise InvalidArgumentError(
            "not a KV handoff blob (bad magic); refusing to parse")
    off = len(_MAGIC)
    (hlen,) = struct.unpack_from("<I", blob, off)
    off += 4
    header = json.loads(blob[off:off + hlen].decode())
    if header.get("version") != 1:
        raise InvalidArgumentError(
            f"KV handoff version {header.get('version')!r} is not "
            "supported (this build speaks version 1)")
    off += hlen

    def take(meta):
        nonlocal off
        dt = _np_dtype(meta["dtype"])
        shape = tuple(meta["shape"])
        n = int(np.prod(shape)) * dt.itemsize if shape else dt.itemsize
        a = np.frombuffer(blob, dtype=dt, count=max(1, int(np.prod(shape))),
                          offset=off).reshape(shape)
        off += n
        return a

    cache = [tuple(take(m) for m in layer) for layer in header["planes"]]
    logits = take(header["logits"]) if header["logits"] is not None else None
    h = KVHandoff(cache=cache, logits0=logits,
                  start=np.asarray(header["start"], np.int32),
                  pos=int(header["pos"]), meta=dict(header.get("meta") or {}))
    _HANDOFF_SECONDS.observe(time.monotonic() - t0)
    return h


# -- declared protocol: the prefill->decode handoff ---------------------------
# serialize_kv/deserialize_kv above are the ``prefill``/``decode`` legs;
# the magic + version checks are the ``reject`` door (a torn blob is a
# retryable failure, never input).  Verified by analysis/protocol.
from ...analysis.protocol.spec import ProtocolSpec, register_protocol

KV_HANDOFF_SPEC = register_protocol(ProtocolSpec(
    name="kv-handoff",
    description="One disaggregated request: prefill serializes the KV "
                "blob, decode ingests it behind the integrity check, "
                "retryable failures re-enter, replies are "
                "exactly-once.",
    module=__name__,
    states=("pending", "in_flight", "decoded", "replied", "failed"),
    initial="pending",
    terminal=("replied", "failed"),
    transitions=(
        ("pending", "prefill", "in_flight"),
        ("in_flight", "decode", "decoded"),
        ("in_flight", "reject", "pending"),
        ("in_flight", "fail", "failed"),
        ("decoded", "reply", "replied"),
    ),
    invariants=(
        ("no-torn-decode",
         "decode never executes over a torn handoff blob"),
        ("reply-at-most-once",
         "a request is replied to at most once"),
    ),
))


def require_kv_planes(kinds) -> None:
    """Raise ``InvalidArgumentError`` naming the plane kinds of a model
    that this module cannot cut (anything but uniform K/V planes)."""
    from ...text.generation import require_kv_planes as _require
    _require(kinds, "the prefill -> decode KV handoff (it ships whole K/V planes between pools)")
