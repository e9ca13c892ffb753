"""Recompile ledger — the "why did this recompile" answer.

Every XLA compile the framework triggers (a @to_static input-signature
miss, a static Executor program-cache miss, a TrainStep retrace on new
input shapes) is recorded with its wall time, its cache key, and a
structured diff against the previous key at the same site — the diff is
the answer to "why did this recompile": which argument changed shape,
which program version bumped, which feed dtype flipped.

Surfaced three ways:
  * StatRegistry gauges (monitor.h parity): ``jit_compile_count``,
    ``jit_cache_hit``, ``jit_compile_ms_total``.
  * an in-memory ring queryable via :func:`compile_events` (bounded, so
    a long-serving process never grows).
  * structured JSONL through ``utils.monitor.LogWriter`` when a ledger
    dir is configured (:func:`set_ledger_dir`, flag ``jit_ledger_dir``,
    env ``PADDLE_TPU_JIT_LEDGER_DIR``).

Always on: compiles are rare and cache-hit accounting is one locked
integer add, so nothing here is gated on FLAGS_enable_profiler.
"""
from __future__ import annotations

import re
import threading
import time
from collections import deque
from typing import Optional

from ..framework import flags as _flags
from ..utils.monitor import stat_add

_lock = threading.Lock()
_ring: deque = deque(maxlen=512)
_last_key: dict = {}
# {"event", "text": the hlo_text callable, "table": parse_scopes of what it
# gave} of the events recorded with a text; bounded like the ring
# (program_scopes() below)
_texts: deque = deque(maxlen=512)
_dir_override = [None]
_writer = [None, None]          # [dir the writer was opened for, LogWriter]


def set_ledger_dir(path: Optional[str]) -> None:
    """Route ledger events to JSONL under ``path`` (None reverts to the
    ``jit_ledger_dir`` flag / env)."""
    with _lock:
        _dir_override[0] = path


def _get_writer():
    """Lazily (re)open the JSONL writer for the configured dir; must be
    called with _lock held."""
    d = _dir_override[0]
    if d is None:
        d = _flags.flag("jit_ledger_dir") or None
    if d != _writer[0]:
        if _writer[1] is not None:
            try:
                _writer[1].close()
            except Exception:
                pass
        from ..utils.monitor import LogWriter
        _writer[0] = d
        _writer[1] = LogWriter(logdir=d, filename_suffix=".ledger") \
            if d else None
    return _writer[1]


def _leaves(key, path=""):
    """Flatten a nested cache key into (path, repr) leaves so the diff
    points at the exact entry that changed.

    Self-describing entries — tuples whose first element is an
    ``"arg:<path>"`` label (the TrainStep/jit signature convention) —
    flatten to ONE leaf under that label, so the diff reads
    ``inputs[0]: ((8,16),'float32','weak') -> ...`` instead of a bare
    positional ``[0][3]``: the ledger and the graph-lint recompile-hazard
    pass then name the same culprit argument."""
    if isinstance(key, (tuple, list)) and key \
            and isinstance(key[0], str) and key[0].startswith("arg:"):
        label = key[0][4:]
        yield (f"{path}.{label}" if path else label, repr(tuple(key[1:])))
        return
    if isinstance(key, (tuple, list)) and any(
            isinstance(e, (tuple, list, dict)) for e in key):
        for i, e in enumerate(key):
            yield from _leaves(e, f"{path}[{i}]")
        return
    yield (path or "·", repr(key))


def key_diff(prev, cur):
    """Human-readable diff between two cache keys (the recompile cause)."""
    if prev is None:
        return ["first compile at this site"]
    p, c = dict(_leaves(prev)), dict(_leaves(cur))
    out = []
    for k in sorted(set(p) | set(c)):
        pv, cv = p.get(k, "<absent>"), c.get(k, "<absent>")
        if pv != cv:
            out.append(f"{k}: {pv} -> {cv}")
    return out or ["key unchanged (cache entry evicted or fetch-union grew)"]


def record_compile(site: str, kind: str, key, ms: float, extra=None,
                   hlo_text=None) -> dict:
    """Record one compile event. ``site`` identifies the compile cache
    (e.g. ``jit:train_step.<locals>.f``); ``kind`` is jit / executor /
    train_step / serving_aot / generate_* / hlo_audit — or
    ``cache_load`` when the persistent executable cache
    (jit/persistent_cache.py) satisfied the site without a fresh XLA
    compile (``extra.orig_kind`` keeps the avoided kind); ``key`` the
    cache key; ``ms`` the wall time of trace+compile (first dispatch),
    or of verify+deserialize for a load.  ``hlo_text``, a zero-argument
    callable, yields the compiled program's text when
    :func:`program_scopes` asks for it, or None once the program is gone
    (a ``Generator``'s holds its Generator weakly; ``TrainStep``'s is the
    executable's own ``as_text``, and is dropped once it has been read);
    it is kept and not called here."""
    with _lock:
        prev = _last_key.get(site)
        _last_key[site] = key
        ev = {"site": site, "kind": kind, "ms": round(float(ms), 3),
              "key": repr(key), "diff": key_diff(prev, key),
              "wall": time.time()}
        if extra:
            ev.update(extra)
        _ring.append(ev)
        if hlo_text is not None:
            _texts.append({"event": ev, "text": hlo_text, "table": None})
        w = _get_writer()
    stat_add("jit_compile_count")
    stat_add("jit_compile_ms_total", int(round(ms)))
    if w is not None:
        w.add_event("jit/compile", ev)
    # first-class trace annotation: a compile that runs inside a traced
    # request/step pins itself to that span (one branch when no span)
    from .tracing import attach_compile_event
    attach_compile_event(ev)
    return ev


def record_cache_hit(site: str) -> None:
    stat_add("jit_cache_hit")


def last_key(site: str):
    """The most recent cache key recorded at ``site`` (None before the
    first compile there) — the graph-lint recompile-hazard pass diffs the
    incoming key against this so the lint and the ledger's own diff name
    the same culprit."""
    with _lock:
        return _last_key.get(site)


def compile_events(site: Optional[str] = None):
    """Snapshot of recorded compile events, newest last."""
    with _lock:
        evs = list(_ring)
    if site is None:
        return evs
    return [e for e in evs if e["site"] == site]


def clear() -> None:
    """Drop recorded events and per-site key memory (tests)."""
    with _lock:
        _ring.clear()
        _last_key.clear()
        _texts.clear()


# -- which part of the model asked for an instruction -------------------------
# A compiled program's text carries, per instruction, the name stack under
# which JAX traced it: ``metadata={op_name="jit(step)/jit(main)/
# transpose(jvp(attention))/dot_general" ...}``.  A profiler capture names
# each device op by that same instruction name, so the two join.
_MODULE = re.compile(r"^HloModule\s+([^\s,]+)", re.M)
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s([^\n]*)$",
                          re.M)
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REFERENCE = re.compile(r"%([\w.\-]+)")
_JIT = re.compile(r"\bp?jit\([^()]*\)")
_WRAPPER = re.compile(r"[\w.\-]+\(|\)")


def scope_of(op_name: str) -> dict:
    """``{"scope": path}`` of one instruction's ``op_name``: the
    ``jax.named_scope`` names it was traced under, outermost first, the
    ``jit(..)`` components and the trailing primitive dropped, the
    transformation wrappers (``jvp(..)``, ``transpose(..)``, ...) peeled
    off; ``"backward": True`` beside it where a ``transpose(`` was.  What
    control flow adds (``while/body``, ``cond/branch_1_fun``) stays in the
    path: a reader looks for the names it knows."""
    # a jit(..) component keeps its place until the last component (the
    # primitive, or the call of a nested jit) is dropped
    parts = _WRAPPER.sub("", _JIT.sub("\0", op_name)).split("/")[:-1]
    out = {"scope": "/".join(p for p in parts if p and p != "\0")}
    if "transpose(" in op_name:
        out["backward"] = True
    return out


def parse_scopes(text: str):
    """(XLA module name, {instruction name: ``scope_of`` its op_name}) of
    a compiled program's text (``Compiled.as_text()``).  Every
    instruction is listed, with an empty path where it has none: an
    instruction the table does not hold is another program's.

    An instruction the COMPILER made (no op_name at all: the prefetch of a
    weight in slices, ``slice-start`` / ``slice-done`` / ``copy-done``,
    the views and relayouts on the way) was asked for by whoever uses its
    result, and takes the entry of its first user that has a path, with
    ``"inherited": True`` beside it.  Code outside every named scope has
    an op_name and stays without a path."""
    module = _MODULE.search(text)
    table, made, users = {}, {}, {}
    for m in _INSTRUCTION.finditer(text):
        name, rest = m.groups()
        op_name = _OP_NAME.search(rest)
        if op_name is None:
            made[name] = None           # (a dict: in the text's order)
        table[name] = scope_of(op_name.group(1) if op_name else "")
        # operands are defined above their users, so ``made`` is complete
        # for them here
        for operand in _REFERENCE.findall(rest):
            if operand in made:
                users.setdefault(operand, []).append(name)
    for name in reversed(made):         # users first: they come later
        for user in users.get(name, ()):
            if table[user]["scope"]:
                table[name] = {**table[user], "inherited": True}
                break
    return (module.group(1) if module else ""), table


def program_scopes() -> dict:
    """``{XLA module name: {instruction name: {"scope": path[, "backward":
    True][, "inherited": True]}}}`` of every program recorded with an
    ``hlo_text`` that still yields one.  The texts are fetched and parsed here, on the first call
    that sees them, and the table is kept beside the event; nothing
    happens before.  Where two programs share a module name (a program
    compiled again for weights that lie otherwise) the later one is the
    one that runs."""
    with _lock:
        entries = list(_texts)
    out = {}
    for entry in entries:
        if entry["table"] is None and entry["text"] is not None:
            text = entry["text"]()
            if text is not None:
                entry["table"] = parse_scopes(text)
                entry["text"] = None        # and what it held
        if entry["table"] is not None:
            module, table = entry["table"]
            out[module] = table
    return out
