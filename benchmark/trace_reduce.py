"""From a profiler capture (``.xplane.pb``) to the numbers the per-layer
metrics read: device busy and idle time, per-program device time, the
operations that took most time, and the idle gaps by what the host was doing.

What the TPU's trace looks like (looked at by hand, PR 23; PERF.md section 5):
planes named ``/device:TPU:<n>`` are the chips, each with a line
``XLA Modules`` (one event per run of a compiled program, named
``jit_<function>(<fingerprint>)``), a line ``XLA Ops`` (one event per
operation inside it) and a line ``Steps``; ``/host:CPU`` holds one line per
host thread, where ``jax.profiler.TraceAnnotation`` spans appear under
their own names.  All planes share one clock (nanoseconds).

Needs nothing but JAX (``jax.profiler.ProfileData``).
"""
from __future__ import annotations

import glob
import os
import re
import statistics

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_FINGERPRINT = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir: str):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def load(path: str):
    """A capture as ``jax.profiler.ProfileData``; ``.gz`` is unpacked in
    memory (the recorded trace under ``tests/data``)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


_KIND = re.compile(r"kind=(\w+)")
_SUFFIX = re.compile(r"[.\d]+$")


def short_name(name: str) -> str:
    """The trace names an operation by its whole HLO instruction; keep its
    result name, its first result shape and its fusion kind:
    ``%fusion.1044 bf16[16,512,4096] kOutput``."""
    lhs, _, rhs = name.partition(" = ")
    shape = rhs.lstrip("(").split("{")[0].split(" ")[0]
    kind = _KIND.search(rhs)
    return " ".join(x for x in (lhs, shape, kind.group(1) if kind else "") if x)[:120]


def category(name: str) -> str:
    """``all fusion kOutput`` (matrix products and what is fused into them),
    ``all fusion kLoop`` (elementwise passes), ``all copy``, ..."""
    lhs, _, rhs = name.partition(" = ")
    kind = _KIND.search(rhs)
    return " ".join(x for x in ("all", _SUFFIX.sub("", lhs.lstrip("%")),
                                kind.group(1) if kind else "") if x)


def _events(line):
    """[(start_ns, end_ns, name)] of one line."""
    out = []
    for ev in line.events:
        start = float(ev.start_ns)
        out.append((start, start + float(ev.duration_ns), ev.name))
    return out


def union(intervals):
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return merged


def describe(profile, top: int = 12) -> dict:
    """Planes, lines, event counts and the commonest names: what to look at
    by hand before trusting ``reduce``."""
    out = {}
    for plane in profile.planes:
        lines = {}
        for line in plane.lines:
            evs = _events(line)
            if not evs:
                continue
            by_name = {}
            for a, b, n in evs:
                by_name[n] = by_name.get(n, 0.0) + (b - a)
            lines[line.name] = {
                "events": len(evs),
                "first_ns": min(e[0] for e in evs),
                "last_ns": max(e[1] for e in evs),
                "top": sorted(((n, t / 1e9) for n, t in by_name.items()),
                              key=lambda x: -x[1])[:top]}
        out[plane.name] = lines
    return out


def reduce_profile(profile, span_names=()) -> dict:
    devices, host_spans = [], []
    t_lo, t_hi = float("inf"), float("-inf")
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"] = _events(line)
                elif line.name == MODULES_LINE:
                    dev["modules"] = _events(line)
            if dev["ops"] or dev["modules"]:
                devices.append(dev)
        elif span_names:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in span_names:
                        a = float(ev.start_ns)
                        host_spans.append((a, a + float(ev.duration_ns),
                                           ev.name))
    if not devices:
        return None
    # the traced window is the stretch over which device events were
    # recorded: the host's trace starts a little earlier and ends a little
    # later than the device's, and those edges are not idle time
    for dev in devices:
        for a, b, _ in dev["ops"] + dev["modules"]:
            t_lo, t_hi = min(t_lo, a), max(t_hi, b)
    window_s = (t_hi - t_lo) / 1e9

    busy, op_time, cat_time, programs = [], {}, {}, {}
    gaps = []
    for dev in devices:
        source = dev["ops"] or dev["modules"]
        merged = union((a, b) for a, b, _ in source)
        busy.append(sum(b - a for a, b in merged) / 1e9)
        for a, b, n in dev["ops"]:
            op_time[n] = op_time.get(n, 0.0) + (b - a) / 1e9
            c = category(n)
            cat_time[c] = cat_time.get(c, 0.0) + (b - a) / 1e9
        for a, b, n in dev["modules"]:
            programs.setdefault(_FINGERPRINT.sub("", n), []).append(
                (b - a) / 1e9)
        edges = [t_lo] + [x for ab in merged for x in ab] + [t_hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]

    host_spans.sort()
    gaps.sort()
    by_label, first = {}, 0
    for a, b in gaps:
        # spans that ended before this gap began cannot cover a later one
        while first < len(host_spans) and host_spans[first][1] <= a \
                and host_spans[first][0] <= a:
            first += 1
        label, best = "no span", 0.0
        for sa, sb, n in host_spans[first:]:
            if sa >= b:
                break
            cover = min(b, sb) - max(a, sa)
            if cover > best:
                label, best = n, cover
        by_label[label] = by_label.get(label, 0.0) + (b - a) / 1e9
    n_dev = len(devices)
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / n_dev,
        "chips": n_dev,
        "programs": {n: {"count": len(d), "median_s": statistics.median(d),
                         "total_s": sum(d)} for n, d in programs.items()},
        # the five heaviest kinds of operation, then the five heaviest
        # single operations
        "top_ops": [[n, t / n_dev] for n, t in sorted(
            cat_time.items(), key=lambda x: -x[1])[:5]]
        + [[short_name(n), t / n_dev] for n, t in sorted(
            op_time.items(), key=lambda x: -x[1])[:5]],
        "idle_gaps": [[n, t / n_dev] for n, t in sorted(
            by_label.items(), key=lambda x: -x[1])[:10]],
    }


def reduce(trace_dir, span_names=()):
    """The reduced trace of the capture under ``trace_dir``, or None when
    there is none (a reader then finds nothing to read)."""
    path = find_xplane(trace_dir) if trace_dir else None
    if path is None:
        return None
    return reduce_profile(load(path), tuple(span_names))


if __name__ == "__main__":
    import json
    import sys
    prof = load(find_xplane(sys.argv[1]) if os.path.isdir(sys.argv[1])
                else sys.argv[1])
    print(json.dumps({"describe": describe(prof),
                      "reduced": reduce_profile(prof, tuple(sys.argv[2:]))},
                     indent=1))
