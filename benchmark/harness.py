"""What every runner shares: the checks that decide ``correct``, host
spans on the profiler's clock, the profiler slice of a traced run, and
percentiles."""
from __future__ import annotations

import contextlib
import math
import os
import shutil
import threading
import time

import jax

SPAN_NAMES = ("make_batch", "train_step_call", "fetch_loss", "submit", "await")


@contextlib.contextmanager
def span(name: str):
    """A host span written into the profiler's own trace (nothing when no
    trace is being captured beyond a no-op annotation)."""
    with jax.profiler.TraceAnnotation(name):
        yield


class Checks:
    """The numbers compared, each beside its limit; ``correct`` is their
    conjunction.  Printed in every run."""

    def __init__(self):
        self.rows = []

    def add(self, name: str, value: float, limit: float, *, kind="max",
            note: str = ""):
        """``kind`` "max": value <= limit passes; "min": value >= limit."""
        value = float(value)
        ok = math.isfinite(value) and (
            value <= limit if kind == "max" else value >= limit)
        self.rows.append({"name": name, "value": value, "limit": limit,
                          "kind": kind, "ok": bool(ok)})
        print(f"CHECK {name}: {value:.6g} "
              f"{'<=' if kind == 'max' else '>='} {limit:.6g} "
              f"{'ok' if ok else 'FAILED'}{' (' + note + ')' if note else ''}",
              flush=True)
        return ok

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)


def worst_leaf_gap(prog: dict, ref: dict, leaves=None):
    """(gap, leaf): the largest gap between the program's and the
    reference's norm of a leaf, against the reference's norm of that leaf or
    of the median leaf, whichever is larger (some gradients are all but
    zero).  ``leaves`` restricts the comparison; the median stays that of
    all leaves."""
    if set(prog) != set(ref):
        raise KeyError(f"leaves differ: {sorted(set(prog) ^ set(ref))[:6]}")
    ordered = sorted(ref.values())
    median = ordered[len(ordered) // 2]
    return max((abs(prog[k] - ref[k]) / max(ref[k], median, 1e-30), k)
               for k in (ref if leaves is None else leaves))


def canonical_view(named: dict, ids: dict) -> dict:
    """The program-named leaves ``named`` arranged as the canonical tree:
    top leaves by name, per-layer leaves as lists in layer order — nothing
    is restacked or copied.  ``ids`` maps program name -> canonical leaf id
    ("wte", "layers/q_w/3")."""
    view, layers = {}, {}
    for name, leaf in ids.items():
        if leaf.startswith("layers/"):
            _, key, i = leaf.split("/")
            layers.setdefault(key, {})[int(i)] = named[name]
        else:
            view[leaf] = named[name]
    view["layers"] = {k: [v[i] for i in range(len(v))]
                      for k, v in layers.items()}
    return view


def sketch_difference(prog: dict, ref: dict, ref_norms: dict) -> float:
    """The norm of the difference of two trees against the reference's
    norm, over all leaves.  The difference's squared norm is estimated leaf
    by leaf from the seeded count-sketches (the sum over a leaf's buckets of
    (s_prog - s_ref)^2 is an unbiased estimate of it); the reference's norm
    is exact."""
    if not set(prog) == set(ref) == set(ref_norms):
        raise KeyError("leaves differ")
    num = sum(float(((prog[k] - ref[k]) ** 2).sum()) for k in ref)
    den = sum(v * v for v in ref_norms.values())
    return math.sqrt(num / max(den, 1e-300))


def device_bytes_now() -> int:
    """Bytes held on the fullest chip at this moment: live buffers plus what
    the runtime has reserved for the loaded programs' temporaries (on this
    runtime ``peak_bytes_in_use`` leaves the second out)."""
    held = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        held = max(held, int(stats.get("bytes_in_use", 0))
                   + int(stats.get("bytes_reserved", 0)))
    return held


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (q in 0..100) of a non-empty list."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of nothing")
    pos = (len(v) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


class ProfilerSlice:
    """Captures ``slice_s`` seconds from the middle of a window of
    ``seconds``, from a thread of its own so that the loop which drives the
    window never waits inside the profiler's start or stop.  Nothing
    happens when ``enabled`` is false."""

    def __init__(self, enabled: bool, bench_dir: str, t_start: float,
                 seconds: float, slice_s: float):
        self.dir = None
        self._thread = None
        if not enabled:
            return
        slice_s = min(slice_s, max(0.5, seconds * 0.6))
        t_on = t_start + (seconds - slice_s) / 2
        self.dir = os.path.join(os.path.dirname(bench_dir), ".cache",
                                "benchmark_trace")
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        self._thread = threading.Thread(
            target=self._capture, args=(t_on, t_on + slice_s),
            name="benchmark-profiler", daemon=True)
        self._thread.start()

    def _capture(self, t_on: float, t_off: float):
        time.sleep(max(0.0, t_on - time.monotonic()))
        # host spans (TraceAnnotation) yes, Python's own tracer no: it
        # slows the host loops that the trace is there to show
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        time.sleep(max(0.0, t_off - time.monotonic()))
        jax.profiler.stop_trace()

    def stop(self):
        """Wait until the capture is written."""
        if self._thread is not None:
            self._thread.join()


class Phases:
    """Seconds of each phase of set-up, printed and kept in the result:
    what a later PR would shorten."""

    def __init__(self, t0: float):
        self._t = t0
        self.seconds = {}

    def mark(self, name: str):
        now = time.monotonic()
        self.seconds[name] = self.seconds.get(name, 0.0) + now - self._t
        self._t = now

    def report(self):
        print("set-up phases (s): " + ", ".join(
            f"{k} {v:.1f}" for k, v in self.seconds.items()), flush=True)
        return self.seconds
