"""Runner ``serve_slots_prefix``: ``serve_slots`` with the prefix cache on.

The configuration's ``serve`` says ``prefix_cache`` and
``prefix_cache_hbm_mb``; both are set as flags before the server boots
(``FLAGS_prefix_cache``, ``FLAGS_prefix_cache_hbm_mb``: the decode runtime
reads them when it builds its slot loop).  The requests that go through
the reference are ONE served from a hit and ONE miss, the longest of each,
so that ``correct`` is decided on both paths of the timed run: the hit's
tokens came through restored blocks and a suffix chunk.  Which is which
the traffic says (``closed_loop_docs``: a document's first ask is a miss,
every later one finds the blocks the first published).  Everything else
(``boot``, the window, the comparison, ``control`` for
``benchmark/control.py``) is ``serve_slots``'s.
"""
from __future__ import annotations

import contextlib

from benchmark.runners import serve_slots


def _hit_and_miss(finished, k: int, seed: int):
    """The longest finished miss (a document's first ask) and the longest
    finished hit: ``serve_slots._sample``'s place."""
    size = lambda r: r.prompt.size + r.max_new                  # noqa: E731
    picks = []
    for hit in (False, True):
        of_kind = [r for r in finished if bool(getattr(r, "ask", 0)) == hit]
        if of_kind:
            picks.append(max(of_kind, key=size))
    return picks[:max(k, 1)]


@contextlib.contextmanager
def _prefix_cache_on(cfg: dict):
    """The two flags from ``serve``, and the sample drawn as above, for the
    time of one run."""
    from paddle_tpu.framework.flags import flag, set_flags
    sv = cfg["serve"]
    names = ("prefix_cache", "prefix_cache_hbm_mb")
    old = {"FLAGS_" + n: flag(n) for n in names}
    sample = serve_slots._sample
    set_flags({"FLAGS_prefix_cache": bool(sv["prefix_cache"]),
               "FLAGS_prefix_cache_hbm_mb": float(sv["prefix_cache_hbm_mb"])})
    serve_slots._sample = _hit_and_miss
    try:
        yield
    finally:
        serve_slots._sample = sample
        set_flags(old)


def run(cell: dict, seed: int, seconds: float, trace: bool,
        t_process_start: float) -> dict:
    with _prefix_cache_on(cell["config"]):
        return serve_slots.run(cell, seed, seconds, trace, t_process_start)


def control(cell: dict, seeds, seconds: float = 10.0) -> list:
    with _prefix_cache_on(cell["config"]):
        return serve_slots.control(cell, seeds, seconds)
