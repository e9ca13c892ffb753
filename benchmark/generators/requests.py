"""The request pool that the serving generators share.

Every seed gets the SAME sequence of (prompt length, new tokens) pairs and
of arrival gaps, in the same order — drawn once from the traffic file's
``shape_seed`` — with its own token ids (and, in the runners, its own
weights).  So runs with different seeds do the same work at the same
moments and differ by the system's own jitter only.  Another order per
seed was tried and taken out (PERF.md section 4): answers come whole, so
what is in flight when a window opens and closes then differs from seed to
seed by several per cent of the window's tokens, and a tail over ~70
requests by more.
"""
from __future__ import annotations

import numpy as np


def _lengths(spec: dict, n: int, rng) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def pool(traffic: dict, vocab: int, seed: int) -> list:
    """[(prompt ids int32 [P], max_new)] of ``pool_requests`` requests."""
    n = int(traffic["pool_requests"])
    shape = np.random.default_rng(int(traffic["shape_seed"]))
    plen = _lengths(traffic["prompt_len"], n, shape)
    mnew = _lengths(traffic["max_new_tokens"], n, shape)
    rng = np.random.default_rng(int(seed))
    return [(rng.integers(0, vocab, int(p), dtype=np.int32), int(m))
            for p, m in zip(plen, mnew)]


def poisson_gaps(traffic: dict) -> np.ndarray:
    """``pool_requests`` exponential gaps at ``rate_per_s``: one fixed
    sequence (``shape_seed``), the same gaps times 1/rate at any rate."""
    n = int(traffic["pool_requests"])
    shape = np.random.default_rng(int(traffic["shape_seed"]) + 1)
    return shape.exponential(1.0 / float(traffic["rate_per_s"]), n)


class Record:
    """One request as the client saw it (times on ``time.monotonic``)."""
    __slots__ = ("index", "prompt", "max_new", "due", "sent", "done",
                 "tokens", "error")

    def __init__(self, index, prompt, max_new, due):
        self.index, self.prompt, self.max_new, self.due = \
            index, prompt, max_new, due
        self.sent = self.done = self.tokens = self.error = None
