"""Traffic of kind ``closed_loop_docs``: a fixed number of callers, each a
worker that holds ONE document at a time, puts ``k`` questions to it one
after another (the next when the last resolves), then takes the next
document of the pool.  A request's prompt is ``document ‖ question``: the
asks of one document share its tokens to the token and nothing else is
shared, so all but a document's first ask can be served from the prefix
cache.  One driver thread; completions come back through the futures'
callbacks, as in ``closed_loop``.

Parameters (the traffic file): ``clients_per_slot`` (x the decode slots);
``ramp_s`` seconds before the window opens (the cold round, in which every
caller's first ask is a miss; counted as set-up); ``shape_seed``,
``pool_docs``; ``asks_per_doc`` (``min`` .. ``max``, uniform) and
``first_doc_asks_mod`` (caller ``c``'s FIRST document has ``1 + c mod
that`` asks, so that after the cold round the callers stand at different
places in their documents); ``doc_len``, ``question_len`` and
``max_new_tokens`` (``requests._lengths`` distributions).  Every seed gets
the SAME documents' lengths, asks, question and answer lengths in the same
order (``shape_seed``), with its own token ids.
"""
from __future__ import annotations

import queue
import time

import numpy as np

from . import requests


class DocRecord(requests.Record):
    """A request that knows which document it asks, and which ask of it
    this is (0: the document's first, which no cache can serve)."""
    __slots__ = ("doc", "ask")


class Docs:
    """The pool: document ``d`` has ``asks[d]`` questions; its token ids
    and its questions' are drawn from ``(seed, d)`` alone, whoever asks."""

    def __init__(self, traffic: dict, vocab: int, seed: int, clients: int):
        n = int(traffic["pool_docs"])
        shape = np.random.default_rng(int(traffic["shape_seed"]))
        lo, hi = (int(traffic["asks_per_doc"][k]) for k in ("min", "max"))
        self.asks = shape.integers(lo, hi + 1, n)
        mod = int(traffic.get("first_doc_asks_mod", 0))
        if mod:
            first = min(clients, n)
            self.asks[:first] = 1 + np.arange(first) % mod
        self.doc_len = requests._lengths(traffic["doc_len"], n, shape)
        most = int(self.asks.max())
        self.question_len = requests._lengths(
            traffic["question_len"], n * most, shape).reshape(n, most)
        self.max_new = requests._lengths(
            traffic["max_new_tokens"], n * most, shape).reshape(n, most)
        self.vocab, self.seed = int(vocab), int(seed)
        self._next = 0                       # the next document to hand out
        self._held = {}                      # client -> [doc, ask, doc ids]

    def _ids(self, d: int, part: int, n: int) -> np.ndarray:
        # any whole seed, 2**31 and over too: a sequence seeds the generator
        rng = np.random.default_rng([self.seed, d, part])
        return rng.integers(0, self.vocab, n, dtype=np.int32)

    def next(self, client: int):
        """(prompt ids int32 [P], max_new, document, ask) of ``client``'s
        next request: the next ask of the document it holds, or the first
        of a new one."""
        held = self._held.get(client)
        if held is None or held[1] >= self.asks[held[0]]:
            d = self._next % len(self.asks)
            self._next += 1
            held = self._held[client] = [d, 0, self._ids(d, 0, self.doc_len[d])]
        d, a, doc = held
        held[1] += 1
        question = self._ids(d, 1 + a, self.question_len[d, a])
        return (np.concatenate([doc, question]), int(self.max_new[d, a]),
                int(d), int(a))


def drive(traffic, seed, seconds, submit, *, vocab_size, slots,
          on_open=None, on_close=None, span=None):
    """Run ramp + window; returns (records, t_open, t_close).  ``submit``
    takes (prompt, max_new) and returns a Future."""
    clients = int(traffic["clients_per_slot"] * slots)
    docs = Docs(traffic, vocab_size, seed, clients)
    ready = queue.SimpleQueue()
    for c in range(clients):
        ready.put(c)
    records = []
    t_open = time.monotonic() + float(traffic["ramp_s"])
    t_close = t_open + seconds
    opened = False
    while True:
        now = time.monotonic()
        if not opened and now >= t_open:
            opened = True
            if on_open:
                on_open()
        if now >= t_close:
            break
        try:
            client = ready.get(timeout=0.02)
        except queue.Empty:
            continue
        prompt, max_new, doc, ask = docs.next(client)
        rec = DocRecord(len(records), prompt, max_new, time.monotonic())
        rec.doc, rec.ask = doc, ask
        records.append(rec)

        def finished(fut, rec=rec, client=client):
            t_done = time.monotonic()
            try:
                rec.tokens = fut.result()
            except Exception as e:   # noqa: BLE001 — counted as failed
                rec.error = e
            rec.done = t_done        # last: a record with ``done`` is whole
            ready.put(client)

        rec.sent = time.monotonic()
        try:
            with span("submit"):
                submit(prompt, max_new).add_done_callback(finished)
        except Exception as e:   # noqa: BLE001 — a refusal is a failure
            rec.error, rec.done = e, time.monotonic()
            ready.put(client)
    if on_close:
        on_close()
    return records, t_open, t_close
