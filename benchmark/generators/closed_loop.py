"""Traffic of kind ``closed_loop``: a fixed number of callers, each sending
its next request when its last one resolves.  One driver thread;
completions come back through the futures' callbacks.

Parameters (the traffic file): ``clients_per_slot`` (x the decode slots
that the runner says the system has); ``ramp_s`` seconds of the same
traffic before the window opens (warm-up, counted as set-up); the pool's
``shape_seed``, ``pool_requests``, ``prompt_len`` and ``max_new_tokens``.
"""
from __future__ import annotations

import queue
import time

from . import requests


def drive(traffic, seed, seconds, submit, *, vocab_size, slots,
          on_open=None, on_close=None, span=None):
    """Run ramp + window; returns (records, t_open, t_close).  ``submit``
    takes (prompt, max_new) and returns a Future."""
    pool = requests.pool(traffic, vocab_size, seed)
    clients = int(traffic["clients_per_slot"] * slots)
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
        prompt, max_new = pool[len(records) % len(pool)]
        rec = requests.Record(len(records), prompt, max_new, time.monotonic())
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
