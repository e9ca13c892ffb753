"""Traffic of kind ``open_loop``: requests are sent on a schedule fixed by
the traffic file, whether or not earlier ones have finished, and each is timed from
the moment it was DUE.  One driver thread.

Parameters (the traffic file): ``rate_per_s`` and ``arrivals``
("poisson"); ``ramp_s`` seconds of the same arrivals before the window
opens (warm-up, counted as set-up); ``drain_s``, how long after the window
a due request may still resolve before it counts as missing; the pool's
``shape_seed``, ``pool_requests``, ``prompt_len`` and ``max_new_tokens``.
"""
from __future__ import annotations

import time

from . import requests


def drive(traffic, seed, seconds, submit, *, vocab_size, slots=None,
          on_open=None, on_close=None, span=None):
    """Run ramp + window + drain; returns (records, t_open, t_close).  An
    open loop does not look at the system's ``slots``."""
    if traffic["arrivals"] != "poisson":
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    pool = requests.pool(traffic, vocab_size, seed)
    gaps = requests.poisson_gaps(traffic)
    records = []
    t0 = time.monotonic()
    t_open = t0 + float(traffic["ramp_s"])
    t_close = t_open + seconds
    due = t0 + float(gaps[0])
    opened = False
    while True:
        now = time.monotonic()
        if not opened and now >= t_open:
            opened = True
            if on_open:
                on_open()
        if now >= t_close:
            break
        if due >= t_close:
            time.sleep(min(0.02, t_close - now))
            continue
        if now < due:
            time.sleep(min(0.02, due - now))
            continue
        i = len(records)
        prompt, max_new = pool[i % len(pool)]
        rec = requests.Record(i, prompt, max_new, due)
        records.append(rec)

        def finished(fut, rec=rec):
            t_done = time.monotonic()
            try:
                rec.tokens = fut.result()
            except Exception as e:   # noqa: BLE001 — counted as failed
                rec.error = e
            rec.done = t_done        # last: a record with ``done`` is whole

        rec.sent = time.monotonic()
        try:
            with span("submit"):
                submit(prompt, max_new).add_done_callback(finished)
        except Exception as e:   # noqa: BLE001 — a refusal is a failure
            rec.error, rec.done = e, time.monotonic()
        due += float(gaps[(i + 1) % len(gaps)])
    if on_close:
        on_close()
    deadline = t_close + float(traffic["drain_s"])
    with span("await"):
        while time.monotonic() < deadline and any(
                r.done is None for r in records if r.due >= t_open):
            time.sleep(0.02)
    return records, t_open, t_close
