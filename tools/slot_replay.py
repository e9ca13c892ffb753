#!/usr/bin/env python3
"""Replay the slot loop's schedule on the host, with no model and no chip.

The lockstep loop (``paddle_tpu/serving/slots.py``) is deterministic once
the lengths are: a request admitted at frontier ``pos`` with ``n`` chunks
is activated at ``max(n x T, pos + n)`` (``_plan_act``), its chunk ``k``
is dispatched once ``pos > act - n + k`` (``_dispatch_chunks``), a loop
with no generating row jumps the frontier to the earliest activation
(``_fast_forward``), and a step moves it one column.  The loop keeps one
step in flight (it dispatches step k+1 before it reads step k), and the
replay follows its retirement timing: a row that ends by ``max_new`` leaves
its slot when its last step is dispatched, so the slot is admitted into in
the next iteration; a row that ends by the end token is found out one step
later, holds its slot for that step (``retire_lag_pct`` of the slot-steps)
and frees it an iteration later.  The caller has its answer when the step
that produced it is read, either way.  With the next step always queued
behind the last, ``--step-ms`` is the DEVICE's step wherever the driver's
own milliseconds fit under it (``steps_read_ready`` near 0: every read
waits for the device), not device plus host.  Given a closed-loop
traffic file, a configuration's ``serve`` block, and a constant step
period and chunk time (from any traced run), this replays those rules for
the file's own sequence of lengths and prints when the cold round ends
(the frontier past the longest prompt bucket with all slots but one
generating), how many answers a window of ``--seconds`` holds after
``--ramp`` seconds, and how many of them come by ``--by`` seconds: what
``ramp_s`` and ``job_requests`` are sized from before any chip time
(PERF.md section 6, PR 40: the round's end to 0.3 s, the answers to 1).

    python3 tools/slot_replay.py <config> <traffic> --step-ms 29 --chunk-ms 43.5

``--first-seen`` is how many of the callers' first requests the loop's
first admission finds queued (a race; 1 or 2 in every run sampled): the
frontier starts at the lowest padded prompt end among them.
``--end-token-share`` is the share of the answers that end by the end token
(at their drawn length) and not by ``max_new``: the benchmark's traffic has
none, a deployment with an end token has mostly such.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _load(kind, name):
    path = name if os.path.exists(name) else os.path.join(
        ROOT, "benchmark", kind, name + ".json")
    with open(path) as f:
        return json.load(f)


def replay(config: dict, traffic: dict, *, step_s: float, chunk_s: float,
           ramp_s: float = None, seconds: float = 45.0, first_seen: int = 1,
           end_token_share: float = 0.0):
    """-> {"start", "round_end_s", "answers" (seconds after the window's
    opening, those inside it), "emitting_pct", "prefilling_pct",
    "retire_lag_pct"}."""
    import numpy as np
    from benchmark.generators import requests
    sv = config["serve"]
    S, T = int(sv["slots"]), int(sv["prefill_chunk"])
    callers = int(traffic["clients_per_slot"] * S)
    ramp = float(traffic["ramp_s"] if ramp_s is None else ramp_s)
    n = int(traffic["pool_requests"])
    shape = np.random.default_rng(int(traffic["shape_seed"]))
    plen = requests._lengths(traffic["prompt_len"], n, shape)
    mnew = requests._lengths(traffic["max_new_tokens"], n, shape)
    by_token = np.random.default_rng(int(traffic["shape_seed"]) + 1) \
        .random(n) < end_token_share
    top = -(-int(traffic["prompt_len"]["max"]) // T) * T
    pending, sent = collections.deque(), 0

    def submit():
        nonlocal sent
        pending.append((int(plen[sent % n]), int(mnew[sent % n]),
                        bool(by_token[sent % n])))
        sent += 1

    def release():
        """The step whose read finds a row's end token is the one behind
        the step that passed the row by: its slot is free from here."""
        for i, s in enumerate(slots):
            if s and s["lag"]:
                slots[i] = None

    slots = [None] * S
    pos, now, done, start, end = 0, 0.0, [], None, None
    steps = gen_steps = pre_steps = lag_steps = 0
    for _ in range(first_seen):
        submit()
    while now < ramp + seconds:
        for i in range(S):                                   # _admit
            if slots[i] is None and pending:
                lp, mn, tok = pending.popleft()
                k = -(-lp // T)
                slots[i] = {"gen": False, "act": max(k * T, pos + k), "n": k,
                            "next": 0, "left": mn, "by_token": tok,
                            "lag": False}
        while sent < callers:            # the rest arrive behind the first
            submit()
        for s in slots:                                      # chunks
            if s and not s["gen"]:
                while s["next"] < s["n"] \
                        and s["act"] - s["n"] + s["next"] < pos:
                    now += chunk_s
                    s["next"] += 1
        for s in slots:                                      # _activate
            if s and not s["gen"] and s["next"] == s["n"] \
                    and pos == s["act"]:
                s["gen"] = True
        gen = [s for s in slots if s and s["gen"]]
        if not gen:                                          # _fast_forward
            release()                     # (it settles the step in flight)
            acts = [s["act"] for s in slots if s]
            if not acts:
                break
            pos = max(pos, min(acts))
            start = pos if start is None else start
            continue
        now += step_s
        pos += 1
        if end is None and pos > top and len(gen) >= S - 1:
            end = now
        if now >= ramp:
            steps += 1
            gen_steps += len(gen)
            lag_steps += sum(1 for s in slots if s and s["lag"])
            pre_steps += sum(1 for s in slots
                             if s and not s["gen"] and not s["lag"])
        release()
        for i, s in enumerate(slots):
            if s and s["gen"]:
                s["left"] -= 1
                if s["left"] <= 0:
                    done.append(now)
                    submit()
                    if s["by_token"]:
                        s["gen"], s["lag"] = False, True
                    else:
                        slots[i] = None
    share = 100.0 / max(steps * S, 1)
    return {"start": start, "round_end_s": end,
            "answers": [d - ramp for d in done if ramp <= d < ramp + seconds],
            "emitting_pct": gen_steps * share,
            "prefilling_pct": pre_steps * share,
            "retire_lag_pct": lag_steps * share}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("traffic")
    ap.add_argument("--step-ms", type=float, required=True)
    ap.add_argument("--chunk-ms", type=float, required=True)
    ap.add_argument("--ramp", type=float)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--by", type=float, default=34.0)
    ap.add_argument("--first-seen", type=int, default=1)
    ap.add_argument("--end-token-share", type=float, default=0.0)
    args = ap.parse_args(argv)
    out = replay(_load("configs", args.config), _load("traffic", args.traffic),
                 step_s=args.step_ms / 1e3, chunk_s=args.chunk_ms / 1e3,
                 ramp_s=args.ramp, seconds=args.seconds,
                 first_seen=args.first_seen,
                 end_token_share=args.end_token_share)
    at = out.pop("answers")
    out.update(answers_in_window=len(at),
               answers_by=sum(1 for a in at if a <= args.by))
    print(json.dumps(out))


if __name__ == "__main__":
    main()
