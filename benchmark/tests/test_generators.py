"""The generators: determinism from a seed, the same work for every seed,
and the open loop's due-time clock."""
import contextlib
import time
from concurrent.futures import Future

import numpy as np

from benchmark.generators import closed_loop, open_loop, requests, train_stream


def test_train_stream_is_seeded_and_rows_differ(bert_tiny):
    tr = {"pool_batches": 3, "fetch_loss_every": 10}
    a = train_stream.make(tr, bert_tiny, 7)
    b = train_stream.make(tr, bert_tiny, 7)
    c = train_stream.make(tr, bert_tiny, 2 ** 31 + 5)
    assert all(np.array_equal(x, y) for ba, bb in zip(a, b) for x, y in zip(ba, bb))
    assert not np.array_equal(a[0][0], c[0][0])
    ids, pos, labels = a[0]
    assert len({r.tobytes() for r in ids}) == ids.shape[0]
    assert np.array_equal(labels, np.take_along_axis(ids, pos, 1))
    assert all(len(set(p)) == len(p) for p in pos)


def test_pool_same_shapes_own_ids(closed_tiny):
    """Every seed replays the same lengths in the same order, with token
    ids of its own."""
    a = requests.pool(closed_tiny, 128, 1)
    b = requests.pool(closed_tiny, 128, 1)
    c = requests.pool(closed_tiny, 128, 2 ** 31 + 9)
    shape = lambda pool: [(p.size, m) for p, m in pool]  # noqa: E731
    assert shape(a) == shape(c) and len(set(shape(a))) > 8
    assert all(np.array_equal(x[0], y[0]) for x, y in zip(a, b))
    assert not all(np.array_equal(x[0], y[0]) for x, y in zip(a, c))
    spec = closed_tiny["prompt_len"]
    assert all(spec["min"] <= p.size <= spec["max"] for p, _ in a)
    assert all(0 <= int(p.min()) and int(p.max()) < 128 for p, _ in a)


def test_poisson_gaps_one_sequence_at_any_rate(open_tiny):
    a = requests.poisson_gaps(open_tiny)
    b = requests.poisson_gaps(dict(open_tiny, rate_per_s=2 * open_tiny["rate_per_s"]))
    assert np.allclose(a, 2 * b)
    assert abs(a.mean() - 1 / open_tiny["rate_per_s"]) < 0.3 / open_tiny["rate_per_s"]


def _instant(prompt, max_new):
    f = Future()
    f.set_result([np.zeros((1, max_new), np.int32)])
    return f


def test_open_loop_times_from_due(open_tiny, gpt_tiny):
    """Requests are due on the seed's schedule, whatever the server does;
    a stalled submit makes later requests late, not later due."""
    calls = []

    def slow_submit(prompt, max_new):
        calls.append(time.monotonic())
        if len(calls) == 3:
            time.sleep(0.3)            # a stall in the third send
        return _instant(prompt, max_new)

    tr = dict(open_tiny, ramp_s=0.0, drain_s=0.5)
    records, t_open, t_close = open_loop.drive(
        tr, 3, 1.5, slow_submit, vocab_size=gpt_tiny["vocab_size"],
        span=lambda n: contextlib.nullcontext())
    gaps = requests.poisson_gaps(tr)
    due = np.array([r.due for r in records])
    assert np.allclose(np.diff(due), gaps[1:len(due)], atol=1e-9)
    assert all(r.sent >= r.due for r in records)
    late = [r.sent - r.due for r in records]
    assert max(late[3:8]) > 0.05 and late[0] < 0.05
    assert all(r.done is not None and r.error is None for r in records)
    assert t_close - t_open == 1.5


def test_closed_loop_keeps_clients_busy(closed_tiny, gpt_tiny):
    pending = []

    def submit(prompt, max_new):
        f = Future()
        pending.append((f, max_new))
        return f

    import threading
    stop = threading.Event()

    def server():
        while not stop.is_set():
            while pending:
                f, n = pending.pop(0)
                f.set_result([np.zeros((1, n), np.int32)])
            time.sleep(0.001)

    t = threading.Thread(target=server, daemon=True)
    t.start()
    opened = []
    records, t_open, t_close = closed_loop.drive(
        dict(closed_tiny, clients_per_slot=1.5, ramp_s=0.2), 1, 0.5, submit,
        vocab_size=gpt_tiny["vocab_size"], slots=2,
        on_open=lambda: opened.append(time.monotonic()),
        span=lambda n: contextlib.nullcontext())
    stop.set()
    t.join()
    assert opened and abs(opened[0] - t_open) < 0.05
    assert len(records) > 30
    # never more than `clients` requests in flight
    events = sorted([(r.sent, 1) for r in records]
                    + [(r.done, -1) for r in records if r.done is not None])
    depth = peak = 0
    for _, d in events:
        depth += d
        peak = max(peak, depth)
    assert peak <= 3
