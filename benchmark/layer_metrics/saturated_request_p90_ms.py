"""90th percentile of (future resolved - request sent) over the requests
that resolved in the window, by the client's clock.  A layer metric where
the queue is always full: the tail then swings too much to decide a PR."""
from benchmark.harness import percentile


def compute(ctx):
    lat = ctx["counters"].get("served_latency_ms")
    return percentile(lat, 90) if lat else None
