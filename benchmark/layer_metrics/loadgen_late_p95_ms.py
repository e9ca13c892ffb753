"""How late the load generator ran: 95th percentile of (actual send - due)
over the requests due in the window; the generator's own clock.  A starved
generator must not be read as a fast server."""
from benchmark.harness import percentile


def compute(ctx):
    late = ctx["counters"].get("loadgen_late_ms")
    return percentile(late, 95) if late else None
