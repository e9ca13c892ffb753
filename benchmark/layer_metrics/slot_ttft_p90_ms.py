"""90th percentile of the slot loop's own time to first token
(``SlotLoop._ttft`` since ``reset_stats()`` at the window's opening; at most
its last 512).  A SCHEDULER number: timed from the moment a Server worker
handed the row to the loop, not from when the client asked."""
from benchmark.harness import percentile


def compute(ctx):
    ttft = ctx["counters"].get("slot_ttft_s")
    return 1e3 * percentile(ttft, 90) if ttft else None
