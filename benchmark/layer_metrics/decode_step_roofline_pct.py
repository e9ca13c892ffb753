"""Roofline share of the decode step program: the least time the chip could
take for the window's average step — the larger of least bytes / peak
bytes/s and operations / peak FLOP/s, from ``benchmark/counts/<family>.py``
— over the step's median device time in the trace.  Least bytes are the
bf16 weights once plus the VALID key/value columns of the live rows (from
the finished requests' lengths, averaged over the window's steps), so the
share says how far the program is from what the work needs, not from what
it chooses to read.  Bandwidth bounds it at these sizes."""
import importlib


def compute(ctx):
    tr, peaks = ctx.get("trace"), ctx.get("peaks")
    prog = (tr or {}).get("programs", {}).get(ctx["programs"].get("step"))
    c = ctx["counters"].get("slot_loop")
    if not prog or not peaks or not c or not c.get("steps"):
        return None
    counts = importlib.import_module(f"benchmark.counts.{ctx['family']}")
    cols = ctx["window"]["valid_kv_columns"] / c["steps"]
    rows = c["emitted_tokens"] / c["steps"]
    least_s = max(
        counts.decode_step_min_bytes(ctx["config"], cols) / peaks["hbm_bytes_per_s"],
        counts.decode_step_flops(ctx["config"], rows, cols) / peaks["bf16_flops_per_s"])
    return 100.0 * least_s / prog["median_s"]
