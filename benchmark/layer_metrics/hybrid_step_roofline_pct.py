"""Roofline share of the step program of a decoder that keeps conv states
beside K/V planes: the least time the chip could take for the window's
average step (the larger of least bytes / peak bytes/s and operations /
peak FLOP/s, from ``benchmark/counts/<family>.py``'s ``step``) over the
step's median device time in the trace.  The average step's live rows,
expert assignments and valid K/V columns are the slot loop's own counters
(the chunks' part taken off the totals).  None where the program keeps no
such counters or the family has no such count."""
import importlib

from benchmark.layer_metrics import _slot_loop


def per_dispatch(c: dict, chunk: bool):
    """(tokens, expert assignments, valid columns) of the average chunk or
    step from ``SlotLoop.counters``, or None."""
    need = ("moe_assignments", "chunk_moe_assignments", "chunk_tokens",
            "kv_columns_valid", "chunk_kv_columns_valid", "emitted_tokens")
    n = c.get("chunks" if chunk else "steps")
    if not n or any(k not in c for k in need):
        return None
    if chunk:
        return tuple(c[k] / n for k in (
            "chunk_tokens", "chunk_moe_assignments", "chunk_kv_columns_valid"))
    return (c["emitted_tokens"] / n,
            (c["moe_assignments"] - c["chunk_moe_assignments"]) / n,
            c["kv_columns_valid"] / n)


def share(ctx, which: str):
    tr, peaks = ctx.get("trace"), ctx.get("peaks")
    prog = (tr or {}).get("programs", {}).get(ctx["programs"].get(which))
    args = per_dispatch(_slot_loop.stats(ctx), which == "chunk")
    if not prog or not peaks or args is None:
        return None
    try:
        counts = importlib.import_module(f"benchmark.counts.{ctx['family']}")
    except ImportError:
        return None
    if not hasattr(counts, which):
        return None
    least = getattr(counts, which)(ctx["config"], *args)
    least_s = max(least["bytes"] / peaks["hbm_bytes_per_s"],
                  least["flops"] / peaks["bf16_flops_per_s"])
    return 100.0 * least_s / prog["median_s"]


def compute(ctx):
    return share(ctx, "step")
