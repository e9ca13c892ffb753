"""Roofline share of the latent decoder's step program: the least time the
chip could take for the window's average step — the larger of least bytes /
peak bytes/s and operations / peak FLOP/s, from ``benchmark/counts/
<family>.py``'s ``step`` — over the step's median device time in the trace.
The average step's rows, held assignments and selected / valid columns are
the slot loop's own counters (the chunks' held assignments taken off the
total)."""
import importlib

from benchmark.layer_metrics import _slot_loop


def per_dispatch(c: dict, chunk: bool):
    """(tokens, held assignments, selected, valid) of the average chunk or
    step from ``SlotLoop.counters``; None where the program has no such
    counters."""
    need = ("moe_assignments_held", "attn_columns_selected",
            "attn_columns_valid", "chunk_tokens",
            "chunk_moe_assignments_held", "chunk_attn_columns_selected",
            "chunk_attn_columns_valid")
    n = c.get("chunks" if chunk else "steps")
    if not n or any(k not in c for k in need):
        return None
    if chunk:
        return tuple(c[k] / n for k in (
            "chunk_tokens", "chunk_moe_assignments_held",
            "chunk_attn_columns_selected", "chunk_attn_columns_valid"))
    return (c["emitted_tokens"] / n,
            (c["moe_assignments_held"] - c["chunk_moe_assignments_held"]) / n,
            c["attn_columns_selected"] / n, c["attn_columns_valid"] / n)


def share(ctx, which: str):
    tr, peaks = ctx.get("trace"), ctx.get("peaks")
    prog = (tr or {}).get("programs", {}).get(ctx["programs"].get(which))
    args = per_dispatch(_slot_loop.stats(ctx), which == "chunk")
    if not prog or not peaks or args is None:
        return None
    counts = importlib.import_module(f"benchmark.counts.{ctx['family']}")
    if not hasattr(counts, which):
        return None
    least = getattr(counts, which)(ctx["config"], *args)
    least_s = max(least["bytes"] / peaks["hbm_bytes_per_s"],
                  least["flops"] / peaks["bf16_flops_per_s"])
    return 100.0 * least_s / prog["median_s"]


def compute(ctx):
    return share(ctx, "step")
