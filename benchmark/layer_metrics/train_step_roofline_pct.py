"""Roofline share of the training step program: the least time the chip
could take for one step — the larger of operations / peak FLOP/s and least
bytes / peak bytes/s, both from the benchmark's own counts — over the
step's median device time in the trace.  (For BERT-large at 16 x 512 the
operations bound it: 82.9 ms against 11.5 ms.)"""
import importlib


def compute(ctx):
    tr, peaks = ctx.get("trace"), ctx.get("peaks")
    prog = (tr or {}).get("programs", {}).get(ctx["programs"]["step"])
    if not prog or not peaks:
        return None
    counts = importlib.import_module(f"benchmark.counts.{ctx['family']}")
    least_s = max(
        counts.train_flops_per_step(ctx["config"]) / peaks["bf16_flops_per_s"],
        counts.train_min_bytes_per_step(ctx["config"]) / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / prog["median_s"]
