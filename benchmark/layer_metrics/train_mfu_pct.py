"""Model FLOP/s utilisation of the traced run: the benchmark's own count of
one step's operations (``benchmark/counts/<family>.py``) x steps per second
of this run's window / the chip's published bf16 peak."""
import importlib


def compute(ctx):
    w = ctx["window"]
    if not w.get("steps") or not ctx.get("peaks"):
        return None
    counts = importlib.import_module(f"benchmark.counts.{ctx['family']}")
    flops = counts.train_flops_per_step(ctx["config"])
    chips = (ctx.get("trace") or {}).get("chips", 1)
    return 100.0 * flops * w["steps"] / w["seconds"] / (
        chips * ctx["peaks"]["bf16_flops_per_s"])
