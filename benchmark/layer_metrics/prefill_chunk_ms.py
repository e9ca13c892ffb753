"""Median device time of one run of the slot loop's prefill-chunk program
in the trace (``XLA Modules`` line)."""


def compute(ctx):
    prog = (ctx.get("trace") or {}).get("programs", {}).get(
        ctx["programs"].get("chunk"))
    return 1e3 * prog["median_s"] if prog else None
