"""Share of slot-steps that emitted a token: ``emitted_tokens / (steps x
slots)`` from ``SlotLoop.counters`` over the window."""


def compute(ctx):
    c = ctx["counters"].get("slot_loop")
    if not c or not c.get("steps"):
        return None
    return 100.0 * c["emitted_tokens"] / (c["steps"] * c["slots"])
