"""Mean host time of one ``step(...)`` call, which returns before the
device is done: host prep + dispatch, no fence added.  Source: the harness's
clock around the call, over the whole window."""


def compute(ctx):
    steps = ctx["window"].get("steps")
    total = ctx["counters"].get("train_step_call_s")
    if not steps or total is None:
        return None
    return 1e3 * total / steps
