"""Share of the traced slice in which no operation ran on the device:
1 - union of device-op intervals / traced window, from the ``.xplane.pb``."""


def compute(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
