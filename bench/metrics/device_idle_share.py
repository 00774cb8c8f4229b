"""Percent of the traced window in which no program ran on the device:
one minus the union of the device's module intervals over the window."""


def read(ctx):
    t = ctx.get("trace")
    if not t or t["window_ns"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_ns"] / t["window_ns"])
