"""Device milliseconds of the propagation operators per evaluation: their
share of the traced span's time (the device time of the modules in
``bench/roofline.py``'s table that start in it, over its length) times the
window's milliseconds per evaluation. The trace covers the window's first
``run_seconds``, and at 4096² no evaluation completes inside it."""

import roofline


def read(ctx):
    t = ctx.get("trace")
    rate = ctx["window"]["evals_per_s"]
    if not t or t["window_ns"] <= 0 or rate <= 0:
        return None
    ns = sum(t["modules"][m][1] for m in roofline.PROPAGATION if m in t["modules"])
    return ns / t["window_ns"] * 1e3 / rate if ns else None
