"""Share of the HBM-bandwidth roofline that the propagation operators reach
in the traced window: calls times each call's least bytes
(``bench/roofline.py``), over the chip's peak bandwidth (``bench/peaks.json``),
over their device time."""

import roofline
import trace


def read(ctx):
    t = ctx.get("trace")
    if not t:
        return None
    least = roofline.least_bytes(ctx["tile_px"])
    return trace.roofline_share(t["modules"], least, ctx["peaks"]["hbm_bytes_per_s"])
