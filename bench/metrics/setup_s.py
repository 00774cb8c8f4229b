"""Seconds from the start of the process to the start of the window: chip
check, compile cache, tiles, default masks, warm-up and the Manager session
(host clock)."""


def read(ctx):
    return ctx["setup_s"]
