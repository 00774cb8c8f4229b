"""Evaluations completed per second of the window: the evaluations of the
whole groups fed in it, over the time from the first group's start to the
last group's masks and Dice being ready on the device (host clock)."""


def read(ctx):
    return ctx["window"]["evals_per_s"]
