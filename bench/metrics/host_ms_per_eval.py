"""Host milliseconds inside ``execute_study`` per evaluation: the harness's
clock around each call (planning excluded), summed, over the calls'
evaluations. The engine does not wait for the device, so this is the
scheduler's and the enqueue's time, which the device's pace bounds."""


def read(ctx):
    calls = ctx["calls"]
    evals = sum(c["evals"] for c in calls)
    return 1e3 * sum(c["t1"] - c["t0"] for c in calls) / evals if evals else None
