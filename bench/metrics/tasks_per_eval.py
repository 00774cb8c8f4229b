"""Engine tasks executed per evaluation, over every ``execute_study`` call
of the run: ``tasks_executed`` (cache hits excluded) over the evaluations
those calls returned. Counts the reuse engine's work; moves evals_per_s."""


def read(ctx):
    calls = ctx["calls"]
    evals = sum(c["evals"] for c in calls)
    return sum(c["tasks"] for c in calls) / evals if evals else None
