"""Mean draft length the planner granted per decode row-iteration in the
window (`IterationTelemetry.k_granted`)."""


def read(run):
    if not run.iterations:
        return None
    return sum(it.k_granted for it in run.iterations) / len(run.iterations)
