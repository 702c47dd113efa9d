"""Mean live rows per engine step in the window
(`StepTelemetry.occupancy`)."""


def read(run):
    if not run.steps:
        return None
    return sum(s.occupancy for s in run.steps) / len(run.steps)
