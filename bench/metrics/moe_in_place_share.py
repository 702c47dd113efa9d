"""Share, in %, of the window's passes whose MoE FFN read the stacked
expert weights in place (`StepTelemetry.experts_in_place`) rather than
through a gather of the union's slots. A program whose telemetry lacks the
field reads nothing."""


def read(run):
    flags = [getattr(s, "experts_in_place", None) for s in run.steps]
    if not flags or None in flags:
        return None
    return 100.0 * sum(map(bool, flags)) / len(flags)
