"""Mean over the window's passes of the engine's `StepTelemetry.
union_experts`: distinct experts the live tokens of a pass routed to, mean
over MoE layers."""


def read(run):
    if not run.steps:
        return None
    return sum(s.union_experts for s in run.steps) / len(run.steps)
