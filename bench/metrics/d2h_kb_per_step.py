"""Mean over the window's passes of the bytes the engine copied from the
device to the host after the pass (`StepTelemetry.d2h_bytes`), in KB of
1000 bytes. A program whose telemetry lacks the field reads nothing."""


def read(run):
    sizes = [getattr(s, "d2h_bytes", None) for s in run.steps]
    if not sizes or None in sizes:
        return None
    return sum(sizes) / len(sizes) / 1e3
