"""95th percentile (nearest rank) of every inter-delivery gap of every
request in the window, in ms: the wall time between two scheduler steps
that each handed that request at least one token."""

from bench.loop import percentile


def read(run):
    return 1e3 * percentile(run.gaps, 0.95) if run.gaps else None
