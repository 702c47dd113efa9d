"""Median (nearest rank), over requests submitted in the window, of the
wall time from submission to the end of the step that delivered the
first token, in ms."""

from bench.loop import percentile


def read(run):
    return 1e3 * percentile(run.ttfts, 0.50) if run.ttfts else None
