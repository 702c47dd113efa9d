"""Seconds from the start of the process to the start of the window:
imports, finding the chip, making the weights, building the engine and
compiling (or loading from the cache) every program the window runs."""


def read(run):
    return run.setup_s
