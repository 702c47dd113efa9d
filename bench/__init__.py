"""Chip benchmark of the serving stack: one command runs one cell of
`BENCHMARK.json` and prints one JSON result line (see `bench/run.py`)."""
