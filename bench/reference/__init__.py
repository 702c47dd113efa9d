"""Plain float32 references of the benchmark's model configurations,
independent of the program under test."""
