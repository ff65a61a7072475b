"""Plain references of the benchmark's model configurations."""
