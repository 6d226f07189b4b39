"""Self-checks of the benchmark (run by hand: python -m pytest bench/checks)."""
