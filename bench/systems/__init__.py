"""The system under test, as the benchmark builds and drives it."""
