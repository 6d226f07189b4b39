"""Metric reducers: one file per metric, found by name (bench/core.py)."""
