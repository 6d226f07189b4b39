"""Median request latency (ms), over the same requests as
``serve_p99_ms``."""

from bench.metrics._stats import nearest_rank


def value(rec):
    if "latency_s" not in rec:
        return None
    return 1e3 * nearest_rank(rec["latency_s"], 50)
