"""90th percentile of request latency (ms), by nearest rank, over the
same requests as ``serve_p99_ms``: every request due in the window, from
when the schedule made it due to when its answer resolved at the client.
A shed or unanswered request counts as missing every limit."""

from bench.metrics._stats import nearest_rank


def value(rec):
    if "latency_s" not in rec:
        return None
    return 1e3 * nearest_rank(rec["latency_s"], 90)
