"""Mean time a request waited in the gateway (ms): from when it was due
to the start of the runner call for its bucket.  One tenant's queue is
first in, first out, so the buckets, in order, take the requests in the
order they were offered."""

import numpy as np


def value(rec):
    if "due" not in rec or not rec["all_spans"]:
        return None
    rows = np.array([s[2] for s in rec["all_spans"]])
    starts = np.array([s[0] for s in rec["all_spans"]])
    n = min(int(rows.sum()), len(rec["due"]))
    bucket_of = np.repeat(np.arange(len(rows)), rows)[:n]
    return 1e3 * float(np.mean(starts[bucket_of] - rec["due"][:n]))
