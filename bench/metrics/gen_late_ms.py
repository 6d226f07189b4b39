"""How late the open-loop generator offered its requests: the 99th
percentile of offered minus due time (ms).  A large value means the host
starved the generator, and latency then reads the generator as well as
the server."""

import numpy as np

from bench.metrics._stats import nearest_rank


def value(rec):
    if "due" not in rec:
        return None
    late = rec["offered"] - rec["due"]
    return 1e3 * nearest_rank(late[np.isfinite(late)], 99)
