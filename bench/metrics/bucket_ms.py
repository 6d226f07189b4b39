"""Mean wall time of one runner call (ms): pad on the host, copy in, run
the engine ladder, and the blocking copy out, as ``launch/serve.py``'s
``run_rows`` does them; host clock, calls that start in the window."""

import numpy as np


def value(rec):
    spans = rec.get("spans")
    if not spans:
        return None
    return 1e3 * float(np.mean([b - a for a, b, _ in spans]))
