"""Helpers the reducers share (a file a metric's name never takes)."""

from __future__ import annotations

import math

import numpy as np


def nearest_rank(values, q: float) -> float:
    """The q-th percentile by nearest rank: a value that was observed, and
    +inf counts (a failed request misses every limit)."""
    v = np.sort(np.asarray(values, float))
    if v.size == 0:
        return math.nan
    return float(v[max(int(math.ceil(q / 100.0 * v.size)) - 1, 0)])


def kernel_time(trace: dict, patterns) -> tuple:
    """(seconds, calls) of the device operations whose name holds any of
    ``patterns``, inside the traced window."""
    names = [n for n in trace["op_s"] if any(p in n for p in patterns)]
    return (sum(trace["op_s"][n] for n in names),
            sum(trace["op_n"][n] for n in names))
