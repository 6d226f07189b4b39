"""Requests answered inside the window, divided by the window (req/s)."""


def value(rec):
    if "answered_in_window" not in rec:
        return None
    return rec["answered_in_window"] / rec["window_s"]
