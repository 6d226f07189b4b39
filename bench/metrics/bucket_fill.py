"""Share of bucket slots filled with requests in the window (%): answered
over buckets times bucket size, from the gateway's own counters."""


def value(rec):
    if "counters" not in rec:
        return None
    c0, c1 = rec["counters"]
    buckets = c1["buckets"] - c0["buckets"]
    if buckets <= 0:
        return None
    return 100.0 * (c1["answered"] - c0["answered"]) / (buckets * rec["bucket"])
