"""The whole step's share of the chip's int8 peak (%): the end-to-end
rate of the run times the model's operations per sample or request
(``bench/work.py``), over the peak.  It bounds every kernel's share of
the same work from above, whichever kernels the path runs."""

from bench import work


def value(rec):
    peak = rec["peaks"]["int8_ops_per_s"]
    cfg = rec["cfg"]
    if rec.get("kind") == "train_loop":
        rate = rec["samples"] / rec["window_s"]
        return 100.0 * rate * work.train_ops_per_sample(cfg) / peak
    if "answered_in_window" in rec:
        rate = rec["answered_in_window"] / rec["window_s"]
        return 100.0 * rate * work.infer_ops_per_sample(cfg) / peak
    return None
