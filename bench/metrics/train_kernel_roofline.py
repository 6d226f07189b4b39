"""Roofline share of the fused training kernel (%): the least time the
chip could take for its work in the window (``bench/work.py``), over its
device time in the trace."""

from bench import work
from bench.metrics._stats import kernel_time

KERNELS = ("fused_tm_train_delta",)


def value(rec):
    tr = rec.get("trace")
    if not tr or rec.get("kind") != "train_loop":
        return None
    secs, calls = kernel_time(tr, KERNELS)
    if secs <= 0 or calls == 0:
        return None
    cfg, b = rec["cfg"], rec["batch"]
    share, _ = work.roofline(calls * b * work.train_kernel_ops_per_sample(cfg),
                             calls * work.train_kernel_bytes_per_step(cfg, b),
                             secs, rec["peaks"])
    return share
