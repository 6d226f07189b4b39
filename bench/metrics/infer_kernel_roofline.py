"""Roofline share of the inference kernel that served (%): the least time
the chip could take for the buckets' work (``bench/work.py``: dense
operations from the model's sizes, at the bucket's padded batch, against
the int8 peak, or the bytes against HBM bandwidth, whichever is longer),
over the kernel's device time in the trace.  Whichever engine served,
the work is the same.  The trace names each kernel after the jitted
wrapper of its ``pallas_call``: ``factorized_tm_forward`` (term_infer),
``sparse_tm_forward`` (sparse_infer), ``fused_tm_forward`` (fused_infer).
"""

from bench import work
from bench.metrics._stats import kernel_time

KERNELS = ("factorized_tm_forward", "sparse_tm_forward", "fused_tm_forward")


def value(rec):
    tr = rec.get("trace")
    if not tr or rec.get("kind") == "train_loop":
        return None
    secs, calls = kernel_time(tr, KERNELS)
    if secs <= 0 or calls == 0:
        return None
    cfg, b = rec["cfg"], rec["bucket"]
    share, _ = work.roofline(calls * b * work.infer_ops_per_sample(cfg),
                             calls * work.infer_bytes_per_call(cfg, b),
                             secs, rec["peaks"])
    return share
