"""Roofline share of the convolutional TM's kernel (%): the least time the
chip could take for the buckets' work (``bench/work_conv.py``: operations
from the model's published sizes, at the bucket's padded batch, against
the int8 peak, or the bytes against HBM bandwidth, whichever is longer),
over the kernel's device time in the trace.  The trace names the kernel
after the jitted wrapper of its ``pallas_call``, ``conv_tm_forward``; a
run without it reads nothing."""

from bench import work, work_conv
from bench.metrics._stats import kernel_time

KERNELS = ("conv_tm_forward",)


def value(rec):
    tr = rec.get("trace")
    if not tr or "image_h" not in rec["cfg"]:
        return None
    secs, calls = kernel_time(tr, KERNELS)
    if secs <= 0 or calls == 0:
        return None
    cfg, b = rec["cfg"], rec["bucket"]
    share, _ = work.roofline(calls * b * work_conv.ops_per_image(cfg),
                             calls * work_conv.bytes_per_call(cfg, b),
                             secs, rec["peaks"])
    return share
