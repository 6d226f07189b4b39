"""The convolutional TM's whole serving step as a share of the chip's int8
peak (%): requests answered in the window per second times the model's
operations per image (``bench/work_conv.py``), over the peak.  It bounds
the kernel's share of the same work from above."""

from bench import work_conv


def value(rec):
    if "answered_in_window" not in rec or "image_h" not in rec["cfg"]:
        return None
    rate = rec["answered_in_window"] / rec["window_s"]
    return (100.0 * rate * work_conv.ops_per_image(rec["cfg"])
            / rec["peaks"]["int8_ops_per_s"])
