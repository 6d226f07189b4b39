"""Samples of the training steps completed in the window, divided by the
window, which ends when the last step has finished (samples/s)."""


def value(rec):
    if rec.get("kind") != "train_loop":
        return None
    return rec["samples"] / rec["window_s"]
