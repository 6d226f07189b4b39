"""Share of the traced window in which no operation ran on the device (%):
1 minus the union of the device's operation intervals over the window."""


def value(rec):
    tr = rec.get("trace")
    if not tr or not tr["n_devices"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
