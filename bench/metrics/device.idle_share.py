"""Share of the traced window in which no operation ran on the device,
in %: one minus the union of the device's operation intervals over the
window."""


def read(record):
    t = record["trace"]
    if t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
