"""1 - (union of the device's operation intervals) / traced window, in %,
averaged over the chips the cell uses."""


def read(rec):
    t = rec.get("trace")
    if not t or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
