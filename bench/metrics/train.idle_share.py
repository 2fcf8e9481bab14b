"""Share of the traced training window in which no operation ran on the
device: 1 - (union of busy intervals) / window, averaged over the chips."""


def read(rec):
    t = rec["trace"]
    if not t or t["window_s"] <= 0 or not t["busy_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
