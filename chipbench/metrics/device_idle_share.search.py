"""Device: share of the traced sub-window in which no operation ran on the
chip (1 - busy / window, busy the union of the trace's operation
intervals)."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
