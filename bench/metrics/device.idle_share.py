"""Share of the traced window in which no operation ran on the device:
1 - (union of device op intervals, averaged over chips) / window."""


def read(run):
    d = run.device
    if not d["window_s"] or not d["busy_s"]:
        return None
    return 100.0 * (1.0 - d["busy_s"] / d["window_s"])
