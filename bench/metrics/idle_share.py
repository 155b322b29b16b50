"""Share of the traced window in which no operation ran on the device:
1 - (union of device-operation intervals) / window."""


def read(name, run):
    r = run.reduced
    if not r["devices"] or not r["window_s"]:
        return None
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])
