"""Share of the traced window (%) in which no operation ran on the
device."""


def read(r):
    w = r.get("device.window_s")
    if not w:
        return None
    return 100.0 * (1.0 - r["device.busy_s"] / w)
