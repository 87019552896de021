"""Share of the traced window in which no operation ran on the device
(1 - union of the device's operation intervals over the window)."""
UNIT = "%"


def read(w):
    r = w.reduced
    if r is None:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)
