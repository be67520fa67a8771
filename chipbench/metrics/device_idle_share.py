"""Device (TPU): the share of the traced steady window in which no
operation ran, 1 - (union of device op intervals / window)."""

UNIT = "%"


def read(r):
    if r.busy_s is None or r.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)
