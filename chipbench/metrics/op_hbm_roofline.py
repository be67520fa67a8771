"""Kernels (Pallas): the request's least HBM time over its device busy time.

Least HBM time = the op's ideal bytes (each row read and written once;
a product's row read twice and written once) over the device's HBM peak
from `peaks.json`.  Twiddles and extra passes are not counted, so a PR
that fuses or removes a pass moves this share without redefining it.
No integer VPU peak is published for the chip, so HBM is the only bound.
"""

UNIT = "%"


def read(r):
    if not r.busy_s or not r.requests:
        return None
    return 100.0 * (r.ideal_bytes / r.hbm_bytes_per_s) / (r.busy_s / r.requests)
