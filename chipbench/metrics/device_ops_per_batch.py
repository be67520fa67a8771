"""Pass plan (`kernels.ntt.ntt_pallas`, `kernels.modmul.modmul_pallas`):
device operations per completed batch in the traced steady window, over
all chips: kernels, XLA fusions, copies, pads and slices.  A count, so a
pass or copy that the plan adds shows."""

UNIT = "ops"


def read(r):
    return r.device_ops / r.batches if r.device_ops and r.batches else None
