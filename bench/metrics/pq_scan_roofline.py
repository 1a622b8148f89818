"""Roofline share of the IVF-PQ code-scan kernel
(``ops.pallas_ivf_scan``, the one-hot decode and score of the codes):
the work a batch needs (``roofline.batch_work``) against the kernel's
device time per program execution in the traced window."""

import scan_roofline

KERNEL = "pq"


def read(ctx):
    return scan_roofline.read(ctx, KERNEL)
