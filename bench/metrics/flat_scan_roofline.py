"""Roofline share of the IVF-Flat fine-scan kernel
(``ops.pallas_ivf_scan``, the fused list scan and select): the work a
batch needs (``roofline.batch_work``) against the kernel's device time
per program execution in the traced window."""

import scan_roofline

KERNEL = "flat"


def read(ctx):
    return scan_roofline.read(ctx, KERNEL)
