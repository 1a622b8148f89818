"""Shared by the scan-roofline readers: find the kernel's events in the
trace, replay the traced window's batches and take the share.

The Pallas calls carry no ``name=``, so their trace names follow the
kernel function; ``KERNEL_OPS`` lists the substrings that pick each
kernel's ops out of the device's op line.
"""

from __future__ import annotations

import roofline

KERNEL_OPS = {"flat": ("fused_list_scan",), "pq": ("pq_scan",)}


def read(ctx, kernel: str):
    if ctx.trace is None or ctx.probe_table is None \
            or ctx.layout.get("kind") != kernel:
        return None
    marks = KERNEL_OPS[kernel]
    seconds, programs = ctx.trace.kernel(
        lambda name: any(m in name for m in marks))
    if seconds <= 0 or programs <= 0:
        return None
    slots = ctx.counter("raft.serve.batch.slots")
    batches = ctx.counter("raft.serve.batch.total")
    if not batches:
        return None
    rows_per_batch = ctx.counter("raft.serve.batch.rows") / batches
    centers, sizes = ctx.probe_table
    ops, nbytes = roofline.mean_batch_work(
        ctx.traced_queries(), rows_per_batch, centers, sizes, ctx.layout)
    if ops <= 0:
        return None
    got = roofline.share(ops, nbytes, seconds / programs, ctx.peaks)
    ctx.notes[f"{kernel}_scan_roofline"] = (
        f"{got['bound']}-bound; per batch {ops!r} ops, {nbytes!r} bytes, "
        f"kernel {seconds / programs!r} s over {programs!r} programs; "
        f"{rows_per_batch!r} rows per batch of {slots / batches!r} slots")
    return got["percent"]
