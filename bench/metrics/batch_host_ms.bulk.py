"""The dispatcher's host time between two device waits (ms): the median,
over consecutive pairs of ``raft.plan.device_wait`` spans in the traced
window, of the time from one's end to the next's start. That stretch
holds the fetch of one batch's results, their scatter to the callers,
the collection and assembly of the next batch and its enqueue: the
host work a closed loop puts between two batches on the device."""

import numpy as np

SPAN = "raft.plan.device_wait"


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = ctx.trace.window
    waits = sorted((s, s + d) for n, s, d in ctx.trace.host
                   if n == SPAN and s >= lo and s + d <= hi)
    gaps = [b[0] - a[1] for a, b in zip(waits, waits[1:])]
    return float(np.median(gaps)) / 1e6 if gaps else None
