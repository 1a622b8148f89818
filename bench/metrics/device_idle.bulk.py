"""Share of the traced window in which the device ran no op: 1 - (the
union of the device's op intervals / the window), averaged over the
chips the cell uses (``trace_reduce.Trace.idle_share``)."""


def read(ctx):
    share = None if ctx.trace is None else ctx.trace.idle_share()
    return None if share is None else 100.0 * share
