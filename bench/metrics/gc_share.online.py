"""Share of the run's window the process spent in garbage collection:
100 times the change of ``raft.runtime.gc.seconds`` (all generations)
over the window and its drain, over the window's ``--seconds``. Every
Python thread, the serving dispatcher included, stops for a
collection."""

COUNTER = "raft.runtime.gc.seconds"


def read(ctx):
    if not any(k == COUNTER or k.startswith(COUNTER + "{")
               for k in ctx.counters):
        return None
    return 100.0 * ctx.counter(COUNTER) / ctx.run.seconds
