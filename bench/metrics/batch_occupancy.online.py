"""Rows the batcher filled per ladder slot it dispatched, over the
window: the change of ``raft.serve.batch.rows`` over the change of
``raft.serve.batch.slots`` (the rest is padding)."""


def read(ctx):
    slots = ctx.counter("raft.serve.batch.slots")
    return 100.0 * ctx.counter("raft.serve.batch.rows") / slots \
        if slots else None
