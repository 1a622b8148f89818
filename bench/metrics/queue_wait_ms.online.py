"""Median of the batcher's ``raft.serve.queue_wait`` spans (ms): the
time each request of the window waited in ``SearchServer``'s queue
before its batch started, one span per request under its
``raft.serve.request`` trace."""

import numpy as np


def read(ctx):
    waits = [s["duration_ms"] for t in ctx.spans
             if t.get("name") == "raft.serve.request"
             for s in t.get("spans", ())
             if s.get("name") == "raft.serve.queue_wait"]
    return float(np.median(waits)) if waits else None
