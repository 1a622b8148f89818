"""Share of the traced window in which the device ran no op while the
host was busy with serving work: the union of the dispatcher's host
phases (``raft.serve.assemble``, ``raft.plan.enqueue``,
``raft.plan.host_epilogue``, ``raft.serve.fetch``,
``raft.serve.scatter``) and of garbage-collection pauses
(``raft.runtime.gc``), less the device's busy intervals, over the
window; averaged over the chips the cell uses. Idle time spent waiting
for arrivals (``raft.serve.collect``) is left out."""

HOST_PHASES = ("raft.serve.assemble", "raft.plan.enqueue",
               "raft.plan.host_epilogue", "raft.serve.fetch",
               "raft.serve.scatter", "raft.runtime.gc")


def union(intervals):
    """Sorted, merged (start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def overlap(xs, ys):
    """Total length of the intersection of two merged interval lists."""
    total, i, j = 0, 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        total += max(0, b - a)
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(ctx):
    t = ctx.trace
    if t is None or not t.devices or t.window_s <= 0:
        return None
    lo, hi = t.window
    host = union((max(s, lo), min(s + d, hi)) for n, s, d in t.host
                 if n in HOST_PHASES and min(s + d, hi) > max(s, lo))
    if not host:
        return None
    covered = sum(b - a for a, b in host)
    idle = [covered - overlap(host, t.busy_intervals(dev))
            for dev in sorted(t.devices)]
    return 100.0 * sum(idle) / len(idle) / (hi - lo)
