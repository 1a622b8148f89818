"""The reduction from a profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
``Trace``: per device, its op events (line ``XLA Ops``) and its program
events (line ``XLA Modules``); from the host, the named spans of the
benchmark (``bench.*``) and of the program (``raft.*``); and the traced
window, the host span ``bench.traced_window``. A ``Trace`` round-trips
through JSON, so a small recorded one sits beside the tests.

All times are nanoseconds on the trace's one clock, clipped to the
window. ``python3 bench/trace_reduce.py <file.xplane.pb>`` prints what
a trace holds: planes, lines, event counts and the commonest names.
"""

from __future__ import annotations

import collections
import json
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.traced_window"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
HOST_PREFIXES = ("bench.", "raft.")

Event = Tuple[str, int, int]     # (name, start_ns, duration_ns)


@dataclass
class Device:
    ops: List[Event] = field(default_factory=list)
    modules: List[Event] = field(default_factory=list)


@dataclass
class Trace:
    window: Tuple[int, int]
    devices: Dict[str, Device]
    host: List[Event] = field(default_factory=list)

    # -- the window --------------------------------------------------------
    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _clip(self, events: List[Event]) -> List[Tuple[int, int, str]]:
        """(start, end, name) of the events, clipped to the window."""
        lo, hi = self.window
        out = []
        for name, s, d in events:
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                out.append((a, b, name))
        return sorted(out)

    # -- busy and idle -----------------------------------------------------
    def busy_intervals(self, device: str) -> List[Tuple[int, int]]:
        """The union of the device's op intervals, in order."""
        merged: List[List[int]] = []
        for a, b, _ in self._clip(self.devices[device].ops):
            if merged and a <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], b)
            else:
                merged.append([a, b])
        return [(a, b) for a, b in merged]

    def busy_s(self, device: Optional[str] = None) -> float:
        """Seconds in which an op ran: on ``device``, or averaged over
        the devices."""
        names = [device] if device else sorted(self.devices)
        if not names:
            return 0.0
        return sum(sum(b - a for a, b in self.busy_intervals(d))
                   for d in names) / len(names) / 1e9

    def idle_share(self) -> Optional[float]:
        """1 - busy / window, averaged over the devices."""
        if not self.devices or self.window_s <= 0:
            return None
        return 1.0 - self.busy_s() / self.window_s

    def idle_gaps(self, device: str, top: int = 10) -> List[Tuple[str, float]]:
        """The longest gaps in which the device ran nothing, each named
        by the host span that overlaps it most (``idle`` where none
        does), longest first."""
        lo, hi = self.window
        gaps, t = [], lo
        for a, b in self.busy_intervals(device):
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if hi > t:
            gaps.append((t, hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        host = self._clip(self.host)
        out = []
        for a, b in gaps[:top]:
            best, name = 0, "idle"
            for s, e, n in host:
                if s >= b:
                    break
                ov = min(e, b) - max(s, a)
                if ov > best:
                    best, name = ov, n
            out.append((name, (b - a) / 1e9))
        return out

    # -- ops ---------------------------------------------------------------
    def op_seconds(self) -> Dict[str, float]:
        """Seconds per op name, summed over the devices and divided by
        their number."""
        tot: Dict[str, float] = collections.defaultdict(float)
        for dev in self.devices.values():
            for a, b, name in self._clip(dev.ops):
                tot[name] += (b - a) / 1e9
        n = max(len(self.devices), 1)
        return {k: v / n for k, v in tot.items()}

    def top_ops(self, top: int = 10) -> List[Tuple[str, float]]:
        return sorted(self.op_seconds().items(), key=lambda kv: -kv[1])[:top]

    def kernel(self, match) -> Tuple[float, int]:
        """(seconds, programs) of the ops for which ``match(name)`` holds:
        their summed duration, and the number of program executions
        that ran at least one of them, both averaged over the devices."""
        secs, progs = 0.0, 0
        for dev in self.devices.values():
            hits = [(a, b) for a, b, n in self._clip(dev.ops) if match(n)]
            secs += sum(b - a for a, b in hits) / 1e9
            mods = self._clip(dev.modules)
            progs += sum(1 for s, e, _ in mods
                         if any(s <= a < e for a, _ in hits))
        n = max(len(self.devices), 1)
        return secs / n, progs / n

    # -- JSON --------------------------------------------------------------
    def to_json(self) -> dict:
        return {"window": list(self.window),
                "devices": {k: {"ops": d.ops, "modules": d.modules}
                            for k, d in self.devices.items()},
                "host": self.host}

    @classmethod
    def from_json(cls, doc: dict) -> "Trace":
        ev = lambda xs: [(str(n), int(s), int(d)) for n, s, d in xs]  # noqa: E731
        return cls(window=tuple(doc["window"]),
                   devices={k: Device(ev(v["ops"]), ev(v["modules"]))
                            for k, v in doc["devices"].items()},
                   host=ev(doc["host"]))


def op_name(event_name: str) -> str:
    """An op event is named by its whole HLO instruction on the TPU;
    keep the instruction's name (``%_fused_list_scan_call.1``)."""
    return event_name.split(" = ", 1)[0]


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and not name.startswith("/device:CPU")


def load(path: str) -> Trace:
    """Read ``path`` (an ``.xplane.pb``) into a ``Trace``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, Device] = {}
    host: List[Event] = []
    window = None
    for plane in pd.planes:
        if is_device_plane(plane.name):
            dev = devices.setdefault(plane.name, Device())
            for line in plane.lines:
                if line.name == OP_LINE:
                    dev.ops.extend((op_name(e.name), int(e.start_ns),
                                    int(e.duration_ns)) for e in line.events)
                elif line.name == MODULE_LINE:
                    dev.modules.extend((e.name, int(e.start_ns),
                                        int(e.duration_ns))
                                       for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (int(e.start_ns),
                                  int(e.start_ns + e.duration_ns))
                    elif e.name.startswith(HOST_PREFIXES):
                        host.append((e.name, int(e.start_ns),
                                     int(e.duration_ns)))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} span in the trace")
    devices = {k: v for k, v in devices.items() if v.ops}
    return Trace(window=window, devices=devices, host=host)


def describe(path: str) -> dict:
    """What a trace holds, for a person writing a reader against it."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            sample = []
            for e in evs[:3]:
                sample.append({"name": e.name, "start_ns": e.start_ns,
                               "duration_ns": e.duration_ns,
                               "stats": {str(k): str(v)[:300]
                                         for k, v in e.stats}})
            lines.append({"line": line.name, "events": len(evs),
                          "top_names": names.most_common(25),
                          "sample": sample})
        out.append({"plane": plane.name, "lines": lines})
    return {"planes": out}


if __name__ == "__main__":
    print(json.dumps(describe(sys.argv[1]), indent=1, default=str))
