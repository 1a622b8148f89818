#!/usr/bin/env python3
"""One run of one benchmark cell:

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

In order: the corpus (fixed by the configuration's ``data_seed``, see
``corpus.py``) and the query pool (from ``--seed``), on the device;
the index; the server with the configuration's fixed ``ServeConfig``,
its ladder pre-warmed (from the persistent compile cache after a
checkout's first run); the cell's traffic for ``--seconds``; then, with
the server closed and its memory freed, the comparison with the
reference (``reference.py``). ``setup_s`` is the first three.

``--trace 0`` reports the cell's end-to-end metrics. ``--trace 1``
reports its per-layer metrics: a profiler trace of part of the window,
the program's spans and counters, each read by its reader in
``bench/metrics``. The last line of standard output is the result; the
numbers compared, each beside its limit, are the last lines of standard
error and the result's last key.

With no TPU, or fewer chips than the cell asks for, the run exits 2 and
prints no result. ``--rehearsal`` runs a tiny copy of the cell (the
configuration's ``rehearsal`` sizes) on whatever JAX finds; its result
is stamped as a rehearsal and holds no metric. ``--control`` runs the
configuration's ``control`` in the program's place: it has to come out
not correct. The driver's runs use neither.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import glob
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from typing import Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import loadgen  # noqa: E402
import manifest  # noqa: E402

TRACE_START = 0.25      # the traced part starts a quarter into the window
TRACE_MAX_S = 10.0      # and lasts at most this, or half the window
DRAIN_S = 60.0
RECALL_AT = 10          # recall_at_10: the first 10 of the k served ids
COMPILE_COUNTERS = ("raft.plan.cache.misses", "raft.plan.build.total")


class NoDevice(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny copy of the cell on any backend; no metric")
    ap.add_argument("--control", action="store_true",
                    help="run the configuration's control in the "
                    "program's place")
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    return args


class CompileClock:
    """Wall seconds in which JAX traced, lowered or compiled (the union
    of the intervals of its monitoring events; copied from
    ``chip_smoke.py``)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        from jax._src import monitoring
        self._spans: list = []
        self._lock = threading.Lock()
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event in self.EVENTS:
            end = time.perf_counter()
            with self._lock:
                self._spans.append((end - duration, end))

    def seconds(self, t0: float, t1: float) -> float:
        with self._lock:
            spans = sorted((max(a, t0), min(b, t1))
                           for a, b in self._spans if b > t0 and a < t1)
        total, end = 0.0, t0
        for a, b in spans:
            total += max(0.0, b - max(a, end))
            end = max(end, b)
        return total


class Tracer(threading.Thread):
    """Profiles ``[start, start + length]`` seconds from now under the
    host span ``bench.traced_window``, then reads the trace."""

    def __init__(self, seconds: float):
        super().__init__(daemon=True, name="bench-tracer")
        self.delay = TRACE_START * seconds
        self.length = min(TRACE_MAX_S, 0.5 * seconds)
        self.host_window = (0.0, 0.0)
        self.trace = None
        self.error: Optional[BaseException] = None

    def run(self) -> None:
        import jax
        import trace_reduce
        out = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            time.sleep(self.delay)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(out, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                    t0 = time.perf_counter()
                    time.sleep(self.length)
                    self.host_window = (t0, time.perf_counter())
            finally:
                jax.profiler.stop_trace()
            path, = glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                              recursive=True)
            self.trace = trace_reduce.load(path)
        except BaseException as e:  # noqa: BLE001 - re-raised by main
            self.error = e
        finally:
            shutil.rmtree(out, ignore_errors=True)


@dataclass
class Context:
    """What a per-layer reader (``bench/metrics/<name>.py``) reads."""

    cell: dict
    cfg: dict
    run: loadgen.Run
    pool: object
    counters: dict
    spans: list
    trace: object
    peaks: dict
    layout: dict
    probe_table: tuple
    host_window: tuple
    notes: dict = field(default_factory=dict)

    def counter(self, name: str) -> float:
        """A counter's change over the window, summed over label sets."""
        return sum(v for k, v in self.counters.items()
                   if k == name or k.startswith(name + "{"))

    def traced_queries(self) -> list:
        """The queries of each request sent inside the traced window."""
        a, b = self.host_window
        return [self.pool[r.rows] for r in self.run.requests
                if a <= r.t_send <= b]


def devices_for(chips: int, rehearsal: bool):
    import jax
    devs = jax.devices()
    if not rehearsal and devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX reports {len(devs)} "
                       f"{devs[0].platform!r} device(s)")
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX reports "
                       f"{len(devs)}")
    return devs[:chips]


def peak_bytes(devs) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs]
    return None if None in peaks else int(max(peaks))


def say(*parts) -> None:
    print("bench:", *parts, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    args = parse(argv)
    man = manifest.load()
    cell = manifest.workload(man, args.workload)
    cfg = manifest.config(man, cell["config"])
    traffic = manifest.traffic(cell["traffic"])
    if args.rehearsal:
        cfg = manifest.merged(cfg, cfg["rehearsal"])
    control = None
    if args.control:
        control = cfg["control"]
        os.environ.update(control.get("env", {}))
        cfg = manifest.merged(cfg, control.get("config", {}))
    if args.trace:
        # keep every request's spans of the window for the readers
        os.environ["RAFT_TPU_TRACE_RING"] = "1000000"
    from raft_tpu.core.compile_cache import enable
    enable()
    import jax
    import numpy as np
    from jax.sharding import Mesh
    try:
        devs = devices_for(cell["chips"], args.rehearsal)
    except NoDevice as e:
        say(f"refused: {e}")
        return 2
    import corpus
    import reference
    from raft_tpu import obs
    from raft_tpu.obs import recorder
    clock = CompileClock()
    mesh = Mesh(np.array(devs), ("data",))
    on_mesh = mesh if cell["chips"] > 1 else None
    c = cfg["corpus"]
    k = cfg["search"]["k"]

    t0 = time.perf_counter()
    x, warm, pool = corpus.make(
        c["data_seed"], args.seed, c["rows"], c["dim"], c["n_centers"],
        c["pool"], max(cfg["serve"]["batch_sizes"]), mesh=on_mesh)
    if control and control["kind"] == "reference_bf16":
        from system import System
        sizes = sorted({int(traffic["queries_per_request"])})
        system = System(server=reference.ReferenceServer(x, mesh, k, sizes),
                        pool=pool, index=None, layout={})
    else:
        system = manifest.family(cfg["family"]).build(cfg, x, warm, pool,
                                                      on_mesh)
    setup_s = time.perf_counter() - t0
    setup_compile_s = clock.seconds(t0, t0 + setup_s)

    probe_table = (system.probe_table()
                   if args.trace and system.index is not None else None)
    before = obs.snapshot()
    n_recorded = recorder.RECORDER.recorded_total
    tracer = Tracer(args.seconds) if args.trace else None
    annotate = jax.profiler.TraceAnnotation if args.trace else None
    if tracer:
        tracer.start()
    t_win = time.perf_counter()
    run = loadgen.drive(system.submit, len(pool), traffic, args.seed,
                        args.seconds, drain_s=DRAIN_S, annotate=annotate)
    if tracer:
        tracer.join()
        if tracer.error is not None:
            raise tracer.error
    counters = obs.snapshot_diff(before, obs.snapshot())["counters"]
    spans = recorder.RECORDER.requests(
        recorder.RECORDER.recorded_total - n_recorded)
    window_compile_s = clock.seconds(t_win, time.perf_counter())
    memory_peak = peak_bytes(devs)
    system.close()
    layout = system.layout
    del system
    gc.collect()

    with (annotate("bench.oracle") if annotate
          else contextlib.nullcontext()):
        t_ref = time.perf_counter()
        numbers = reference.compare(reference.Exact(x, mesh, RECALL_AT),
                                    pool, run, k)
        ref_s = time.perf_counter() - t_ref
    limits = cfg["limits"]
    correct = all(numbers[n] <= limits[n] for n in limits)

    lat = run.latencies_ms()
    late = run.lateness_ms()
    say(f"{'rehearsal ' if args.rehearsal else ''}"
        f"{'control=' + control['kind'] + ' ' if control else ''}"
        f"workload={args.workload} seed={args.seed} "
        f"device={devs[0].platform}/{devs[0].device_kind} x{len(devs)}")
    say(f"setup_s={setup_s!r} of which compiling={setup_compile_s!r} "
        "(a checkout's first run compiles; later runs load the cache)")
    say(f"requests={len(run.requests)} failed={run.failed()} "
        f"queries_in_window={run.queries_in_window()}")
    say(f"generator lateness ms p50={loadgen.percentile(late, 50)!r} "
        f"p95={loadgen.percentile(late, 95)!r}")
    say(f"compiles in window: plan misses+builds="
        f"{sum(counters.get(n, 0) for n in COMPILE_COUNTERS)!r} "
        f"compiling_s={window_compile_s!r}")
    say(f"memory_peak_bytes={memory_peak!r} reference_s={ref_s!r}")

    values = {
        "setup_s": setup_s,
        "qps": run.queries_in_window() / args.seconds,
        "p50_ms": loadgen.percentile(lat, 50),
        "p95_ms": loadgen.percentile(lat, 95),
        "recall_at_10": 1.0 - numbers["recall_miss"],
    }
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": len(run.requests),
              "failed": run.failed(), "metrics": {}, "device": device}
    if args.trace:
        trace = tracer.trace
        device.update(busy_s=trace.busy_s(), window_s=trace.window_s)
        first = sorted(trace.devices)[0] if trace.devices else None
        result["breakdown"] = {
            "device_ops": [list(kv) for kv in trace.top_ops(10)],
            "idle_gaps": [list(kv) for kv in
                          (trace.idle_gaps(first, 10) if first else [])]}
    if args.rehearsal:
        result["rehearsal"] = True
    elif args.trace:
        import roofline
        ctx = Context(cell=cell, cfg=cfg, run=run, pool=pool,
                      counters=counters, spans=spans, trace=tracer.trace,
                      peaks=roofline.peaks_for(devs[0].device_kind),
                      layout=layout, probe_table=probe_table,
                      host_window=tracer.host_window)
        for m in manifest.metrics_for(man, "per_layer", cell["name"]):
            v = manifest.reader(m["name"]).read(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
        for name, note in ctx.notes.items():
            say(f"{name}: {note}")
    else:
        for m in manifest.metrics_for(man, "end_to_end", cell["name"]):
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    result["compared"] = {n: {"value": numbers[n], "limit": limits[n]}
                          for n in limits}
    for n in limits:
        print(f"compared {n}={numbers[n]!r} limit={limits[n]!r} "
              f"{'ok' if numbers[n] <= limits[n] else 'FAILED'}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
