#!/usr/bin/env python3
"""Find the highest open-loop rate a configuration sustains, once, by a
sweep on the chip (the driver's runs never sweep):

    python3 bench/sweep.py --config ivf_flat.2m --rates 1000,2000,4000 \
        --seconds 10 --seed 1

One process builds the configuration's system, then offers Poisson
arrivals of one-query requests at each rate in turn for ``--seconds``.
Per rate it prints one JSON line: requests sent and failed, p50 and p95
latency from the due time, the generator's lateness, and the growth of
the backlog (the median latency of the window's last third over its
first third; near 1 when the server keeps up). The rate an online cell
offers is fixed in its traffic file from this, at about four fifths of
the highest rate whose p95 stays under the limit with no growing
backlog.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (os.path.dirname(HERE), HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import loadgen  # noqa: E402
import manifest  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    from raft_tpu.core.compile_cache import enable
    enable()
    import jax
    import numpy as np
    import corpus
    man = manifest.load()
    cfg = manifest.config(man, args.config)
    if args.rehearsal:
        cfg = manifest.merged(cfg, cfg["rehearsal"])
    elif jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    c = cfg["corpus"]
    x, warm, pool = corpus.make(
        c["data_seed"], args.seed, c["rows"], c["dim"], c["n_centers"],
        c["pool"], max(cfg["serve"]["batch_sizes"]))
    system = manifest.family(cfg["family"]).build(cfg, x, warm, pool, None)
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            traffic = {"kind": "open_poisson", "rate_rps": rate,
                       "queries_per_request": 1}
            run = loadgen.drive(system.submit, len(pool), traffic,
                                args.seed, args.seconds, drain_s=30.0)
            lat = run.latencies_ms()
            third = max(len(lat) // 3, 1)
            print(json.dumps({
                "rate_rps": rate, "requests": len(run.requests),
                "failed": run.failed(),
                "p50_ms": loadgen.percentile(lat, 50),
                "p95_ms": loadgen.percentile(lat, 95),
                "late_p95_ms": loadgen.percentile(run.lateness_ms(), 95),
                "backlog_growth": float(np.median(lat[-third:])
                                        / np.median(lat[:third]))}),
                flush=True)
    finally:
        system.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
