"""The work a batch of searches needs, and a kernel's share of its
roofline.

The counts are of the work the algorithm needs, whatever implements
it, so a later rewrite of a kernel can be credited:

* bytes: the real (unpadded) rows of the distinct lists the batch
  probes, each list read once per batch (``dim * 4`` bytes a row for
  flat, ``pq_dim * pq_bits / 8`` for PQ), plus the batch's f32 queries;
* operations: ``2 * dim`` per probed row and query for flat, and
  ``pq_dim`` look-up-table adds per probed row and query for PQ.

Which lists a batch probes comes from a host replay of the coarse step
(the ``n_probes`` nearest centres of each query) over the requests the
traced window sent, grouped into batches in the order they were sent at
the window's measured mean rows per batch. The share is the least time
the chip could take, ``max(ops / peak FLOP/s, bytes / peak B/s)``, over
the kernel's measured device time per batch.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Tuple

import numpy as np

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def peaks_for(device_kind: str, path: str = PEAKS) -> dict:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}: add its published numbers")
    return table[device_kind]


def probed_lists(queries: np.ndarray, centers: np.ndarray,
                 n_probes: int) -> np.ndarray:
    """(m, n_probes) indices of each query's nearest centres (squared
    L2, float64): the coarse step, replayed on the host."""
    q = np.asarray(queries, np.float64)
    c = np.asarray(centers, np.float64)
    d = (q * q).sum(1)[:, None] + (c * c).sum(1)[None, :] - 2.0 * q @ c.T
    p = min(n_probes, c.shape[0])
    return np.argpartition(d, p - 1, axis=1)[:, :p]


def batch_work(probes: np.ndarray, list_sizes: np.ndarray,
               layout: dict) -> Tuple[float, float]:
    """(operations, bytes) one batch needs; ``probes`` holds the probed
    lists of each of its queries."""
    m = probes.shape[0]
    dim = layout["dim"]
    rows_scored = float(list_sizes[probes].sum())
    rows_read = float(list_sizes[np.unique(probes)].sum())
    per_row_op = 2 * dim if layout["kind"].startswith("flat") \
        else layout["pq_dim"]
    return (per_row_op * rows_scored,
            layout["bytes_per_row"] * rows_read + 4.0 * m * dim)


def batches(queries: List[np.ndarray], rows_per_batch: float
            ) -> List[np.ndarray]:
    """Requests' queries, in the order sent, grouped into consecutive
    batches of about ``rows_per_batch`` rows (whole requests each)."""
    out, cur, n = [], [], 0
    for q in queries:
        cur.append(q)
        n += len(q)
        if n >= rows_per_batch:
            out.append(np.concatenate(cur))
            cur, n = [], 0
    return out


def mean_batch_work(queries: List[np.ndarray], rows_per_batch: float,
                    centers: np.ndarray, list_sizes: np.ndarray,
                    layout: dict, max_batches: int = 64
                    ) -> Tuple[float, float]:
    """Mean (operations, bytes) per batch over up to ``max_batches``
    batches replayed from ``queries``."""
    work = [batch_work(probed_lists(b, centers, layout["n_probes"]),
                       list_sizes, layout)
            for b in batches(queries, rows_per_batch)[:max_batches]]
    if not work:
        return 0.0, 0.0
    ops, byt = zip(*work)
    return float(np.mean(ops)), float(np.mean(byt))


def share(ops: float, nbytes: float, seconds: float, peaks: dict,
          flops_key: str = "bf16_flops") -> Dict[str, object]:
    """Percent of the roofline that ``seconds`` reaches for the work, and
    which bound (compute or memory) sets the roofline."""
    t_ops = ops / peaks[flops_key]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return {"percent": 100.0 * max(t_ops, t_mem) / seconds,
            "bound": "compute" if t_ops >= t_mem else "memory"}
