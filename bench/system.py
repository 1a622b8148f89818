"""What a family module (``bench/families/<family>.py``) hands the
harness: the server under test, and what the metric readers may read
of its index."""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


def serve_config(cfg: dict):
    """The configuration's fixed serving contract as a ``ServeConfig``."""
    from raft_tpu import serve
    s = cfg["serve"]
    return serve.ServeConfig(
        batch_sizes=tuple(s["batch_sizes"]), max_queue=s["max_queue"],
        max_wait_ms=s["max_wait_ms"],
        default_deadline_ms=s["default_deadline_ms"],
        probes_ladder=tuple(s["probes_ladder"]))


@dataclass
class System:
    """``server.submit(queries) -> Future`` of ``(dists, ids)``; ``layout``
    describes the index for the roofline arithmetic (``kind``, ``dim``,
    ``n_probes``, ``bytes_per_row`` and, for PQ, ``pq_dim``)."""

    server: object
    pool: np.ndarray
    index: object
    layout: dict

    def submit(self, rows):
        return self.server.submit(self.pool[rows])

    def probe_table(self):
        """Host copies of the coarse centres and the list sizes: what a
        host replay of the coarse step needs."""
        return (np.asarray(self.index.centers, np.float32),
                np.asarray(self.index.list_sizes, np.int64))

    def close(self) -> None:
        self.server.close()
        self.server = self.index = None
