#!/usr/bin/env python
"""Bring-up smoke of the main path on the chip: build IVF indexes over a
real-size corpus, serve k-NN requests through ``serve.SearchServer``,
and check recall against exact search.

Scale: the reference's gbench k-NN case (``cpp/bench/neighbors/
knn.cuh:380-389``, SURVEY.md §6): a 2,000,000 × 128 f32 corpus and
1,000 held-out queries from the same Gaussian mixture, generated from
``--seed`` with ``raft_tpu.random.make_blobs``. Phases, in order:

1. device check — a TPU, never a CPU fallback;
2. exact brute force at HIGHEST precision, k=32: the ground truth;
3. fused (Pallas) brute force, recall@32 >= 0.99;
4. IVF-Flat (2048 lists) served through ``SearchServer``, recall@10;
5. IVF-PQ (2048 lists, pq_dim 64, 8 bits) served likewise, k=10;
6. IVF-BQ, one search at k=10 with its exact rescore.

``--chips 4`` runs only the list-sharded multi-chip path on a 4-device
mesh: a 10,000,000 × 128 corpus (the reference's other gbench case),
``sharded_ivf_flat_build`` with 4096 lists, 512 requests through
``DistributedSearchServer``, compared with exact ``distributed_knn``
and with each shard's lists searched alone by the one-device program.

Every earlier line is a JSON object with a phase's facts (wall seconds
with compile time apart, recall, the kernel tier that served, peak
device bytes, compile-cache events). These are bring-up facts, not
benchmark numbers. Any failed phase or gate exits non-zero; the last
line, on success only, is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time

N, DIM, NQ = 2_000_000, 128, 1000
N_LISTS = 2048
N_CENTERS = 8192   # mixture components: 244 rows each at 2M, 1220 at 10M
K_EXACT, K_IVF = 32, 10
FLAT_PROBES, PQ_PROBES, BQ_PROBES = 128, 128, 128
PQ_DIM, PQ_BITS = 64, 8
BATCH_SIZES = (1, 8, 64, 256)
N_REQUESTS, N_CLIENTS, MAX_REQUEST_NQ = 512, 16, 8
N4, N_LISTS4, PROBES4 = 10_000_000, 4096, 64

CODE_SAMPLE = 10_000       # rows whose PQ codes are checked on the host

GATES = {"fused_recall": 0.99, "ivf_flat_recall": 0.90,
         "ivf_pq_recall": 0.85, "ivf_pq_estimator_recall": 0.75,
         "pq_code_match": 0.999, "ivf_bq_recall": 0.80,
         "dist_recall": 0.90, "dist_mem_balance": 0.8}


class SmokeError(Exception):
    """A phase ran but failed its gate."""


def gate(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeError(what)


def emit(**fields) -> None:
    print(json.dumps(fields, default=float), flush=True)


class CompileClock:
    """Wall seconds in which JAX was tracing, lowering or compiling,
    read from its monitoring events. Compiles nest and run on several
    threads at once, so the clock counts the union of their intervals:
    a phase's compile seconds are wall seconds, never more than its
    wall, and the rest of the wall is execution."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self._spans: list = []
        self._lock = threading.Lock()
        from jax._src import monitoring
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event in self.EVENTS:
            end = time.perf_counter()  # the event fires as its span ends
            with self._lock:
                self._spans.append((end - duration, end))

    def seconds(self, t0: float, t1: float) -> float:
        """Wall seconds of [t0, t1] covered by at least one compile."""
        with self._lock:
            spans = sorted((max(a, t0), min(b, t1))
                           for a, b in self._spans if b > t0 and a < t1)
        total, end = 0.0, t0
        for a, b in spans:
            total += max(0.0, b - max(a, end))
            end = max(end, b)
        return total


class Phase:
    """Times one phase and gathers its facts: wall and compile seconds,
    counter deltas, peak device bytes."""

    def __init__(self, name: str, clock: CompileClock):
        self.name = name
        self.clock = clock
        self.facts: dict = {}

    def __enter__(self) -> "Phase":
        from raft_tpu import obs
        self._before = obs.snapshot()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, *exc) -> None:
        if exc_type is not None:
            return
        from raft_tpu import obs
        t1 = time.perf_counter()
        wall = t1 - self._t0
        compile_s = self.clock.seconds(self._t0, t1)
        self.counters = obs.snapshot_diff(self._before,
                                          obs.snapshot())["counters"]
        cache = {k.split("event=", 1)[1].rstrip("}"): v
                 for k, v in self.counters.items()
                 if k.startswith("raft.compile_cache.event{")}
        emit(phase=self.name, wall_s=wall, compile_s=compile_s,
             execute_s=wall - compile_s, **self.facts,
             peak_bytes_in_use=peak_bytes(), compile_cache=cache)

    def count(self, name: str) -> float:
        """Sum of a counter's deltas over this phase, all label sets
        (valid once the phase has exited)."""
        return sum(v for k, v in self.counters.items()
                   if k == name or k.startswith(name + "{"))


def peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def recall(got, want, k: int) -> float:
    """Mean |top-k(got) ∩ top-k(want)| / k over rows."""
    import numpy as np
    got, want = np.asarray(got)[:, :k], np.asarray(want)[:, :k]
    hits = sum(len(set(g) & set(w)) for g, w in zip(got, want))
    return hits / (k * len(got))


def served_recall(rows, ids, exact_ids, k: int) -> float:
    """Recall of served requests: request ``j`` answered query rows
    ``rows[j]`` with ``ids[j]``."""
    import numpy as np
    return recall(np.concatenate([np.asarray(i)[:, :k] for i in ids]),
                  np.asarray(exact_ids)[np.concatenate(rows)], k)


def make_data(n: int, dim: int, nq: int, seed: int, n_centers: int):
    """Corpus and held-out queries from one Gaussian mixture (unit-scale
    centers, unit noise — the bench suite's semi-hard ANN mixture),
    generated on the device from ``seed`` (one program each, not one
    compile per eager op)."""
    import jax
    from raft_tpu.random import make_blobs
    key = jax.random.key(seed)
    centers = jax.random.normal(jax.random.fold_in(key, 0),
                                (n_centers, dim))
    blobs = jax.jit(make_blobs, static_argnums=(0, 1))
    x, _ = blobs(n, dim, centers=centers, seed=jax.random.fold_in(key, 1))
    q, _ = blobs(nq, dim, centers=centers, seed=jax.random.fold_in(key, 2))
    return jax.block_until_ready((x, q))


# -- phases ----------------------------------------------------------------

def phase_device(min_count: int = 1) -> dict:
    """The device JAX reports; anything but a TPU is an error."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu" or len(devs) < min_count:
        raise SmokeError(f"no TPU found: JAX reports {len(devs)} "
                         f"{dev.platform!r} device(s), need "
                         f"{min_count} tpu")
    from raft_tpu.core import native
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}
    emit(phase="device", **info, native_lib_loaded=native.available())
    return info


def phase_exact(x, q, clock, k: int = K_EXACT):
    """Exact brute force at HIGHEST precision — the ground truth."""
    import jax
    from raft_tpu.neighbors.brute_force import brute_force_knn
    with Phase("exact", clock) as ph:
        _, ids = jax.block_until_ready(brute_force_knn(x, q, k,
                                                       mode="exact"))
        t0 = time.perf_counter()
        jax.block_until_ready(brute_force_knn(x, q, k, mode="exact"))
        ph.facts.update(warm_call_s=time.perf_counter() - t0, k=k)
    import numpy as np
    return np.asarray(ids)


def phase_fused(x, q, exact_ids, clock, k: int = K_EXACT,
                compiled: bool = True) -> float:
    """Fused Pallas brute force. Where ``compiled``, its program must
    hold a Mosaic kernel (``tpu_custom_call``) and nothing may run in
    the Pallas interpreter; otherwise (the CPU rehearsal) the kernel
    path must have run in the interpreter."""
    import functools
    import jax
    from raft_tpu.neighbors.brute_force import brute_force_knn
    fused = functools.partial(brute_force_knn, k=k, mode="fused")
    with Phase("fused", clock) as ph:
        _, ids = jax.block_until_ready(fused(x, q))
        r = recall(ids, exact_ids, k)
        mosaic = "tpu_custom_call" in jax.jit(fused).lower(x, q).as_text()
        ph.facts.update(recall=r, k=k, mosaic_kernel=mosaic)
    interpreted = ph.count("raft.dispatch.interpret_fallback")
    if compiled:
        gate(mosaic, "fused kNN program holds no Mosaic kernel")
        gate(interpreted == 0, "fused kNN ran in the Pallas interpreter")
    else:
        gate(interpreted > 0, "fused kNN did not run the Pallas kernel")
    gate(r >= GATES["fused_recall"],
         f"fused recall@{k} {r:.4f} < {GATES['fused_recall']}")
    return r


def drive_server(server, q, seed: int, n_requests: int, n_clients: int,
                 max_nq: int):
    """``n_clients`` threads submit ``n_requests`` requests of 1..max_nq
    queries (rows cycle through ``q``) → (row index, ids) per request."""
    import numpy as np
    rng = np.random.default_rng(seed)
    sizes = rng.integers(1, max_nq + 1, n_requests)
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]]) % len(q)
    qh = np.asarray(q)
    rows = [(np.arange(s, s + n) % len(qh)) for s, n in zip(starts, sizes)]
    out = [None] * n_requests
    errors = []

    def client(c):
        try:
            for r in range(c, n_requests, n_clients):
                _, ids = server.search(qh[rows[r]], timeout=600.0)
                out[r] = ids
        except Exception as e:  # noqa: BLE001 - re-raised below
            errors.append(e)

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900.0)
    if errors:
        raise errors[0]
    gate(all(o is not None for o in out) and
         not any(t.is_alive() for t in threads),
         "served traffic did not complete")
    return rows, out


def phase_served(name: str, build, q, exact_ids, params, k: int, clock,
                 seed: int, batch_sizes=BATCH_SIZES,
                 n_requests: int = N_REQUESTS, n_clients: int = N_CLIENTS,
                 max_nq: int = MAX_REQUEST_NQ, min_recall: float = 0.0):
    """Build an index (``build()``) and serve it through
    ``SearchServer`` under client threads: recall of the served ids,
    zero plan compiles in the served window, and one batch bit-equal to
    a direct ``plan.search`` of its shape. Returns the index."""
    import jax
    import numpy as np
    from raft_tpu import obs, serve
    with Phase(name, clock) as ph:
        t0 = time.perf_counter()
        index = build()
        jax.block_until_ready([v for v in vars(index).values()
                               if isinstance(v, jax.Array)])
        build_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        server = serve.SearchServer.from_index(
            index, q[:max(batch_sizes)], k, params=params,
            config=serve.ServeConfig(batch_sizes=tuple(batch_sizes)))
        setup_s = time.perf_counter() - t0
        try:
            before = obs.snapshot()
            t0 = time.perf_counter()
            rows, ids = drive_server(server, q, seed, n_requests,
                                     n_clients, max_nq)
            window_s = time.perf_counter() - t0
            diff = obs.snapshot_diff(before, obs.snapshot())["counters"]
            compiles = sum(v for key, v in diff.items()
                           if key.split("{")[0] in (
                               "raft.plan.cache.misses",
                               "raft.plan.build.total"))
            r = served_recall(rows, ids, exact_ids, k)
            # one batch alone at a ladder shape vs the plan directly
            nb = batch_sizes[1] if len(batch_sizes) > 1 else batch_sizes[0]
            _, served_ids = server.search(np.asarray(q[:nb]),
                                          timeout=600.0)
            _, plan = server.ladder.plan_for(nb, 0)
            _, direct_ids = plan.search(q[:nb], block=True)
            bit_equal = bool(np.array_equal(np.asarray(served_ids),
                                            np.asarray(direct_ids)))
        finally:
            server.close()
        ph.facts.update(build_s=build_s, server_setup_s=setup_s,
                        served_window_s=window_s, requests=n_requests,
                        clients=n_clients, recall=r, k=k,
                        n_probes=params.n_probes,
                        compiles_in_window=compiles,
                        plan_bit_equal=bit_equal)
    fused = ph.count("raft.ivf_scan.fused.total")
    emit(phase=name + ".tier", fused_plans=fused,
         pallas_routes=ph.count("raft.dispatch.route{path=pallas}"),
         interpret_fallback=ph.count("raft.dispatch.interpret_fallback"))
    gate(compiles == 0, f"{name}: {compiles} plan compiles in the served "
         "window")
    gate(bit_equal, f"{name}: served ids differ from plan.search")
    gate(r >= min_recall, f"{name}: recall@{k} {r:.4f} < {min_recall}")
    return index


def phase_ivf_flat(x, q, exact_ids, clock, seed: int, n_lists: int = N_LISTS,
                   n_probes: int = FLAT_PROBES, **serve_kw) -> None:
    from raft_tpu.neighbors import ivf_flat
    phase_served("ivf_flat", lambda: ivf_flat.build(
                     x, ivf_flat.IndexParams(n_lists=n_lists)),
                 q, exact_ids, ivf_flat.SearchParams(n_probes=n_probes),
                 K_IVF, clock, seed, min_recall=GATES["ivf_flat_recall"],
                 **serve_kw)


def code_match(index, x, n_sample: int, seed: int) -> float:
    """Fraction of stored PQ codes, over ``n_sample`` sampled rows, that
    are the nearest codeword of their row's rotated residual, computed
    on the host in f64. The exact rescore hides a partly wrong encoder;
    this does not."""
    import numpy as np
    ids = np.asarray(index.lists_indices)
    lst, slot = np.nonzero(ids >= 0)
    pick = np.random.default_rng(seed).choice(
        len(lst), min(n_sample, len(lst)), replace=False)
    lst, slot = lst[pick], slot[pick]
    codes = np.asarray(index.codes[lst, slot]).astype(np.int64)
    rows = np.asarray(x[ids[lst, slot]], np.float64)
    rot = np.asarray(index.rotation_matrix, np.float64)
    books = np.asarray(index.pq_centers, np.float64)     # (S, C, len)
    resid = (rows - np.asarray(index.centers, np.float64)[lst]) @ rot.T
    sub = resid.reshape(len(rows), books.shape[0], books.shape[2])
    hits = 0
    for a in range(0, len(sub), 500):
        d = ((sub[a:a + 500, :, None, :] - books[None]) ** 2).sum(-1)
        hits += int((d.argmin(-1) == codes[a:a + 500]).sum())
    return hits / codes.size


def phase_ivf_pq(x, q, exact_ids, clock, seed: int, n_lists: int = N_LISTS,
                 n_probes: int = PQ_PROBES, pq_dim: int = PQ_DIM,
                 **serve_kw):
    """IVF-PQ at the repo's rescored operating point (``keep_raw`` +
    ``rescore_factor=8``, as bench_suite's PQ row); then the codes
    against the nearest codeword, and the estimator-only recall, whose
    k=10 code scan is the kernel Mosaic refused before its bins were
    lane-aligned. Returns the estimator-only ids."""
    import dataclasses
    import jax
    from raft_tpu.neighbors import ivf_pq
    sp = ivf_pq.SearchParams(n_probes=n_probes, rescore_factor=8)
    index = phase_served(
        "ivf_pq", lambda: ivf_pq.build(x, ivf_pq.IndexParams(
            n_lists=n_lists, pq_dim=pq_dim, pq_bits=PQ_BITS,
            keep_raw=True)),
        q, exact_ids, sp, K_IVF, clock, seed,
        min_recall=GATES["ivf_pq_recall"], **serve_kw)
    with Phase("ivf_pq.estimator", clock) as ph:
        match = code_match(index, x, CODE_SAMPLE, seed)
        _, ids = jax.block_until_ready(ivf_pq.search(
            index, q, K_IVF, dataclasses.replace(sp, rescore_factor=0)))
        r = recall(ids, exact_ids, K_IVF)
        ph.facts.update(nearest_code_frac=match, recall=r, k=K_IVF)
    gate(match >= GATES["pq_code_match"],
         f"ivf_pq: {match:.6f} of codes are the nearest codeword")
    gate(r >= GATES["ivf_pq_estimator_recall"],
         f"ivf_pq estimator-only recall@{K_IVF} {r:.4f} < "
         f"{GATES['ivf_pq_estimator_recall']}")
    return ids


def phase_ivf_bq(x, q, exact_ids, clock, n_lists: int = N_LISTS,
                 n_probes: int = BQ_PROBES):
    """One ``ivf_bq.search`` at k=10 with its default exact rescore.
    Returns its ids."""
    import jax
    from raft_tpu.neighbors import ivf_bq
    with Phase("ivf_bq", clock) as ph:
        t0 = time.perf_counter()
        index = ivf_bq.build(x, ivf_bq.IndexParams(n_lists=n_lists))
        jax.block_until_ready(index.bits)
        build_s = time.perf_counter() - t0
        sp = ivf_bq.SearchParams(n_probes=n_probes)
        t0 = time.perf_counter()
        _, ids = jax.block_until_ready(ivf_bq.search(index, q, K_IVF, sp))
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        jax.block_until_ready(ivf_bq.search(index, q, K_IVF, sp))
        r = recall(ids, exact_ids, K_IVF)
        ph.facts.update(build_s=build_s, first_search_s=first_s,
                        warm_search_s=time.perf_counter() - t0, recall=r,
                        k=K_IVF, n_probes=n_probes)
    gate(r >= GATES["ivf_bq_recall"],
         f"ivf_bq: recall@{K_IVF} {r:.4f} < {GATES['ivf_bq_recall']}")
    return ids


def run_one_chip(seed: int, clock: CompileClock) -> None:
    import gc
    import numpy as np
    with Phase("data", clock) as ph:
        x, q = make_data(N, DIM, NQ, seed, N_CENTERS)
        ph.facts.update(n=N, dim=DIM, nq=NQ, corpus_bytes=x.nbytes)
    exact_ids = phase_exact(x, q, clock)
    phase_fused(x, q, exact_ids, clock)
    phase_ivf_flat(x, q, exact_ids, clock, seed)
    gc.collect()  # release the index before the next build
    pq_ids = phase_ivf_pq(x, q, exact_ids, clock, seed)
    gc.collect()
    bq_ids = phase_ivf_bq(x, q, exact_ids, clock)
    # the two recalls need not differ; the answers must
    emit(phase="bq_vs_pq_estimator",
         ids_equal_frac=float((np.asarray(bq_ids)
                               == np.asarray(pq_ids)).mean()))


# -- four chips ------------------------------------------------------------

def sharded_corpus(mesh, n: int, dim: int, nq: int, seed: int,
                   n_centers: int):
    """Each device generates its own rows in one program (a 10M corpus
    would not fit one device's generation transients) → one row-sharded
    array."""
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from raft_tpu.random import make_blobs
    per = n // mesh.size
    key = jax.random.key(seed)
    centers = jax.random.normal(jax.random.fold_in(key, 0),
                                (n_centers, dim))

    def shard_rows(c):
        shard_key = jax.random.fold_in(key, 10 + lax.axis_index("data"))
        return make_blobs(per, dim, centers=c, seed=shard_key)[0]

    x = jax.jit(jax.shard_map(shard_rows, mesh=mesh, in_specs=P(),
                              out_specs=P("data", None)))(centers)
    q, _ = jax.jit(make_blobs, static_argnums=(0, 1))(
        nq, dim, centers=centers, seed=jax.random.fold_in(key, 2))
    return jax.block_until_ready((x, q))


def one_device_reference(index, q, k: int, n_probes: int):
    """The list-sharded search recomputed without the mesh: each shard's
    lists searched alone by the one-device program, on the device that
    holds them (at 10M rows the padded index is 4 × 8 GB, so no one
    device holds it whole), in four query chunks (each probe step
    gathers (queries, max_list, dim): 2 GB at 250 queries), then the
    per-shard top-k merged in f32 on the host — the ids the f32 mesh
    merge must reproduce."""
    import dataclasses
    import jax
    import numpy as np
    from raft_tpu.parallel import make_mesh
    from raft_tpu.parallel.ivf import distributed_ivf_flat_search
    from raft_tpu.neighbors.ivf_flat import SearchParams

    def parts(a):
        return {sh.device: (sh.index[0].start or 0, sh.data)
                for sh in a.addressable_shards}

    fields = ("centers", "lists_data", "lists_indices", "lists_norms",
              "list_sizes")
    by_field = {f: parts(getattr(index, f)) for f in fields}
    ds, is_ = [], []
    for dev in sorted(by_field["centers"],
                      key=lambda d: by_field["centers"][d][0]):
        view = dataclasses.replace(
            index, **{f: by_field[f][dev][1] for f in fields})
        mesh1, chunk = make_mesh(devices=[dev]), -(-len(q) // 4)
        got = [distributed_ivf_flat_search(
                   view, jax.device_put(q[a:a + chunk], dev), k,
                   SearchParams(n_probes=n_probes), mesh=mesh1,
                   merge="f32") for a in range(0, len(q), chunk)]
        ds.append(np.concatenate([np.asarray(d) for d, _ in got]))
        is_.append(np.concatenate([np.asarray(i) for _, i in got]))
    d, i = np.concatenate(ds, 1), np.concatenate(is_, 1)
    order = np.argsort(d, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(i, order, axis=1)


def run_four_chips(seed: int, clock: CompileClock) -> None:
    import jax
    import numpy as np
    from raft_tpu import serve
    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.parallel import (distributed_knn, make_mesh,
                                   sharded_ivf_flat_build)
    devs = jax.devices()[:4]
    mesh = make_mesh(devices=devs)
    with Phase("data4", clock) as ph:
        x, q = sharded_corpus(mesh, N4, DIM, NQ, seed, N_CENTERS)
        ph.facts.update(n=N4, dim=DIM, nq=NQ, corpus_bytes=x.nbytes)
    with Phase("dist_exact", clock) as ph:
        _, exact_ids = jax.block_until_ready(distributed_knn(
            x, q, K_IVF, mesh))
        exact_ids = np.asarray(exact_ids)
        ph.facts.update(k=K_IVF)
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devs]
    with Phase("sharded_build", clock) as ph:
        index = sharded_ivf_flat_build(
            x, ivf_flat.IndexParams(n_lists=N_LISTS4), mesh=mesh)
        jax.block_until_ready(index.lists_data)
        sizes = np.asarray(index.list_sizes)
        # what the build left on each device (None: the CPU rehearsal
        # reports no memory stats)
        grown = [None if b is None else
                 (d.memory_stats() or {})["bytes_in_use"] - b
                 for d, b in zip(devs, in_use)]
        ph.facts.update(n_lists=N_LISTS4, max_list=index.lists_data.shape[1],
                        max_over_mean_list=float(sizes.max() / sizes.mean()),
                        bytes_grown=grown, shard_devices=sorted(
                            {str(s.device) for s in
                             index.lists_data.addressable_shards}))
    del x  # the corpus is not needed past the build
    gate(len(ph.facts["shard_devices"]) == 4,
         f"lists not spread over 4 devices: {ph.facts['shard_devices']}")
    if None not in grown:
        gate(min(grown) >= GATES["dist_mem_balance"] * max(grown),
             f"the build's device memory is uneven across the mesh: "
             f"{grown}")
    sp = ivf_flat.SearchParams(n_probes=PROBES4)
    with Phase("dist_serve", clock) as ph:
        t0 = time.perf_counter()
        server = serve.DistributedSearchServer.from_sharded_index(
            index, q[:max(BATCH_SIZES)], K_IVF, params=sp, mesh=mesh,
            merge="f32",
            config=serve.ServeConfig(batch_sizes=BATCH_SIZES))
        setup_s = time.perf_counter() - t0
        try:
            t0 = time.perf_counter()
            rows, ids = drive_server(server, q, seed, N_REQUESTS,
                                     N_CLIENTS, MAX_REQUEST_NQ)
            window_s = time.perf_counter() - t0
        finally:
            server.close()
        r = served_recall(rows, ids, exact_ids, K_IVF)
        ref = one_device_reference(index, q, K_IVF, PROBES4)
        same = float(np.mean([np.array_equal(np.asarray(i)[:, :K_IVF],
                                              ref[rr])
                              for rr, i in zip(rows, ids)]))
        ph.facts.update(server_setup_s=setup_s, served_window_s=window_s,
                        recall_vs_distributed_knn=r,
                        ids_equal_one_device_frac=same, n_probes=PROBES4,
                        merge="f32")
    gate(r >= GATES["dist_recall"],
         f"distributed recall@{K_IVF} {r:.4f} < {GATES['dist_recall']}")
    gate(same == 1.0, f"mesh ids differ from the one-device search "
         f"({same:.6f} equal)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    # the compile cache first, then JAX
    from raft_tpu.core.compile_cache import enable
    enable()
    clock = CompileClock()
    try:
        info = phase_device(min_count=args.chips)
        if args.chips == 4:
            run_four_chips(args.seed, clock)
        else:
            run_one_chip(args.seed, clock)
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
