"""The mutable-serving program: main IVF search + delta merge +
tombstone filter compiled as ONE executable.

The :mod:`neighbors.plan` family builders produce the pure jittable
serving function ``fn(q, *operands) -> (d, i)`` for the wrapped index;
this module appends two stages and AOT-compiles the whole thing:

* **tombstone filter** — result ids from the main index are looked up
  in a packed uint32 bitmap (one gather + shift per candidate); dead
  ids drop to the metric's worst value before the merge, so a deleted
  row can never outrank a live one. The bitmap only needs to cover the
  main index's id space ``[0, id_base)`` — delta rows that die are
  invalidated in place (their slot id flips to -1), so the filter
  stays one fixed-shape operand per epoch.
* **delta merge** — the delta segment (a fixed-capacity append-only
  flat buffer) is scored EXACTLY against every query (one MXU matmul
  over ``(cap, dim)``), top-k selected, and merged with the filtered
  main results inside the same program. Capacities come from the
  pre-warmed rung ladder, so delta growth swaps operand shapes between
  compiled programs instead of recompiling (the ``serve/ladder.py``
  discipline applied to mutable state).

All stages honor the family's OUTPUT convention (`ivf_flat._postprocess`):
L2 metrics merge ascending, InnerProduct descending, cosine as 1 - cos
over normalized rows — the merge key flips sign accordingly and
invalid/dead slots sit at the convention's worst value.
"""

from __future__ import annotations

import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from raft_tpu import obs
from raft_tpu.obs import profiler
from raft_tpu.core.error import expects
from raft_tpu.core.precision import matmul_precision
from raft_tpu.distance.distance_types import DistanceType

__all__ = ["compile_mutate_program", "compile_tail_program",
           "delta_scores", "mutate_tail"]

# Compile-surface rung declarations (graftlint GL012–GL014): the
# mutable tier's key dimensions.  delta_cap is the one GRID here —
# delta growth must swap between pre-warmed capacity rungs, never
# recompile (the PR 9 discipline GL013 now enforces statically).
COMPILE_SURFACE_RUNGS = {
    "delta_cap": ("delta_capacities", (1024, 4096, 16384),
                  "the delta-segment capacity rung ladder "
                  "(MutateConfig.delta_capacities) — growth swaps "
                  "operand shapes between pre-warmed programs"),
    "delta_rung": ("delta_capacities", None,
                   "a rung INDEX into delta_capacities"),
    "tomb_words": ("tomb_words", None,
                   "packed tombstone bitmap width — fixed per epoch "
                   "(id_base/32), changes only at compaction swap"),
    "tombstone_slack": ("tombstone_slack", None,
                        "k + slack over-fetch — config, fixed per "
                        "index"),
}

_SQRT_METRICS = (DistanceType.L2SqrtExpanded,
                 DistanceType.L2SqrtUnexpanded)


def _descending(metric: DistanceType) -> bool:
    """True when the family's OUTPUT distances sort larger-is-better
    (InnerProduct returns similarities)."""
    return metric == DistanceType.InnerProduct


def delta_scores(q, delta_data, delta_norms, delta_ids,
                 metric: DistanceType) -> jax.Array:
    """Exact (nq, cap) delta-segment scores in the family OUTPUT
    convention; invalid slots (id < 0) land at the worst value."""
    from raft_tpu.neighbors.ivf_flat import _metric_kind, _postprocess
    kind = _metric_kind(metric)
    if metric == DistanceType.CosineExpanded:
        # delta rows are stored normalized (upsert applies the build()
        # row normalization); queries normalize here like the main fn
        q = q / jnp.maximum(jnp.linalg.norm(q, axis=1, keepdims=True),
                            1e-30)
    ip = jnp.matmul(q, delta_data.T, precision=matmul_precision(),
                    preferred_element_type=jnp.float32)
    if kind == "ip":
        s = -ip
    else:
        qq = jnp.sum(q * q, axis=1)
        s = jnp.maximum(qq[:, None] + delta_norms[None, :] - 2.0 * ip,
                        0.0)
        if metric in _SQRT_METRICS:
            s = jnp.sqrt(s)
    s = jnp.where(delta_ids[None, :] >= 0, s, jnp.inf)
    return _postprocess(s, metric)


def _tombstone_dead(ids, tomb_words) -> jax.Array:
    """Per-candidate dead mask from the packed uint32 bitmap. -1
    (pad) ids shift to word 0 via the clip but are dead regardless."""
    word = tomb_words[jnp.clip(ids >> 5, 0, tomb_words.shape[0] - 1)]
    bit = (word >> (ids & 31).astype(jnp.uint32)) & jnp.uint32(1)
    return (ids < 0) | (bit != 0)


def mutate_tail(d_main, i_main, ds, delta_ids, tomb_words, k: int,
                metric: DistanceType) -> Tuple[jax.Array, jax.Array]:
    """Tombstone-filter the main results, top-k the delta scores, and
    merge — the postprocess stages of the mutable serving program."""
    desc = _descending(metric)
    worst = -jnp.inf if desc else jnp.inf
    dead = _tombstone_dead(i_main, tomb_words)
    d_main = jnp.where(dead, worst, d_main)
    i_main = jnp.where(dead, -1, i_main)
    # delta top-k (cap may undercut k on the smallest rung — merging
    # fewer candidates is still exact, the delta only HAS cap rows)
    kd = min(k, ds.shape[1])
    vd, sel = lax.top_k(ds if desc else -ds, kd)
    dd = vd if desc else -vd
    id_d = jnp.take(delta_ids, sel)
    id_d = jnp.where(jnp.isfinite(dd), id_d, -1)
    cat_d = jnp.concatenate([d_main, dd], axis=1)
    cat_i = jnp.concatenate([i_main, id_d], axis=1)
    v, sel2 = lax.top_k(cat_d if desc else -cat_d, k)
    return (v if desc else -v), jnp.take_along_axis(cat_i, sel2, axis=1)


class MutateExecutable:
    """One AOT-compiled (nq, n_probes, delta-rung) operating point of a
    mutable index's epoch: ``run(q, dd, dn, di, tw)`` hands the
    executable its baked main-index operands plus the CURRENT delta /
    tombstone device buffers (same shapes each call — that is the
    rung contract)."""

    __slots__ = ("executable", "operands", "nq", "k", "n_probes", "cap",
                 "delta_cap", "tomb_words")

    def __init__(self, executable, operands, nq, k, n_probes, cap,
                 delta_cap, tomb_words):
        self.executable = executable
        self.operands = operands
        self.nq = int(nq)
        self.k = int(k)
        self.n_probes = int(n_probes)
        self.cap = int(cap)
        self.delta_cap = int(delta_cap)
        self.tomb_words = int(tomb_words)

    def run(self, q, delta_data, delta_norms, delta_ids, tomb_words):
        return self.executable(q, *self.operands, delta_data,
                               delta_norms, delta_ids, tomb_words)


def _delta_structs(delta_cap: int, dim: int, tomb_words: int):
    return (jax.ShapeDtypeStruct((delta_cap, dim), jnp.float32),
            jax.ShapeDtypeStruct((delta_cap,), jnp.float32),
            jax.ShapeDtypeStruct((delta_cap,), jnp.int32),
            jax.ShapeDtypeStruct((tomb_words,), jnp.uint32))


def compile_mutate_program(index, rep_queries, nq: int, k: int, params,
                           delta_cap: int, tomb_words: int,
                           slack: int = 16) -> MutateExecutable:
    """AOT-compile the full mutable serving program — the family's plan
    program (ISSUE 2 builders, fused kernels and all) with the delta
    merge + tombstone filter appended — for one (nq, n_probes,
    delta-rung) point. The main phase fetches ``k + slack`` candidates
    (the tombstone filter runs post-top-k: slack absorbs dead ids
    without losing result slots — ``MutateConfig.tombstone_slack``).
    The ONE cap-measurement sync of the plan lifecycle happens here,
    never on the serving path. Counted under
    ``raft.plan.cache.misses`` / ``raft.plan.build.total`` so the
    zero-steady-state-compile assertion reads the same counters as the
    immutable serving tier."""
    import numpy as np
    from raft_tpu.neighbors import plan as plan_mod

    family, program = plan_mod._resolve_builder(index)
    q = np.asarray(rep_queries, np.float32)
    expects(q.ndim == 2 and q.shape[1] == index.dim,
            "mutate: rep_queries must be (nq, dim=%d), got %s",
            index.dim, q.shape)
    reps = -(-nq // q.shape[0])
    q = np.tile(q, (reps, 1))[:nq]
    k_main = k + max(0, int(slack))
    metric = index.metric
    obs.counter("raft.plan.cache.misses").inc()
    obs.counter("raft.plan.build.total").inc()
    with obs.timed("raft.mutate.plan.build", family=family):
        r = plan_mod.resolve_cap(plan_mod.route(index, nq, k_main, params),
                                 index, jnp.asarray(q), params)
        expects(not r.host_tail,
                "mutate: the wrapped %s plan needs a host-side rescore "
                "epilogue (raw corpus off-device) — mutable serving "
                "requires a sync-free plan (keep_raw=False, or device "
                "rescore)", family)
        operands = plan_mod._operands(r, index)
        n_ops = len(operands)

        def fused(q_in, *ops):
            core, (dd, dn, di, tw) = ops[:n_ops], ops[n_ops:]
            d, i = program(q_in, *core, r=r)
            ds = delta_scores(q_in, dd, dn, di, metric)
            return mutate_tail(d, i.astype(jnp.int32), ds, di, tw, k,
                               metric)

        q_struct = jax.ShapeDtypeStruct((nq, index.dim), jnp.float32)
        # plan-cache idiom: compiled ONCE per (epoch, nq, rung) key and
        # cached on the epoch — the fresh callable never re-traces
        t_c0 = time.perf_counter()
        executable = jax.jit(fused).lower(  # graftlint: disable=GL002
            q_struct, *operands,
            *_delta_structs(delta_cap, index.dim, tomb_words)).compile()
        # compile-time ledger (resource profiler): idle-chip seconds
        profiler.note_compile("mutate", time.perf_counter() - t_c0)
    return MutateExecutable(executable, operands, nq, k, r.n_probes, r.cap,
                            delta_cap, tomb_words)


class TailExecutable:
    """The delta-merge + tombstone-filter stages compiled ALONE —
    composed after a search whose main phase is its own dispatch (the
    distributed serving tier: the shard_map program and its cross-shard
    merge stay untouched; this program post-processes the merged
    results against the replicated delta segment)."""

    __slots__ = ("executable", "nq", "k", "delta_cap", "tomb_words")

    def __init__(self, executable, nq, k, delta_cap, tomb_words):
        self.executable = executable
        self.nq = int(nq)
        self.k = int(k)
        self.delta_cap = int(delta_cap)
        self.tomb_words = int(tomb_words)

    def run(self, q, d, i, delta_data, delta_norms, delta_ids,
            tomb_words):
        return self.executable(q, d, i, delta_data, delta_norms,
                               delta_ids, tomb_words)


def compile_tail_program(nq: int, k: int, dim: int, metric,
                         delta_cap: int, tomb_words: int,
                         k_main: Optional[int] = None,
                         d_dtype=jnp.float32, i_dtype=jnp.int32
                         ) -> TailExecutable:
    """AOT-compile the standalone tail for one (nq, delta-rung) point
    (counted under the same plan counters as the fused program).
    ``k_main`` is the width of the incoming main-phase results (``k +
    tombstone_slack`` when the upstream search over-fetches)."""
    obs.counter("raft.plan.cache.misses").inc()
    obs.counter("raft.plan.build.total").inc()
    k_main = k if k_main is None else int(k_main)

    def tail(q, d, i, dd, dn, di, tw):
        ds = delta_scores(q, dd, dn, di, metric)
        return mutate_tail(d.astype(jnp.float32), i.astype(jnp.int32),
                           ds, di, tw, k, metric)

    # plan-cache idiom: compiled ONCE per (epoch, nq, delta-rung) key
    # and cached on the epoch — the fresh callable never re-traces
    t_c0 = time.perf_counter()
    executable = jax.jit(tail).lower(  # graftlint: disable=GL002
        jax.ShapeDtypeStruct((nq, dim), jnp.float32),
        jax.ShapeDtypeStruct((nq, k_main), d_dtype),
        jax.ShapeDtypeStruct((nq, k_main), i_dtype),
        *_delta_structs(delta_cap, dim, tomb_words)).compile()
    profiler.note_compile("mutate", time.perf_counter() - t_c0)
    return TailExecutable(executable, nq, k, delta_cap, tomb_words)
