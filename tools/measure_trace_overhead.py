"""Measure the flight-recorder/span overhead on the flat serving row.

The ISSUE 3 acceptance gate: `bench_suite` `fixed_cost_ms`/`plan_qps`
for the flat row must regress < 5% with the recorder enabled. This
tool measures exactly those two figures (the flat row's own
methodology — warm per-call wall, chained in-jit marginal, warm AOT
plan per-call wall) in one process: tracing OFF
(`obs.set_trace_enabled(False)`), tracing ON (spans + flight
recorder, the shipped default), and — ISSUE 14 — PROFILING ON on top
(the continuous resource profiler attached at its default
``RAFT_TPU_PROFILE_SAMPLE`` rate; the per-dispatch marginal it adds is
one Bernoulli draw on the blocking path, gated < 5% too, measured on
a BLOCKED plan call since the profiler only arms around a sync the
caller was paying anyway). Writes the comparison to
``chiprun_out/trace_overhead_<platform>.json``.

Method notes:

* one build + one plan warmup are shared by both modes (the overhead
  under test is per-REQUEST host work: span allocation, attribute
  dicts, recorder append — not compile time);
* the chained in-jit marginal is measured ONCE and shared: it runs
  inside jit where host tracing cannot exist, so re-measuring it per
  mode would only inject device-noise into the `fixed_cost_ms`
  comparison (observed ±7% on CPU — larger than the effect under
  test). With a shared marginal, the OFF→ON `fixed_cost_ms` delta IS
  the per-call wall delta: exactly the host-side cost the recorder
  adds to one serving call;
* the OFF pass runs first, ON second; each wall is a best-of-5 of a
  mean over repeated calls (`bench_suite._time`), so allocator warmup
  biases AGAINST the ON pass if anything;
* `fixed_cost_ms` = per-batch wall − chained in-jit marginal, the
  bench_suite definition.

Run: PYTHONPATH=. python tools/measure_trace_overhead.py
Env: TRACE_OVERHEAD_N (default 100000) dataset rows; PROFILE_PLATFORM
to pin the backend (cpu for the harness); TRACE_OVERHEAD_OUT for the
artifact path.
"""
import json
import os
import time

import jax
import jax.numpy as jnp

if os.environ.get("PROFILE_PLATFORM"):
    jax.config.update("jax_platforms", os.environ["PROFILE_PLATFORM"])
print(jax.devices(), flush=True)

import bench_suite
from raft_tpu import obs
from raft_tpu.neighbors import ivf_flat
from raft_tpu.neighbors import plan as plan_mod

n = int(os.environ.get("TRACE_OVERHEAD_N", 100_000))
d, nq, k = 128, 1000, 32
nlists = 256
n_probes = 32
key = jax.random.key(4)

db, q = bench_suite._ann_dataset(n, d, nq)
jax.block_until_ready((db, q))
index = ivf_flat.build(db, ivf_flat.IndexParams(n_lists=nlists,
                                                kmeans_n_iters=10))
jax.block_until_ready(index.lists_data)
sp = ivf_flat.SearchParams(n_probes=n_probes)
ivf_flat.search(index, q, k, sp)               # warm + measure cap
pl = plan_mod.warmup(index, q, k, sp)

import dataclasses
spp = dataclasses.replace(sp, probe_cap=bench_suite._cached_cap(
    index, nq, n_probes))
reps = bench_suite._chain_reps()
qb = bench_suite._chained_batches(q, key, reps)
jax.block_until_ready(qb)


def run1(qq, centers, data, norms, idsarr, sizes):
    idx2 = ivf_flat.Index(
        centers=centers, lists_data=data, lists_indices=idsarr,
        lists_norms=norms, list_sizes=sizes, metric=index.metric,
        size=index.size, scale=index.scale)
    return ivf_flat.search(idx2, qq, k, spp)


# the shared in-jit marginal (host tracing cannot exist inside jit)
obs.set_trace_enabled(False)
t_marg = min(bench_suite._chained_search_time(
    run1, qb, reps, index.centers, index.lists_data,
    index.lists_norms, index.lists_indices, index.list_sizes)
    for _ in range(2))
print(f"shared marginal: {t_marg*1e3:.2f} ms/call", flush=True)


def measure():
    t = bench_suite._time(lambda: ivf_flat.search(index, q, k, sp),
                          reps=3)
    t_plan = bench_suite._time(lambda: pl.search(q), reps=3)
    # the blocking plan call — the serving dispatcher's shape, the
    # path the resource profiler arms on (ISSUE 14)
    t_plan_block = bench_suite._time(
        lambda: pl.search(q, block=True), reps=3)
    return t, t_plan, t_plan_block


from raft_tpu.obs import profiler

modes = {}
for mode, on, prof_rate in (("trace_off", False, 0.0),
                            ("trace_on", True, 0.0),
                            ("profile_on", True, None)):
    obs.set_trace_enabled(on)
    if prof_rate is None:
        # the shipped default rate (RAFT_TPU_PROFILE_SAMPLE, 0.01)
        profiler.enable_profiling()
    else:
        profiler.disable_profiling()
    obs.RECORDER.clear()
    t_best, t_plan_best, t_block_best = measure()
    for _ in range(4):
        t, t_plan, t_block = measure()
        t_best, t_plan_best, t_block_best = (
            min(t_best, t), min(t_plan_best, t_plan),
            min(t_block_best, t_block))
    modes[mode] = {
        "qps": round(nq / t_best, 1),
        "marginal_qps": round(nq / t_marg, 1),
        "plan_qps": round(nq / t_plan_best, 1),
        "plan_block_qps": round(nq / t_block_best, 1),
        "fixed_cost_ms": round((t_best - t_marg) * 1e3, 3),
        "plan_percall_ms": round(t_plan_best * 1e3, 3),
        "plan_block_percall_ms": round(t_block_best * 1e3, 3),
        "recorded_traces": len(obs.RECORDER),
    }
    if prof_rate is None:
        modes[mode]["profile_sample_rate"] = \
            profiler.profile_sample_rate()
        modes[mode]["profile_samples"] = profiler.report().get(
            "samples", 0)
    print(mode, json.dumps(modes[mode]), flush=True)
profiler.disable_profiling()
obs.set_trace_enabled(True)

off, on = modes["trace_off"], modes["trace_on"]
delta = {
    "plan_qps_ratio": round(on["plan_qps"] / off["plan_qps"], 4),
    # with the shared marginal this IS the per-call wall delta of the
    # cold-path search — the host cost tracing adds to one request
    "fixed_cost_ms_delta": round(
        on["fixed_cost_ms"] - off["fixed_cost_ms"], 3),
    # the < 5% gate on both serving figures (fixed_cost compared as a
    # share of the plan per-call wall — an absolute ms delta on a
    # near-zero baseline would gate on noise)
    "plan_qps_regression_pct": round(
        100.0 * (1.0 - on["plan_qps"] / off["plan_qps"]), 2),
    "fixed_cost_delta_pct_of_percall": round(
        100.0 * (on["fixed_cost_ms"] - off["fixed_cost_ms"])
        / max(off["plan_percall_ms"], 1e-9), 2),
}
delta["gate_lt_5pct"] = bool(
    delta["plan_qps_regression_pct"] < 5.0
    and delta["fixed_cost_delta_pct_of_percall"] < 5.0)

# profiling marginal (ISSUE 14): profile_on vs trace_on — the cost the
# resource profiler adds ON TOP of the shipped tracing default, at its
# default sample rate. The GATE reads the BLOCKING plan call only:
# that is the serving dispatcher's shape and the only path the
# profiler touches (`prof = block and profiler.sampled()` — the
# non-blocking path short-circuits before any draw, so its delta is
# pure machine noise and is reported informationally).
prof = modes["profile_on"]
delta["profile_plan_qps_regression_pct"] = round(
    100.0 * (1.0 - prof["plan_qps"] / on["plan_qps"]), 2)
delta["profile_block_regression_pct"] = round(
    100.0 * (1.0 - prof["plan_block_qps"] / on["plan_block_qps"]), 2)
delta["profile_gate_lt_5pct"] = bool(
    delta["profile_block_regression_pct"] < 5.0)

artifact = {
    "tool": "measure_trace_overhead",
    "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
    "platform": jax.devices()[0].platform,
    "shape": {"n": n, "dim": d, "nq": nq, "k": k, "n_lists": nlists,
              "n_probes": n_probes, "chain": reps},
    "modes": modes,
    "delta": delta,
}
here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
out_path = os.environ.get("TRACE_OVERHEAD_OUT") or os.path.join(
    here, "chiprun_out",
    f"trace_overhead_{jax.devices()[0].platform}.json")
os.makedirs(os.path.dirname(out_path), exist_ok=True)
with open(out_path, "w") as f:
    json.dump(artifact, f, indent=1)
print(json.dumps(delta), flush=True)
print(f"artifact -> {out_path}", flush=True)
