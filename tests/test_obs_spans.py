"""Tests for the request-tracing layer (ISSUE 3): span nesting and
trace propagation, the flight recorder (eviction + slow-query log),
Chrome-trace export validity, the debug endpoint routes, the
RAFT_TPU_TRACE=0 no-op contract, and the serving-path integration
(a plan search producing a trace of its enqueue and device wait;
batched sub-batch spans sharing one trace)."""

import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu import obs
from raft_tpu.obs import recorder as recorder_mod
from raft_tpu.obs import spans


@pytest.fixture
def tracing():
    """Tracing on + a clean global recorder, state restored after."""
    prev = spans.trace_enabled()
    spans.set_trace_enabled(True)
    obs.RECORDER.clear()
    yield obs.RECORDER
    obs.RECORDER.clear()
    spans.set_trace_enabled(prev)


class TestSpanBasics:
    def test_nesting_parent_child_one_trace(self, tracing):
        with spans.span("raft.t.root", who="root") as root:
            with spans.span("raft.t.mid") as mid:
                with spans.span("raft.t.leaf") as leaf:
                    assert leaf.trace_id == root.trace_id
                assert spans.current_span() is mid
            assert mid.parent_id == root.span_id
        tr = tracing.requests(1)[0]
        assert tr["name"] == "raft.t.root"
        by_name = {s["name"]: s for s in tr["spans"]}
        assert by_name["raft.t.leaf"]["parent_id"] == mid.span_id
        assert by_name["raft.t.mid"]["parent_id"] == root.span_id
        assert by_name["raft.t.root"]["parent_id"] is None
        # every span carries the SAME trace id (via the one trace dict)
        assert tr["trace_id"] == root.trace_id
        assert tr["attrs"] == {"who": "root"}

    def test_sibling_spans_share_parent(self, tracing):
        with spans.span("raft.t.root") as root:
            with spans.span("raft.t.a"):
                pass
            with spans.span("raft.t.b"):
                pass
        tr = tracing.requests(1)[0]
        parents = {s["name"]: s["parent_id"] for s in tr["spans"]}
        assert parents["raft.t.a"] == root.span_id
        assert parents["raft.t.b"] == root.span_id

    def test_exception_records_error_attr(self, tracing):
        with pytest.raises(RuntimeError):
            with spans.span("raft.t.root"):
                raise RuntimeError("boom")
        tr = tracing.requests(1)[0]
        assert tr["spans"][-1]["attrs"]["error"] == "RuntimeError"

    def test_taxonomy_enforced(self, tracing):
        # assembled so the repo-wide source lint does not see a
        # literal bad name at this call site
        bad = "not" + ".raft.name"
        with pytest.raises(ValueError):
            with spans.span(bad):
                pass

    def test_spanned_decorator_reentrant(self, tracing):
        @spans.spanned("raft.t.fib")
        def fib(n):
            return n if n < 2 else fib(n - 1) + fib(n - 2)

        assert fib(4) == 3
        # each top-level call is its own trace; recursion nests inside
        traces = tracing.requests()
        assert all(t["name"] == "raft.t.fib" for t in traces)
        assert len(traces[0]["spans"]) > 1

    def test_set_attrs_and_durations(self, tracing):
        with spans.span("raft.t.root") as sp:
            sp.set_attrs(a=1, b="x")
            sp.set_attr("c", 2)
        tr = tracing.requests(1)[0]
        assert tr["attrs"] == {"a": 1, "b": "x", "c": 2}
        root = tr["spans"][-1]
        assert root["duration_ms"] >= 0
        assert tr["duration_ms"] == root["duration_ms"]

    def test_sync_records_device_ms(self, tracing):
        with spans.span("raft.t.root") as sp:
            x = jnp.ones((8, 8)) * 2.0
            sp.sync(x)
        tr = tracing.requests(1)[0]
        assert tr["attrs"]["device_ms"] >= 0

    def test_add_child_span_rank_tag(self, tracing):
        import time
        with spans.span("raft.t.root") as root:
            t0 = time.perf_counter()
            spans.add_child_span("raft.t.shard", t0, 0.001, rank=3)
        tr = tracing.requests(1)[0]
        sh = [s for s in tr["spans"] if s["name"] == "raft.t.shard"][0]
        assert sh["attrs"]["rank"] == 3
        assert sh["parent_id"] == root.span_id


class TestDisabledNoop:
    def test_span_returns_shared_null(self, tracing):
        spans.set_trace_enabled(False)
        s1 = spans.span("raft.t.x", a=1)
        s2 = spans.span("raft.t.y")
        # the hot path allocates NO span objects when disabled: one
        # shared null instance, reused for every call site
        assert s1 is s2
        with s1 as sp:
            sp.set_attr("k", 1)  # accepted, dropped
            assert sp.sync(jnp.ones(2)) == 0.0
        assert spans.current_span() is s1
        assert spans.current_trace_id() is None
        assert len(obs.RECORDER) == 0

    def test_nothing_recorded_when_disabled(self, tracing):
        spans.set_trace_enabled(False)
        with spans.span("raft.t.root"):
            with spans.span("raft.t.child"):
                pass
        assert obs.RECORDER.requests() == []

    def test_env_toggle_spellings(self, monkeypatch):
        for v, want in (("0", False), ("false", False), ("off", False),
                        ("no", False), ("1", True), ("", True)):
            monkeypatch.setenv("RAFT_TPU_TRACE", v)
            assert spans._env_enabled() is want
        monkeypatch.delenv("RAFT_TPU_TRACE")
        assert spans._env_enabled() is True

    def test_mid_trace_disable_still_balanced(self, tracing):
        # a span opened while enabled must close cleanly even if
        # tracing is switched off inside it
        with spans.span("raft.t.root"):
            spans.set_trace_enabled(False)
            with spans.span("raft.t.child"):
                pass
        spans.set_trace_enabled(True)
        assert len(obs.RECORDER) == 1


def _trace(trace_id="t1", name="raft.x.search", dur=1.0, n_spans=1,
           attrs=None):
    return {"trace_id": trace_id, "name": name, "start_unix": 1e9,
            "duration_ms": dur,
            "spans": [{"name": name, "span_id": f"s{i}",
                       "parent_id": None, "t_start_ms": 0.0,
                       "duration_ms": dur, "tid": 7}
                      for i in range(n_spans)],
            **({"attrs": attrs} if attrs else {})}


class TestFlightRecorder:
    def test_ring_eviction(self):
        reg = obs.MetricsRegistry(enabled=True)
        rec = recorder_mod.FlightRecorder(capacity=4, slow_ms=1e9,
                                          registry=reg)
        for i in range(10):
            rec.record(_trace(trace_id=f"t{i}"))
        assert len(rec) == 4
        ids = [t["trace_id"] for t in rec.requests()]
        assert ids == ["t9", "t8", "t7", "t6"]  # most recent first
        assert rec.get("t0") is None            # evicted
        assert rec.get("t9")["trace_id"] == "t9"
        assert rec.recorded_total == 10

    def test_slow_threshold_and_slow_ring(self):
        reg = obs.MetricsRegistry(enabled=True)
        rec = recorder_mod.FlightRecorder(capacity=2, slow_ms=100.0,
                                          registry=reg)
        rec.record(_trace("fast", dur=5.0))
        rec.record(_trace("slow1", dur=150.0))
        # the fast flood evicts slow1 from the main ring...
        rec.record(_trace("f2", dur=1.0))
        rec.record(_trace("f3", dur=1.0))
        assert rec.get("slow1") is not None      # ...but the slow ring keeps it
        assert [t["trace_id"] for t in rec.slow_requests()] == ["slow1"]
        snap = reg.snapshot()["counters"]
        assert snap["raft.obs.recorder.traces"] == 4
        assert snap["raft.obs.recorder.slow_traces"] == 1

    def test_slow_log_only_for_requests(self):
        reg = obs.MetricsRegistry(enabled=True)
        rec = recorder_mod.FlightRecorder(capacity=8, slow_ms=100.0,
                                          registry=reg)
        rec.record(_trace("b", name="raft.ivf_flat.build", dur=5000.0))
        assert rec.slow_requests() == []         # builds are not queries
        rec.record(_trace("s", name="raft.plan.search", dur=5000.0))
        assert [t["trace_id"] for t in rec.slow_requests()] == ["s"]

    def test_runtime_threshold_override(self):
        reg = obs.MetricsRegistry(enabled=True)
        rec = recorder_mod.FlightRecorder(capacity=8, slow_ms=1e9,
                                          registry=reg)
        rec.set_slow_threshold_ms(10.0)
        rec.record(_trace("s", dur=20.0))
        assert len(rec.slow_requests()) == 1

    def test_to_json_shape(self):
        rec = recorder_mod.FlightRecorder(
            capacity=8, slow_ms=100.0,
            registry=obs.MetricsRegistry(enabled=False))
        rec.record(_trace("a", dur=1.0))
        rec.record(_trace("b", dur=500.0))
        body = rec.to_json()
        assert body["capacity"] == 8
        assert body["slow_threshold_ms"] == 100.0
        assert body["recorded_total"] == 2
        assert body["slow_trace_ids"] == ["b"]
        assert [t["trace_id"] for t in body["traces"]] == ["b", "a"]
        assert [t["trace_id"]
                for t in rec.to_json(1)["traces"]] == ["b"]
        json.dumps(body)  # JSON-serializable end to end

    def test_env_knobs(self, monkeypatch):
        monkeypatch.setenv("RAFT_TPU_TRACE_RING", "7")
        monkeypatch.setenv("RAFT_TPU_TRACE_SLOW_MS", "42.5")
        rec = recorder_mod.FlightRecorder(
            registry=obs.MetricsRegistry(enabled=False))
        assert rec.capacity == 7
        assert rec.slow_ms == 42.5


class TestChromeExport:
    def test_events_valid(self, tracing):
        with spans.span("raft.t.root", k=8):
            with spans.span("raft.t.child"):
                pass
            spans.add_child_span("raft.t.shard", 0.0, 0.001, rank=2)
        ct = obs.to_chrome_trace(tracing.requests(1)[0])
        # round-trips as JSON
        ct = json.loads(json.dumps(ct))
        events = ct["traceEvents"]
        xs = [e for e in events if e["ph"] == "X"]
        assert len(xs) == 3
        for e in xs:
            for field in ("ts", "dur", "pid", "tid"):
                assert isinstance(e[field], (int, float)), e
            assert e["name"].startswith("raft.")
            assert e["args"]["trace_id"] == ct["otherData"]["trace_id"]
        shard = [e for e in xs if e["name"] == "raft.t.shard"][0]
        assert shard["pid"] == 2                 # rank → pid row
        child = [e for e in xs if e["name"] == "raft.t.child"][0]
        assert "parent_id" in child["args"]

    def test_passes_trace_lint(self, tracing):
        with spans.span("raft.t.root"):
            pass
        lint = _load_lint()
        text = json.dumps(obs.to_chrome_trace(tracing.requests(1)[0]))
        assert lint.lint_chrome_trace(text) == []


def _load_lint():
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "check_metric_names",
        os.path.join(os.path.dirname(__file__), "..", "tools",
                     "check_metric_names.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestSpanLint:
    # assembled so this file's own literals don't trip the source lint
    _SPAN = "spans." + "span({q}{name}{q})"

    def test_source_mode_flags_bad_span_name(self, tmp_path):
        lint = _load_lint()
        p = tmp_path / "bad.py"
        p.write_text(self._SPAN.format(name="cuml.bad.span", q='"') + "\n"
                     + self._SPAN.format(name="raft.good.span", q='"'))
        out = lint.lint_source([str(p)])
        assert len(out) == 1 and "taxonomy" in out[0]

    def test_span_never_kind_conflicts_with_metric(self, tmp_path):
        lint = _load_lint()
        p = tmp_path / "ok.py"
        p.write_text(
            self._SPAN.format(name="raft.x.op", q='"') + "\n" +
            "obs." + 'counter("raft.x.op").inc()\n')
        assert lint.lint_source([str(p)]) == []

    def test_required_span_coverage_full_scan(self, tmp_path,
                                              monkeypatch):
        lint = _load_lint()
        p = tmp_path / "only.py"
        p.write_text(self._SPAN.format(name="raft.x.op", q='"') + "\n")
        monkeypatch.setattr(lint, "iter_source_files",
                            lambda: [str(p)])
        out = lint.lint_source()
        for name in lint.REQUIRED_SPAN_NAMES:
            assert any(name in v for v in out)

    def test_trace_mode_flags_defects(self):
        lint = _load_lint()
        assert lint.lint_chrome_trace("{nope") != []
        assert lint.lint_chrome_trace('{"a": 1}') == \
            ["trace: no traceEvents array"]
        bad = {"traceEvents": [
            {"name": "not.raft", "ph": "X", "ts": 0, "dur": 1,
             "pid": 0, "tid": 0},
            {"name": "raft.x.y", "ph": "X", "ts": 0, "pid": 0,
             "tid": 0},  # missing dur
        ]}
        out = lint.lint_chrome_trace(json.dumps(bad))
        assert len(out) == 2


class TestEndpoint:
    def _get(self, url):
        try:
            r = urllib.request.urlopen(url, timeout=5)
            return r.status, r.read()
        except urllib.error.HTTPError as e:
            return e.code, e.read()

    def test_routes(self, tracing):
        reg = obs.MetricsRegistry(enabled=True)
        reg.counter("raft.t.hits").inc(3)
        rec = recorder_mod.FlightRecorder(capacity=8, slow_ms=1e9,
                                          registry=reg)
        rec.record(_trace("tr1", dur=1.0))
        with obs.serve(port=0, recorder=rec, registry=reg) as srv:
            code, body = self._get(srv.url + "/metrics")
            assert code == 200
            assert b"raft_t_hits_total 3" in body
            code, body = self._get(srv.url + "/healthz")
            assert code == 200
            assert json.loads(body)["status"] == "ok"
            code, body = self._get(srv.url + "/debug/requests")
            assert code == 200
            dump = json.loads(body)
            assert [t["trace_id"] for t in dump["traces"]] == ["tr1"]
            code, body = self._get(srv.url
                                   + "/debug/requests?trace=tr1")
            assert code == 200
            assert json.loads(body)["trace_id"] == "tr1"
            code, body = self._get(
                srv.url + "/debug/requests?format=chrome")
            assert code == 200
            ct = json.loads(body)
            assert _load_lint().lint_chrome_trace(body.decode()) == []
            assert any(e.get("ph") == "X" for e in ct["traceEvents"])
            code, _ = self._get(srv.url + "/debug/requests?trace=nope")
            assert code == 404
            code, _ = self._get(srv.url + "/nope")
            assert code == 404

    def test_healthz_degraded_on_suspects(self):
        reg = obs.MetricsRegistry(enabled=True)
        reg.gauge("raft.comms.health.suspects", session="s").set(2)
        reg.gauge("raft.comms.health.max_staleness_seconds",
                  session="s").set(30.0)
        rec = recorder_mod.FlightRecorder(
            capacity=2, registry=obs.MetricsRegistry(enabled=False))
        with obs.serve(port=0, recorder=rec, registry=reg) as srv:
            code, body = self._get(srv.url + "/healthz")
            assert code == 503
            body = json.loads(body)
            assert body["status"] == "degraded"
            assert list(body["suspects"].values()) == [2.0]


class TestServingIntegration:
    @pytest.fixture(scope="class")
    def flat(self):
        key = jax.random.key(0)
        db = jax.random.normal(key, (2000, 32))
        q = jax.random.normal(jax.random.fold_in(key, 1), (64, 32))
        from raft_tpu.neighbors import ivf_flat
        idx = ivf_flat.build(db, ivf_flat.IndexParams(
            n_lists=16, kmeans_n_iters=3))
        return idx, q

    def test_plan_search_trace_has_stage_breakdown(self, tracing, flat):
        """ONE blocking plan search → a recorded trace whose measured
        phases (the enqueue, then the device wait) are children of the
        raft.plan.search root, with plan/cap attributes, exportable as
        valid Chrome-trace JSON. No fixed-fraction stage spans: the
        stages are named scopes inside the compiled program."""
        from raft_tpu.neighbors import ivf_flat, plan as plan_mod
        idx, q = flat
        pl = plan_mod.warmup(idx, q, 8,
                             ivf_flat.SearchParams(n_probes=4))
        obs.RECORDER.clear()
        pl.search(q, block=True)
        tr = obs.RECORDER.requests(1)[0]
        assert tr["name"] == "raft.plan.search"
        by_name = {s["name"]: s for s in tr["spans"]}
        root = by_name["raft.plan.search"]
        enq = by_name["raft.plan.enqueue"]
        wait = by_name["raft.plan.device_wait"]
        assert enq["parent_id"] == root["span_id"]
        assert wait["parent_id"] == root["span_id"]
        assert enq["t_start_ms"] + enq["duration_ms"] \
            <= wait["t_start_ms"] + 1e-3
        assert not [n for n in by_name if n.startswith("raft.plan.stage")]
        assert tr["attrs"]["cap"] == pl.cap
        assert tr["attrs"]["n_probes"] == pl.n_probes
        assert tr["attrs"]["family"] == "ivf_flat"
        text = json.dumps(obs.to_chrome_trace(tr))
        assert json.loads(text)["traceEvents"]
        assert _load_lint().lint_chrome_trace(text) == []

    def test_plan_build_trace_cache_attrs(self, tracing, flat):
        from raft_tpu.neighbors import ivf_flat, plan as plan_mod
        idx, q = flat
        sp = ivf_flat.SearchParams(n_probes=4)
        plan_mod.build_plan(idx, q, 8, sp, warm=False)
        obs.RECORDER.clear()
        plan_mod.build_plan(idx, q, 8, sp, warm=False)  # cache hit
        builds = [t for t in obs.RECORDER.requests()
                  if t["name"] == "raft.plan.build"]
        assert builds and builds[0]["attrs"]["plan_cache"] == "hit"

    def test_batched_search_sub_batches_one_trace(self, tracing):
        from raft_tpu.neighbors.ann_types import batched_search

        def one(qb):
            return qb[:, :2], jnp.zeros((qb.shape[0], 2), jnp.int32)

        q = jnp.ones((10, 4))
        with spans.span("raft.t.request") as root:
            batched_search(one, q, max_batch=4)
        tr = tracing.requests(1)[0]
        subs = [s for s in tr["spans"]
                if s["name"] == "raft.ann.sub_batch"]
        assert len(subs) == 3                    # 4 + 4 + 2
        assert all(s["parent_id"] == root.span_id for s in subs)
        assert [s["attrs"]["rows"] for s in subs] == [4, 4, 2]
        assert subs[-1]["attrs"]["padded"] == 2

    def test_cold_search_records_cap_mode(self, tracing, flat):
        from raft_tpu.neighbors import ivf_flat
        idx, q = flat
        sp = ivf_flat.SearchParams(n_probes=4)
        ivf_flat.search(idx, q, 8, sp)           # warm the cap cache
        obs.RECORDER.clear()
        ivf_flat.search(idx, q, 8, sp)
        tr = obs.RECORDER.requests(1)[0]
        assert tr["name"] == "raft.ivf_flat.search"
        assert tr["attrs"]["cap_mode"] in ("cache_hit", "measured")
        assert tr["attrs"]["nq"] == 64

    def test_trace_off_serving_still_works(self, tracing, flat):
        from raft_tpu.neighbors import ivf_flat, plan as plan_mod
        idx, q = flat
        pl = plan_mod.warmup(idx, q, 8,
                             ivf_flat.SearchParams(n_probes=4))
        spans.set_trace_enabled(False)
        obs.RECORDER.clear()
        d, i = pl.search(q, block=True)
        assert d.shape == (64, 8)
        assert len(obs.RECORDER) == 0


@pytest.mark.skipif(not hasattr(jax, "shard_map"),
                    reason="this jax lacks jax.shard_map")
class TestShardedTrace:
    def test_rank_tagged_shard_spans(self, tracing, devices):
        from raft_tpu.parallel.mesh import make_mesh
        from raft_tpu.parallel.ivf import (distributed_ivf_flat_build,
                                           distributed_ivf_flat_search_parts)
        mesh = make_mesh(axis_names=("data",))
        key = jax.random.key(0)
        db = jax.random.normal(key, (512, 16))
        q = jax.random.normal(jax.random.fold_in(key, 1), (8, 16))
        from raft_tpu.neighbors.ivf_flat import IndexParams, SearchParams
        dindex = distributed_ivf_flat_build(
            db, IndexParams(n_lists=8, kmeans_n_iters=2), mesh)
        obs.RECORDER.clear()
        distributed_ivf_flat_search_parts(
            dindex, q, 4, SearchParams(n_probes=2))
        traces = [t for t in obs.RECORDER.requests()
                  if t["name"] == "raft.parallel.ivf.search"]
        assert traces
        tr = traces[0]
        shard = [s for s in tr["spans"]
                 if s["name"] == "raft.parallel.ivf.shard"]
        n_shards = mesh.shape["data"]
        assert len(shard) == n_shards
        assert sorted(s["attrs"]["rank"] for s in shard) == \
            list(range(n_shards))
        assert tr["attrs"]["n_shards"] == n_shards
        assert tr["attrs"].get("shmap_plan") in ("hit", "miss")


class TestKernelPrecisionThreading:
    def test_xla_precision_mapping(self):
        from jax import lax
        from raft_tpu.core.precision import (matmul_precision,
                                             xla_precision_for_kernel)
        assert xla_precision_for_kernel(None) == matmul_precision()
        assert xla_precision_for_kernel("bf16x3") == lax.Precision.HIGH
        assert xla_precision_for_kernel("bf16") == lax.Precision.DEFAULT
        assert xla_precision_for_kernel("default") == \
            lax.Precision.DEFAULT
        assert xla_precision_for_kernel("highest") == \
            lax.Precision.HIGHEST
        assert xla_precision_for_kernel(lax.Precision.HIGH) == \
            lax.Precision.HIGH
        with pytest.raises(ValueError):
            xla_precision_for_kernel("fp4")

    def test_pq_codebook_knob_reaches_trainer(self):
        """The knob used to be silently del'd in
        _train_codebooks_per_subspace; every spelling must now build
        (and the trainer must see the resolved precision)."""
        from raft_tpu.neighbors import ivf_pq
        key = jax.random.key(3)
        db = jax.random.normal(key, (512, 16))
        outs = []
        for kp in (None, "bf16", "bf16x3", "highest"):
            idx = ivf_pq.build(db, ivf_pq.IndexParams(
                n_lists=4, kmeans_n_iters=2, pq_dim=4, pq_bits=4,
                kmeans_kernel_precision=kp))
            assert idx.pq_centers.shape == (4, 16, 4)
            outs.append(np.asarray(idx.pq_centers))
        # highest and the None default (highest) agree exactly on CPU
        np.testing.assert_allclose(outs[0], outs[3])


class TestTraceparent:
    """W3C-style cross-process propagation (ISSUE 16): the header is
    `00-<trace_id>-<span_id>-01`, trace_id itself contains a dash
    (`{pid:x}-{counter:08x}`) so parsing is anchored at both ends."""

    def test_header_round_trips(self, tracing):
        with spans.span("raft.t.root") as sp:
            hdr = spans.current_traceparent()
            assert hdr == f"00-{sp.trace_id}-{sp.span_id}-01"
            assert spans.parse_traceparent(hdr) == (sp.trace_id,
                                                    sp.span_id)

    def test_no_open_span_means_no_header(self, tracing):
        assert spans.current_traceparent() is None
        with spans.span("raft.t.root"):
            pass
        assert spans.current_traceparent() is None

    def test_disabled_tracing_means_no_header(self, tracing):
        spans.set_trace_enabled(False)
        with spans.span("raft.t.root"):
            assert spans.current_traceparent() is None

    def test_parse_is_lenient_never_raises(self, tracing):
        for bad in (None, "", " ", "junk", "00", "00-", "00-a",
                    "00-a-", "01-a-b-01", "00--b-01", "00-a--01",
                    "zz-a-b-01", "00-a-b-01-extra-extra"):
            assert spans.parse_traceparent(bad) is None
        # whitespace around a valid header is tolerated
        assert spans.parse_traceparent("  00-1a-2b-3c-01  ") == \
            ("1a-2b", "3c")

    def test_remote_parent_links_across_threads(self, tracing):
        import threading

        box = {}
        with spans.span("raft.t.upstream") as up:
            box["hdr"] = spans.current_traceparent()

        def worker():
            with spans.span("raft.t.remote_child",
                            remote_parent=box["hdr"]) as ch:
                box["tid"] = ch.trace_id
                box["pid"] = ch.parent_id

        t = threading.Thread(target=worker)
        t.start()
        t.join()
        assert box["tid"] == up.trace_id
        assert box["pid"] == up.span_id
        child = [f for f in tracing.fragments(up.trace_id)
                 if f["name"] == "raft.t.remote_child"][0]
        assert child["remote_parent"] == up.span_id

    def test_malformed_remote_parent_roots_fresh_trace(self, tracing):
        with spans.span("raft.t.root", remote_parent="not-a-header") \
                as sp:
            assert sp.trace_id
            assert sp.parent_id is None

    def test_fragments_dedupes_slow_and_ring(self, tracing):
        # a slow REQUEST trace lands in both the ring and the slow
        # log; fragments() must return it once
        rec = recorder_mod.FlightRecorder(slow_ms=0.0)
        with spans.span("raft.t.search", request=True) as sp:
            tid = sp.trace_id
        tr = obs.RECORDER.requests(1)[0]
        rec.record(tr)
        assert len(rec.fragments(tid)) == 1
