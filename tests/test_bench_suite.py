"""Tests for the bench harness machinery (bench_suite.check_gates
- the perf-regression gate, the role of the reference's recall
thresholds + gbench tracking)."""


class TestPerfGates:
    """The bench perf-regression gate machinery (bench_suite.check_gates
    — the role of the reference's recall thresholds + gbench tracking)."""

    def _rows(self, **over):
        # metric names derive from the suite's operating-point
        # constants: a moved headline point must move its gates with it
        import bench_suite as bs
        fp, ip = bs.FLAT_PROBES, bs.IVF_PROBES
        rows = [{"metric": "pairwise_L2Expanded_8192x8192x256_ms",
                 "value": 10.0},
                {"metric": "pairwise_L1_8192x8192x256_ms", "value": 50.0},
                {"metric": "bfknn_fused_500kx128_q1000_k32_qps",
                 "value": 90_000.0},
                {"metric": f"ivf_flat_search_500kx128_q1000_k32_p{fp}_qps",
                 "value": 50_000.0, "recall": 0.93},
                {"metric": f"ivf_flat_search_100kx128_q1000_k32_p{fp}_qps",
                 "value": 60_000.0, "recall": 0.93,
                 "marginal_gap": 1.4},
                {"metric": f"ivf_pq_search_500kx128_q1000_k32_p{ip}_qps",
                 "value": 50_000.0, "recall": 0.92},
                {"metric": f"ivf_pq4_search_500kx128_q1000_k32_p{ip}_qps",
                 "value": 50_000.0, "recall": 0.90},
                {"metric": f"ivf_bq_search_500kx128_q1000_k32_p{ip}_qps",
                 "value": 50_000.0, "recall": 0.70}]
        for r in rows:
            if r["metric"] in over:
                r["value"] = over[r["metric"]]
        return rows

    def test_all_pass(self):
        import bench_suite
        assert bench_suite.check_gates(self._rows()) == []

    def test_ceiling_trip(self):
        import bench_suite
        fails = bench_suite.check_gates(self._rows(
            **{"pairwise_L2Expanded_8192x8192x256_ms": 99.0}))
        assert [f["metric"] for f in fails] == \
            ["pairwise_L2Expanded_8192x8192x256_ms"]
        assert fails[0]["kind"] == "ceiling"

    def test_qps_floor_trip(self):
        import bench_suite
        fails = bench_suite.check_gates(self._rows(**{
            f"ivf_flat_search_500kx128_q1000_k32"
            f"_p{bench_suite.FLAT_PROBES}_qps": 100.0}))
        assert fails and fails[0]["kind"] == "floor"

    def test_missing_metric_is_a_failure(self):
        """A PERF gate must never pass by not running (require_all
        mode) — drop a speed-gate-only row so this exercises the
        PERF_GATES missing branch, not the recall one."""
        import bench_suite
        metric = "bfknn_fused_500kx128_q1000_k32_qps"
        rows = [r for r in self._rows() if r["metric"] != metric]
        fails = bench_suite.check_gates(rows, require_all=True)
        assert any(f["kind"] == "missing" and f["metric"] == metric
                   for f in fails)
        # case-filtered runs don't charge unselected gates
        assert bench_suite.check_gates(rows, require_all=False) == []

    def test_recall_gate_trips(self):
        import bench_suite
        metric = (f"ivf_pq_search_500kx128_q1000_k32"
                  f"_p{bench_suite.IVF_PROBES}_qps")
        rows = self._rows(**{})
        for r in rows:
            if r["metric"] == metric:
                r["recall"] = 0.51
        fails = bench_suite.check_gates(rows)
        assert [f["kind"] for f in fails] == ["recall"]
        assert fails[0]["metric"] == metric

    def test_recall_gate_never_passes_by_not_running(self):
        """A recall-gated row that didn't run (case errored, or its
        recall field vanished) is a failure under require_all."""
        import bench_suite
        metric = (f"ivf_pq_search_500kx128_q1000_k32"
                  f"_p{bench_suite.IVF_PROBES}_qps")
        rows = [r for r in self._rows() if r["metric"] != metric]
        fails = bench_suite.check_gates(rows, require_all=True)
        assert any(f["kind"] == "missing" and f["metric"] == metric
                   for f in fails)
        # case-filtered runs don't charge unselected recall gates
        assert bench_suite.check_gates(rows, require_all=False) == []
        # a row missing only its recall field is also charged
        rows2 = self._rows()
        for r in rows2:
            if r["metric"] == metric:
                del r["recall"]
        fails2 = bench_suite.check_gates(rows2, require_all=True)
        assert any(f["kind"] == "missing" and f["metric"] == metric
                   for f in fails2)


class TestGapGate:
    """GAP_GATES (ISSUE 7): marginal_qps / plan_qps ceilings — the
    marginal-vs-end-to-end gap as a first-class regression signal."""

    def _rows(self, **kw):
        return TestPerfGates()._rows(**kw)

    def _flat100k(self):
        import bench_suite
        return (f"ivf_flat_search_100kx128_q1000_k32"
                f"_p{bench_suite.FLAT_PROBES}_qps")

    def test_gap_ceiling_trips(self):
        import bench_suite
        rows = self._rows()
        for r in rows:
            if r["metric"] == self._flat100k():
                r["marginal_gap"] = 5.3   # the round-5 class of gap
        fails = bench_suite.check_gates(rows)
        assert [f["kind"] for f in fails] == ["marginal_gap"]
        assert fails[0]["metric"] == self._flat100k()
        assert fails[0]["gate"] == 2.0

    def test_gap_gate_never_passes_by_not_running(self):
        import bench_suite
        rows = self._rows()
        for r in rows:
            if r["metric"] == self._flat100k():
                del r["marginal_gap"]
        fails = bench_suite.check_gates(rows, require_all=True)
        assert any(f["kind"] == "missing"
                   and f["metric"] == self._flat100k() for f in fails)
        # case-filtered runs don't charge unselected gap gates
        assert bench_suite.check_gates(rows, require_all=False) == []


class TestUnknownCase:
    def test_typod_case_name_refuses_to_run(self):
        """An unknown case name must never yield a silent empty run —
        a typo'd --gate invocation exiting green having measured
        nothing (VERDICT r4 #9)."""
        import pytest
        import bench_suite
        with pytest.raises(SystemExit, match="unknown case"):
            bench_suite.run_all(["ivf_flatt"])
