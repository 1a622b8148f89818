"""graftlint framework tests (ISSUE 6).

Per-rule positive/negative fixture snippets (each rule must flag its
bug class and stay silent on the idiomatic fix), the suppression and
baseline round-trips, the JSON output schema, and the tier-1 wrapper
asserting the real tree is clean under the checked-in baseline.

Metric-name fixtures are assembled from pieces (the same trick as
tests/test_obs.py) so THIS file's literals don't trip the repo-wide
GL010 scan.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.graftlint import core, engine  # noqa: E402


def _write(tmp_path, rel, text):
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(text)
    return p


def _run(root, select=None):
    findings, suppressed = engine.run(str(root), select=select)
    return findings, suppressed


def _codes(findings):
    return [f.rule for f in findings]


class TestFramework:
    def test_registry_has_contracted_rules(self):
        rules = core.all_rules()
        for code in ("GL001", "GL002", "GL003", "GL004", "GL005",
                     "GL006", "GL007", "GL008", "GL009", "GL010",
                     "GL011", "GL012", "GL013", "GL014"):
            assert code in rules, f"rule {code} missing from registry"

    def test_syntax_error_reported_not_crashed(self, tmp_path):
        _write(tmp_path, "raft_tpu/broken.py", "def f(:\n")
        findings, _ = _run(tmp_path)
        assert _codes(findings) == ["GL000"]

    def test_path_scoping(self, tmp_path):
        # GL004 scope is distance/linalg/neighbors — the same call in
        # ops/ stays silent
        src = "import jax.numpy as jnp\nd = jnp.dot(a, b)\n"
        _write(tmp_path, "raft_tpu/ops/x.py", src)
        findings, _ = _run(tmp_path, select=["GL004"])
        assert findings == []
        _write(tmp_path, "raft_tpu/linalg/x.py", src)
        findings, _ = _run(tmp_path, select=["GL004"])
        assert _codes(findings) == ["GL004"]


class TestGL001HostSync:
    BUG = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    c = float(x.max())\n"
        "    a = np.asarray(x)\n"
        "    x.block_until_ready()\n"
        "    return x * c, a\n")

    OK = (
        "import functools\n"
        "import jax\n"
        "import jax.numpy as jnp\n"
        "@functools.partial(jax.jit, static_argnames=('mode',))\n"
        "def f(x, mode):\n"
        "    k = x.shape[1]\n"
        "    scale = float(k) * float(mode)\n"
        "    n = float(len(x))\n"
        "    return x * scale / n\n"
        "def host_path(x):\n"
        "    return float(x.max())\n")   # not jitted: host code is fine

    LOWERED = (
        "import jax\n"
        "def make():\n"
        "    def fn(q):\n"
        "        return int(q.sum())\n"
        "    return fn\n"
        "def build(f):\n"
        "    return jax.jit(fn)\n")      # fn jitted by name elsewhere

    def test_flags_sync_in_decorated_jit(self, tmp_path):
        _write(tmp_path, "raft_tpu/a.py", self.BUG)
        findings, _ = _run(tmp_path, select=["GL001"])
        assert _codes(findings) == ["GL001"] * 3
        assert "float()" in findings[0].message
        assert "np.asarray" in findings[1].message
        assert "block_until_ready" in findings[2].message

    def test_static_values_and_host_code_stay_silent(self, tmp_path):
        _write(tmp_path, "raft_tpu/a.py", self.OK)
        findings, _ = _run(tmp_path, select=["GL001"])
        assert findings == []

    def test_flags_jit_by_name(self, tmp_path):
        # the plan.py shape: `fn` built in one function, jitted in
        # another — marking is by name, module-wide
        _write(tmp_path, "raft_tpu/a.py", self.LOWERED)
        findings, _ = _run(tmp_path, select=["GL001"])
        assert _codes(findings) == ["GL001"]
        assert "int()" in findings[0].message


class TestGL002Retrace:
    BUG_LAMBDA = (
        "import jax\n"
        "def serve(x):\n"
        "    return jax.jit(lambda q: q + 1)(x)\n")

    BUG_LOCAL = (
        "import jax\n"
        "def serve(x, mesh):\n"
        "    def local(q):\n"
        "        return q + 1\n"
        "    f = jax.jit(jax.shard_map(local, mesh=mesh))\n"
        "    return f(x)\n")

    BUG_CAPTURE = (
        "import jax\n"
        "import numpy as np\n"
        "def serve(x):\n"
        "    table = np.arange(128)\n"
        "    def build():\n"
        "        def local(q):\n"
        "            return q + table\n"
        "        return jax.jit(local)\n"
        "    return build()(x)\n")

    OK_MODULE = (
        "import jax\n"
        "g = jax.jit(lambda q: q + 1)\n"     # module scope: traced once
        "def serve(x):\n"
        "    return g(x)\n")

    OK_BUILDER = (
        "import jax\n"
        "def serve(x, cache):\n"
        "    def build():\n"
        "        def local(q):\n"
        "            return q + 1\n"
        "        return jax.jit(local)\n"
        "    f = cache.setdefault('k', build)\n"
        "    return f(x)\n")

    def test_flags_lambda_and_local_def(self, tmp_path):
        _write(tmp_path, "raft_tpu/a.py", self.BUG_LAMBDA)
        _write(tmp_path, "raft_tpu/b.py", self.BUG_LOCAL)
        findings, _ = _run(tmp_path, select=["GL002"])
        assert len(findings) == 2
        assert "lambda" in findings[0].message
        assert "local" in findings[1].message

    def test_flags_ndarray_capture_even_in_builder(self, tmp_path):
        _write(tmp_path, "raft_tpu/a.py", self.BUG_CAPTURE)
        findings, _ = _run(tmp_path, select=["GL002"])
        assert any("table" in f.message for f in findings)

    def test_module_scope_and_builder_idiom_stay_silent(self, tmp_path):
        _write(tmp_path, "raft_tpu/a.py", self.OK_MODULE)
        _write(tmp_path, "raft_tpu/b.py", self.OK_BUILDER)
        findings, _ = _run(tmp_path, select=["GL002"])
        assert findings == []


class TestGL003Locks:
    BUG = (
        "import threading\n"
        "class S:\n"
        "    GUARDED_BY = ('_q',)\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._q = []\n"
        "    def bad(self):\n"
        "        self._q.append(1)\n"
        "        self._pop_locked()\n"
        "    def _pop_locked(self):\n"
        "        return self._q.pop()\n")

    OK = (
        "import threading\n"
        "class S:\n"
        "    GUARDED_BY = ('_q',)\n"
        "    def __init__(self):\n"
        "        self._cond = threading.Condition()\n"
        "        self._q = []\n"
        "    def good(self):\n"
        "        with self._cond:\n"
        "            self._q.append(1)\n"
        "            self._pop_locked()\n"
        "    def _pop_locked(self):\n"
        "        return self._q.pop()\n")

    NESTED_DEF = (
        "import threading\n"
        "class S:\n"
        "    GUARDED_BY = ('_n',)\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._n = 0\n"
        "    def spawn(self):\n"
        "        with self._lock:\n"
        "            def cb():\n"
        "                self._n += 1\n"   # runs later, lock NOT held
        "            return cb\n")

    def test_flags_unlocked_guarded_access_and_locked_call(self,
                                                           tmp_path):
        _write(tmp_path, "raft_tpu/serve/a.py", self.BUG)
        findings, _ = _run(tmp_path, select=["GL003"])
        msgs = " | ".join(f.message for f in findings)
        assert "self._q" in msgs and "_pop_locked()" in msgs

    def test_locked_regions_and_locked_methods_silent(self, tmp_path):
        _write(tmp_path, "raft_tpu/serve/a.py", self.OK)
        findings, _ = _run(tmp_path, select=["GL003"])
        assert findings == []

    def test_nested_def_does_not_inherit_lock(self, tmp_path):
        _write(tmp_path, "raft_tpu/serve/a.py", self.NESTED_DEF)
        findings, _ = _run(tmp_path, select=["GL003"])
        assert _codes(findings) == ["GL003"]

    def test_out_of_scope_tree_not_checked(self, tmp_path):
        # GL003 is scoped to serve/ + comms/
        _write(tmp_path, "raft_tpu/cluster/a.py", self.BUG)
        findings, _ = _run(tmp_path, select=["GL003"])
        assert findings == []


class TestGL004Precision:
    BUG = (
        "import jax.numpy as jnp\n"
        "def score(q, d):\n"
        "    return jnp.einsum('qd,ld->ql', q, d)\n")

    OK = (
        "import jax.numpy as jnp\n"
        "from jax import lax\n"
        "from raft_tpu.core.precision import matmul_precision\n"
        "def score(q, d):\n"
        "    a = jnp.einsum('qd,ld->ql', q, d,\n"
        "                   precision=matmul_precision())\n"
        "    b = lax.dot_general(q, d, (((1,), (1,)), ((), ())),\n"
        "                        precision=lax.Precision.DEFAULT)\n"
        "    return a + b\n")

    def test_flags_missing_precision(self, tmp_path):
        _write(tmp_path, "raft_tpu/distance/a.py", self.BUG)
        findings, _ = _run(tmp_path, select=["GL004"])
        assert _codes(findings) == ["GL004"]

    def test_explicit_precision_silent(self, tmp_path):
        _write(tmp_path, "raft_tpu/neighbors/a.py", self.OK)
        findings, _ = _run(tmp_path, select=["GL004"])
        assert findings == []


class TestGL005Clock:
    BUG = ("import time\n"
           "def poison():\n"
           "    return time.time()\n")
    OK = ("import time\n"
          "def poison():\n"
          "    return time.monotonic() + time.perf_counter()\n")

    def test_flags_wall_clock(self, tmp_path):
        _write(tmp_path, "raft_tpu/a.py", self.BUG)
        findings, _ = _run(tmp_path, select=["GL005"])
        assert _codes(findings) == ["GL005"]

    def test_monotonic_silent(self, tmp_path):
        _write(tmp_path, "raft_tpu/a.py", self.OK)
        findings, _ = _run(tmp_path, select=["GL005"])
        assert findings == []


class TestGL006Swallow:
    SILENT = "try:\n    x()\nexcept Exception:\n    pass\n"
    BARE = "try:\n    x()\nexcept:\n    cleanup()\n"
    COUNTED = ("try:\n    x()\nexcept:\n    obs." +
               'counter("raft.serve.dispatcher.errors").inc()\n')
    RERAISED = ("try:\n    x()\nexcept Exception:\n"
                "    log.error('x failed')\n    raise\n")
    HANDLED = ("try:\n    x()\nexcept ValueError as e:\n"
               "    y = fallback(e)\n")

    def test_flags_silent_pass_and_bare_except(self, tmp_path):
        _write(tmp_path, "raft_tpu/serve/x.py", self.SILENT + self.BARE)
        findings, _ = _run(tmp_path, select=["GL006"])
        assert _codes(findings) == ["GL006", "GL006"]

    def test_counted_reraised_and_typed_handlers_silent(self, tmp_path):
        _write(tmp_path, "raft_tpu/mutate/x.py",
               self.COUNTED + self.RERAISED + self.HANDLED)
        findings, _ = _run(tmp_path, select=["GL006"])
        assert findings == []

    def test_out_of_scope_tree_not_checked(self, tmp_path):
        # ops/ has legitimate best-effort handlers; the rule's contract
        # covers the failure-handling trees only
        _write(tmp_path, "raft_tpu/ops/x.py", self.SILENT)
        findings, _ = _run(tmp_path, select=["GL006"])
        assert findings == []

    def test_failure_handling_trees_carry_zero_gl006(self):
        """ISSUE 12 satellite acceptance: the GL006 baseline is
        DRAINED — serve/, mutate/ AND comms/ are clean outright
        (modulo justified suppression pragmas); the former
        grandfathered comms sites were fixed (health.py's dropped
        beat now counts under raft.comms.health.errors) or justified
        (launcher env sniffing, health key retirement)."""
        findings, _ = engine.run(
            REPO, files=[os.path.join(REPO, "raft_tpu", "serve"),
                         os.path.join(REPO, "raft_tpu", "mutate"),
                         os.path.join(REPO, "raft_tpu", "comms")],
            select=["GL006"])
        assert findings == []

    def test_baseline_is_empty(self):
        """ISSUE 12 satellite acceptance: tools/graftlint_baseline.json
        carries ZERO findings — and stays that way (new findings are
        fixed or justified, never grandfathered)."""
        with open(os.path.join(REPO, engine.DEFAULT_BASELINE)) as f:
            obj = json.load(f)
        assert obj["findings"] == []
        assert engine.load_baseline(
            os.path.join(REPO, engine.DEFAULT_BASELINE)) == {}


class TestGL010GL011Metrics:
    # assembled so this file's own literals don't trip the tree scan
    _C = "obs." + "{fn}({q}{name}{q})"

    @classmethod
    def _call(cls, fn, name):
        return cls._C.format(fn=fn, name=name, q='"')

    def test_taxonomy_and_kind_conflict(self, tmp_path):
        _write(tmp_path, "raft_tpu/a.py",
               self._call("counter", "cuml.wrong.prefix") + ".inc()\n" +
               self._call("counter", "raft.dup.name") + ".inc()\n" +
               self._call("gauge", "raft.dup.name") + ".set(1)\n")
        findings, _ = _run(tmp_path, select=["GL010", "GL011"])
        assert _codes(findings) == ["GL010", "GL011"]
        assert "taxonomy" in findings[0].message
        assert "already a counter" in findings[1].message

    def test_timed_conflict_across_files(self, tmp_path):
        _write(tmp_path, "raft_tpu/a.py",
               "with " + self._call("timed", "raft.x.y") +
               ":\n    pass\n")
        _write(tmp_path, "raft_tpu/b.py",
               self._call("counter", "raft.x.y.seconds") + ".inc()\n")
        findings, _ = _run(tmp_path, select=["GL011"])
        assert len(findings) == 1
        assert "raft.x.y.seconds" in findings[0].message
        # the conflict names the FIRST site
        assert "raft_tpu/a.py:1" in findings[0].message


class TestSuppression:
    def test_pragma_silences_named_rule_only(self, tmp_path):
        _write(tmp_path, "raft_tpu/a.py",
               "import time\n"
               "a = time.time()  # graftlint: disable=GL005\n"
               "b = time.time()  # graftlint: disable=GL001\n"
               "c = time.time()  # graftlint: disable=all\n")
        findings, suppressed = _run(tmp_path, select=["GL005"])
        assert [f.line for f in findings] == [3]
        assert sorted(f.line for f in suppressed) == [2, 4]


class TestBaseline:
    def test_round_trip_strict_on_new_code(self, tmp_path):
        src = ("import time\n"
               "t0 = time.time()\n")
        _write(tmp_path, "raft_tpu/a.py", src)
        findings, _ = _run(tmp_path, select=["GL005"])
        assert len(findings) == 1
        bl = tmp_path / "baseline.json"
        engine.write_baseline(str(bl), findings)
        allow = engine.load_baseline(str(bl))
        new, old = engine.split_new(findings, allow)
        assert new == [] and len(old) == 1
        # line drift does NOT un-grandfather (match is on content)...
        _write(tmp_path, "raft_tpu/a.py", "import time\n\n\n" + src[12:])
        findings2, _ = _run(tmp_path, select=["GL005"])
        new, old = engine.split_new(findings2, allow)
        assert new == [] and len(old) == 1
        # ...but a NEW instance of the pattern is strict
        _write(tmp_path, "raft_tpu/a.py",
               src + "t1 = time.time()\n")
        findings3, _ = _run(tmp_path, select=["GL005"])
        new, old = engine.split_new(findings3, allow)
        assert len(new) == 1 and len(old) == 1

    def test_baseline_file_shape(self, tmp_path):
        _write(tmp_path, "raft_tpu/a.py",
               "import time\nt = time.time()\n")
        findings, _ = _run(tmp_path, select=["GL005"])
        bl = tmp_path / "b.json"
        obj = engine.write_baseline(str(bl), findings)
        assert obj["version"] == engine.BASELINE_VERSION
        e = obj["findings"][0]
        assert set(e) == {"rule", "file", "context", "count"}
        assert e["rule"] == "GL005"
        assert e["file"] == "raft_tpu/a.py"


class TestJsonOutput:
    def test_schema(self, tmp_path):
        _write(tmp_path, "raft_tpu/a.py",
               "import time\nt = time.time()\n")
        timings = {}
        findings, suppressed = engine.run(str(tmp_path),
                                          select=["GL005"],
                                          timings=timings)
        obj = engine.to_json(findings, [], suppressed, timings)
        assert obj["version"] == engine.JSON_VERSION
        assert set(obj) == {"version", "findings", "counts",
                            "grandfathered", "suppressed",
                            "timings_ms"}
        f = obj["findings"][0]
        assert set(f) == {"rule", "file", "line", "col", "message",
                          "context"}
        assert obj["counts"] == {"GL005": 1}
        # per-rule wall time is attributable (ISSUE 12 satellite)
        assert obj["timings_ms"].get("GL005", -1) >= 0
        # round-trips through json
        assert json.loads(json.dumps(obj)) == obj


class TestCLI:
    def _cli(self, *args):
        return subprocess.run(
            [sys.executable, "-m", "tools.graftlint", *args],
            cwd=REPO, capture_output=True, text=True)

    def test_tree_is_clean_under_checked_in_baseline(self):
        """The tier-1 wrapper for the precommit gate: the real tree
        exits 0 (acceptance: `python -m tools.graftlint` exits 0)."""
        r = self._cli()
        assert r.returncode == 0, r.stdout + r.stderr

    def test_list_rules(self):
        r = self._cli("--list-rules")
        assert r.returncode == 0
        for code in ("GL001", "GL002", "GL003", "GL004", "GL005",
                     "GL006", "GL007", "GL008", "GL009", "GL010",
                     "GL011", "GL012", "GL013", "GL014"):
            assert code in r.stdout

    def test_seeded_bug_fails_the_gate(self, tmp_path):
        """Acceptance: seeding a known bug makes the gate fail — a
        GL005 wall-clock call in a fresh file is a NEW finding even
        with the checked-in baseline."""
        p = tmp_path / "seeded.py"
        p.write_text("import time\nt = time.time() - 5\n")
        r = self._cli(str(p))
        assert r.returncode == 1
        assert "GL005" in r.stdout

    def test_json_flag(self, tmp_path):
        p = tmp_path / "seeded.py"
        p.write_text("import time\nt = time.time()\n")
        r = self._cli(str(p), "--json", "--no-baseline")
        assert r.returncode == 1
        obj = json.loads(r.stdout)
        assert obj["counts"] == {"GL005": 1}

    def test_unknown_rule_is_usage_error(self):
        r = self._cli("--select", "GL999")
        assert r.returncode == 2


class TestBaselineContract:
    def test_no_grandfathered_findings_in_serve(self):
        """Acceptance: the new serving layer carries NO baseline
        entries — its findings were fixed, not grandfathered."""
        allow = engine.load_baseline(
            os.path.join(REPO, engine.DEFAULT_BASELINE))
        assert not [k for k in allow
                    if k[1].startswith("raft_tpu/serve/")]

    def test_real_serve_tree_clean_without_baseline(self):
        findings, _ = engine.run(
            REPO, files=[os.path.join(REPO, "raft_tpu", "serve")])
        assert findings == []

    def test_dist_serving_tier_carries_zero_baseline(self):
        """ISSUE 8 acceptance: the new distributed serving tier
        (serve/dist.py + serve/merge.py) ships GL002/GL003-clean with
        an EMPTY baseline — no grandfathered findings, and a fresh
        lint of just those files agrees."""
        allow = engine.load_baseline(
            os.path.join(REPO, engine.DEFAULT_BASELINE))
        assert not [k for k in allow
                    if k[1] in ("raft_tpu/serve/dist.py",
                                "raft_tpu/serve/merge.py")]
        findings, _ = engine.run(
            REPO, files=[
                os.path.join(REPO, "raft_tpu", "serve", "dist.py"),
                os.path.join(REPO, "raft_tpu", "serve", "merge.py")])
        assert findings == []

    def test_mutate_carries_zero_baseline_and_zero_gl003(self):
        """ISSUE 9 acceptance: the new mutable-index subsystem
        (raft_tpu/mutate/) ships with an EMPTY baseline — no
        grandfathered findings — and a fresh GL003 lint of the tree
        finds nothing live: the dispatcher/compactor boundary's
        GUARDED_BY discipline holds statically."""
        allow = engine.load_baseline(
            os.path.join(REPO, engine.DEFAULT_BASELINE))
        assert not [k for k in allow
                    if k[1].startswith("raft_tpu/mutate/")]
        findings, _ = engine.run(
            REPO, files=[os.path.join(REPO, "raft_tpu", "mutate")],
            select=["GL003"])
        assert findings == []
        # the whole tree (all rules) is clean too, modulo justified
        # suppressions
        findings, _ = engine.run(
            REPO, files=[os.path.join(REPO, "raft_tpu", "mutate")])
        assert findings == []

    def test_gl003_scope_covers_mutate(self):
        """The GL003 path scope gained mutate/: a seeded unlocked
        GUARDED_BY write there is a live finding."""
        from tools.graftlint.rules.locks import LockDiscipline
        assert "raft_tpu/mutate" in LockDiscipline.paths

    def test_gl003_scope_covers_post_pr6_threaded_modules(self):
        """ISSUE 12 satellite: the modules that grew locks/threads
        after PR 6 fixed the scoping are now inside it — and the
        shadow/SLO classes declare their contracts."""
        from tools.graftlint.rules.locks import LockDiscipline
        for p in ("raft_tpu/obs/quality.py", "raft_tpu/obs/slo.py",
                  "raft_tpu/testing/faults.py"):
            assert p in LockDiscipline.paths
        from raft_tpu.obs.quality import QualityMonitor
        from raft_tpu.obs.slo import SLOTracker
        assert set(QualityMonitor.GUARDED_BY) >= {
            "_pending", "_windows", "_epoch", "_closed"}
        assert set(SLOTracker.GUARDED_BY) >= {"_ring", "_report"}

    def test_gl003_live_in_quality_scope(self, tmp_path):
        """A seeded unlocked GUARDED_BY write in the newly-scoped
        quality module is a live finding; the same bug in an
        unscoped obs module stays out of contract."""
        bug = ("import threading\n"
               "class M:\n"
               "    GUARDED_BY = ('_pending',)\n"
               "    def __init__(self):\n"
               "        self._cond = threading.Condition()\n"
               "        self._pending = []\n"
               "    def bad(self):\n"
               "        self._pending.append(1)\n")
        _write(tmp_path, "raft_tpu/obs/quality.py", bug)
        findings, _ = _run(tmp_path, select=["GL003"])
        assert _codes(findings) == ["GL003"]
        _write(tmp_path, "raft_tpu/obs/quality.py", "x = 1\n")
        _write(tmp_path, "raft_tpu/obs/registry.py", bug)
        findings, _ = _run(tmp_path, select=["GL003"])
        assert findings == []

    def test_no_grandfathered_findings_in_parallel(self):
        """ISSUE 7 satellite: the per-build shard_map sites in
        parallel/ now ride the keyed _shmap_plan cache — their GL002
        grandfather entries were DELETED, not carried. A new retrace
        hazard in parallel/ fails the lint outright."""
        allow = engine.load_baseline(
            os.path.join(REPO, engine.DEFAULT_BASELINE))
        assert not [k for k in allow
                    if k[1].startswith("raft_tpu/parallel/")]

    def test_real_parallel_tree_has_no_gl002(self):
        findings, _ = engine.run(
            REPO, files=[os.path.join(REPO, "raft_tpu", "parallel")])
        assert [f for f in findings if f.rule == "GL002"] == []


class TestLockOrderContract:
    """ISSUE 12 tentpole acceptance (the full interprocedural fixture
    suite lives in tests/test_graftlint_concurrency.py)."""

    def test_lock_order_graph_is_acyclic(self):
        from tools.graftlint import callgraph
        program = callgraph.get_program({}, REPO)
        assert program.lock_cycles() == [], \
            "lock-order cycle in the real tree — potential deadlock"

    def test_gl007_gl008_gl009_live_clean_with_empty_baseline(self):
        findings, _ = engine.run(
            REPO, select=["GL007", "GL008", "GL009"])
        assert findings == []
        allow = engine.load_baseline(
            os.path.join(REPO, engine.DEFAULT_BASELINE))
        assert not [k for k in allow
                    if k[0] in ("GL007", "GL008", "GL009")]


class TestShimDelegation:
    def test_check_metric_names_uses_registry_scanner(self, tmp_path):
        """check_metric_names.lint_source delegates to the graftlint
        metrics rule — same events, legacy message format."""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "check_metric_names",
            os.path.join(REPO, "tools", "check_metric_names.py"))
        shim = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(shim)
        from tools.graftlint.rules import metrics
        assert shim.CALL_RE is metrics.CALL_RE
        assert shim.NAME_RE is metrics.NAME_RE
        p = tmp_path / "x.py"
        p.write_text("obs." + 'counter("bad.prefix").inc()\n')
        out = shim.lint_source([str(p)])
        assert len(out) == 1 and "taxonomy" in out[0]


class TestRealTreeRegressions:
    """Pin the real findings this PR fixed so they cannot come back
    silently (the satellites of ISSUE 6)."""

    def test_batcher_declares_guarded_fields(self):
        from raft_tpu.serve.batcher import SearchServer
        assert set(SearchServer.GUARDED_BY) >= {
            "_q", "_rows_queued", "_closed", "_shed_times"}

    def test_dist_dispatcher_declares_guarded_fields(self):
        """ISSUE 8 satellite: the distributed dispatcher redeclares the
        GL003 contract (the rule is per-class — an inherited tuple
        would not be seen statically)."""
        import ast
        from raft_tpu.serve.dist import DistributedSearchServer
        assert set(DistributedSearchServer.GUARDED_BY) >= {
            "_q", "_rows_queued", "_closed", "_shed_times"}
        # and the declaration is a LITERAL on the class body, where
        # the static rule reads it
        tree = ast.parse(open(os.path.join(
            REPO, "raft_tpu", "serve", "dist.py")).read())
        cls = next(n for n in ast.walk(tree)
                   if isinstance(n, ast.ClassDef)
                   and n.name == "DistributedSearchServer")
        decls = [s for s in cls.body if isinstance(s, ast.Assign)
                 and any(isinstance(t, ast.Name)
                         and t.id == "GUARDED_BY"
                         for t in s.targets)]
        assert decls, "DistributedSearchServer must declare " \
                      "GUARDED_BY literally"

    def test_controller_documents_single_writer(self):
        from raft_tpu.serve.controller import LoadController
        assert LoadController.GUARDED_BY == ()

    def test_linalg_dot_threads_precision(self):
        findings, _ = engine.run(
            REPO, files=[os.path.join(REPO, "raft_tpu", "linalg"),
                         os.path.join(REPO, "raft_tpu", "distance")],
            select=["GL004"])
        assert findings == []
