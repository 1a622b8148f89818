"""chip_smoke.py's phases at a tiny size on the CPU test mesh (Pallas in
interpret mode): the same code paths the chip run drives, with their
gates, plus the refusal to run without a TPU."""

import json

import numpy as np
import pytest

import chip_smoke as cs

K_TINY = cs.K_IVF
SERVE_KW = dict(batch_sizes=(1, 8, 16), n_requests=24, n_clients=3,
                max_nq=4)


@pytest.fixture(scope="module")
def clock():
    return cs.CompileClock()


@pytest.fixture(scope="module")
def data():
    x, q = cs.make_data(3000, 32, 48, seed=0, n_centers=24)
    return x, q


@pytest.fixture(scope="module")
def exact_ids(data, clock):
    x, q = data
    return cs.phase_exact(x, q, clock, k=K_TINY)


def test_main_refuses_without_tpu(capsys):
    assert cs.main([]) != 0
    captured = capsys.readouterr()
    assert "no TPU found" in captured.err
    assert '"ok"' not in captured.out


def test_exact_is_ground_truth(data, exact_ids):
    x, q = data
    xn, qn = np.asarray(x), np.asarray(q)
    d = ((qn[:, None, :] - xn[None, :, :]) ** 2).sum(-1)
    want = np.argsort(d, axis=1)[:, :K_TINY]
    assert cs.recall(exact_ids, want, K_TINY) >= 0.99


def test_fused_routes_pallas(data, exact_ids, clock, monkeypatch):
    # tiny tiles put the k neighbors into few bins: the chip-scale gate
    # is not meaningful here, the routing check is
    monkeypatch.setitem(cs.GATES, "fused_recall", 0.5)
    x, q = data
    r = cs.phase_fused(x, q, exact_ids, clock, k=K_TINY, compiled=False)
    assert 0.5 <= r <= 1.0
    with pytest.raises(cs.SmokeError, match="Mosaic"):
        cs.phase_fused(x, q, exact_ids, clock, k=K_TINY, compiled=True)


def test_ivf_flat_served(data, exact_ids, clock, capsys):
    x, q = data
    cs.phase_ivf_flat(x, q, exact_ids, clock, seed=0, n_lists=16,
                      n_probes=8, **SERVE_KW)
    rows = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    row = next(r for r in rows if r.get("phase") == "ivf_flat")
    assert row["compiles_in_window"] == 0 and row["plan_bit_equal"]
    assert row["recall"] >= cs.GATES["ivf_flat_recall"]


def test_ivf_pq_served(data, exact_ids, clock, capsys):
    x, q = data
    cs.phase_ivf_pq(x, q, exact_ids, clock, seed=0, n_lists=16,
                    n_probes=8, pq_dim=16, **SERVE_KW)
    phases = {r["phase"] for r in map(
        json.loads, capsys.readouterr().out.splitlines())}
    assert {"ivf_pq", "ivf_pq.tier", "ivf_pq.estimator"} <= phases


def test_ivf_bq(data, exact_ids, clock):
    x, q = data
    cs.phase_ivf_bq(x, q, exact_ids, clock, n_lists=16, n_probes=8)


def test_gate_failure_raises(data, exact_ids, clock, monkeypatch):
    monkeypatch.setitem(cs.GATES, "ivf_bq_recall", 1.01)
    x, q = data
    with pytest.raises(cs.SmokeError, match="ivf_bq"):
        cs.phase_ivf_bq(x, q, exact_ids, clock, n_lists=16, n_probes=8)


def test_four_chip_path_on_virtual_mesh(clock, monkeypatch, capsys):
    """The --chips 4 path on 4 virtual CPU devices: mesh recall vs exact
    distributed_knn, ids equal to the one-device search, lists spread
    over the 4 devices."""
    monkeypatch.setattr(cs, "N4", 4000)
    monkeypatch.setattr(cs, "N_LISTS4", 32)
    monkeypatch.setattr(cs, "PROBES4", 8)
    monkeypatch.setattr(cs, "NQ", 48)
    monkeypatch.setattr(cs, "DIM", 32)
    monkeypatch.setattr(cs, "N_CENTERS", 32)
    monkeypatch.setattr(cs, "BATCH_SIZES", (1, 8, 16))
    monkeypatch.setattr(cs, "N_REQUESTS", 24)
    monkeypatch.setattr(cs, "N_CLIENTS", 3)
    monkeypatch.setattr(cs, "MAX_REQUEST_NQ", 4)
    cs.run_four_chips(0, clock)
    rows = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    serve_row = next(r for r in rows if r.get("phase") == "dist_serve")
    assert serve_row["ids_equal_one_device_frac"] == 1.0
    build_row = next(r for r in rows if r.get("phase") == "sharded_build")
    assert len(set(build_row["shard_devices"])) == 4
