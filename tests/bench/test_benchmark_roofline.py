"""The roofline arithmetic (bench/roofline.py) on a hand-worked batch."""

import numpy as np
import pytest

import roofline

PEAKS = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}


def test_flat_batch_work_by_hand():
    # lists of 3, 5, 0 and 7 real rows; two queries probe {0, 1} and
    # {1, 3}: 8 + 12 rows scored, lists {0, 1, 3} = 15 rows read once
    sizes = np.array([3, 5, 0, 7])
    probes = np.array([[0, 1], [1, 3]])
    layout = {"kind": "flat", "dim": 4, "n_probes": 2, "bytes_per_row": 16}
    ops, nbytes = roofline.batch_work(probes, sizes, layout)
    assert ops == 2 * 4 * (8 + 12)
    assert nbytes == 16 * 15 + 4 * 2 * 4


def test_pq_batch_work_by_hand():
    sizes = np.array([3, 5, 0, 7])
    probes = np.array([[0, 1], [1, 3]])
    layout = {"kind": "pq", "dim": 4, "n_probes": 2, "pq_dim": 2,
              "bytes_per_row": 2}
    ops, nbytes = roofline.batch_work(probes, sizes, layout)
    assert ops == 2 * (8 + 12)
    assert nbytes == 2 * 15 + 4 * 2 * 4


def test_share_names_the_bound():
    # 200 ops take 2 s at peak, 10 bytes 1 s: compute-bound, 2 s of 4
    got = roofline.share(200.0, 10.0, 4.0, PEAKS)
    assert got == {"percent": 50.0, "bound": "compute"}
    got = roofline.share(10.0, 30.0, 6.0, PEAKS)
    assert got == {"percent": 50.0, "bound": "memory"}


def test_coarse_replay_picks_nearest_centres():
    centers = np.array([[0.0, 0.0], [10.0, 0.0], [0.0, 10.0], [5, 5]])
    q = np.array([[9.0, 1.0], [1.0, 9.0]])
    got = roofline.probed_lists(q, centers, 2)
    assert set(got[0]) == {1, 3} and set(got[1]) == {2, 3}


def test_batches_group_whole_requests_in_order():
    qs = [np.zeros((n, 2)) for n in (3, 3, 3, 1, 4)]
    out = roofline.batches(qs, 6)
    assert [len(b) for b in out] == [6, 8]


def test_peaks_table_refuses_an_unknown_kind():
    assert roofline.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks_for("TPU v9 imaginary")
