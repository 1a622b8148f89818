"""The comparison that decides ``correct`` fails what it must, at the
rehearsal size on the CPU: the control in the program's place, and the
timed path broken underneath the harness in each way a one-chip search
cell can be broken: an answer altered where it is produced, and half of
a batch left out. A search keeps no state that a step could leave
unchanged, and no cell spans chips, so those faults have no case here.

``ivf_pq.2m``'s control is exact search in bfloat16 in the program's
place. ``ivf_flat.2m``'s is the program's own single-pass bfloat16
path (``RAFT_TPU_*_PRECISION=default``), which the CPU cannot show:
XLA:CPU and the Pallas interpreter compute f32 whatever the setting
(``raft_tpu/core/precision.py``). Its case here puts the bfloat16
reference in the program's place instead; the chip reads both
(PERF.md section 2)."""

import json

import numpy as np
import pytest

import manifest
import run
from raft_tpu.serve import batcher

SEED = str(2 ** 31 + 23)


def compared(cell, capsys, *extra) -> dict:
    assert run.main(["--workload", cell, "--seed", SEED, "--seconds", "1",
                     "--rehearsal", *extra]) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return res


BF16_REFERENCE = {"kind": "reference_bf16"}


@pytest.mark.parametrize("cell", ("flat2m.bulk", "pq2m.bulk"))
def test_control_is_not_correct(cell, capsys, monkeypatch):
    real = manifest.config

    def config(man, name, root=manifest.ROOT):
        cfg = real(man, name, root)
        if cfg["control"]["kind"] == "program_lowp":
            cfg["control"] = BF16_REFERENCE     # see the module docstring
        return cfg

    monkeypatch.setattr(manifest, "config", config)
    res = compared(cell, capsys, "--control")
    assert res["correct"] is False
    err = res["compared"]["dist_err"]
    assert err["value"] > 3 * err["limit"]


def _altered(orig):
    def dispatch(self, plan, qb):
        d, i = orig(self, plan, qb)
        return d, np.roll(np.asarray(i), 1, axis=0)
    return dispatch


def _half_left_out(orig):
    def dispatch(self, plan, qb):
        d, i = orig(self, plan, qb)
        d, i = np.array(d), np.array(i)
        h = len(qb) // 2
        d[h:], i[h:] = d[:len(qb) - h], i[:len(qb) - h]
        return d, i
    return dispatch


@pytest.mark.parametrize("fault", (_altered, _half_left_out),
                         ids=("answer_altered", "half_batch_left_out"))
def test_broken_one_chip_path_is_not_correct(fault, capsys, monkeypatch):
    monkeypatch.setattr(batcher.SearchServer, "_dispatch",
                        fault(batcher.SearchServer._dispatch))
    res = compared("flat2m.bulk", capsys)
    assert res["correct"] is False
    assert res["compared"]["recall_miss"]["value"] > \
        res["compared"]["recall_miss"]["limit"]
