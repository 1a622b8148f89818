"""``bench/run.py`` end to end on the CPU at each configuration's
rehearsal size: the last line has the five keys and no metric, the
program comes out correct, and with no TPU and no rehearsal flag the
run refuses."""

import json

import pytest

import run

CELLS = ("flat2m.bulk", "pq2m.bulk", "flat2m.online")


def result(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_is_correct_and_writes_no_metric(cell, capsys):
    assert run.main(["--workload", cell, "--seed", str(2 ** 31 + 11),
                     "--seconds", "1", "--rehearsal"]) == 0
    res = result(capsys)
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(res)
    assert res["rehearsal"] is True and res["metrics"] == {}
    assert res["correct"] is True and res["attempted"] > 0
    assert res["failed"] == 0
    assert list(res)[-1] == "compared"
    assert set(res["device"]) >= {"platform", "kind", "count"}


def test_refuses_without_a_tpu(capsys):
    assert run.main(["--workload", "flat2m.bulk", "--seed", "1",
                     "--seconds", "1"]) == 2
    assert capsys.readouterr().out == ""
