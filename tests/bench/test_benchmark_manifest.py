"""BENCHMARK.json: every cell's configuration, traffic and metric
resolves by name, names and units keep to their characters, and every
per-layer metric's cells report the end-to-end metric it moves."""

import json
import os

import pytest

import manifest

MAN = manifest.load()
CONFIG_KEYS = ("source", "source_case", "from_source", "deployment", "chips",
               "family", "corpus", "index", "search", "precision", "serve",
               "reduced", "assumed", "limits", "control", "rehearsal")


def test_every_name_resolves():
    assert manifest.problems(MAN) == []


def test_top_level_keys_and_command():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["command"] == ["python3", "bench/run.py"]
    assert MAN["paths"] == ["bench", "tests/bench"]
    assert 1 <= MAN["run_seconds"] <= 51


@pytest.mark.parametrize("entry", MAN["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_contract(entry):
    cfg = manifest.config(MAN, entry["name"])
    assert all(k in cfg for k in CONFIG_KEYS)
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    assert "knn.cuh:380-389" in cfg["source_case"]
    # the fixed serving contract: no degradation, no deadlines
    assert cfg["serve"]["probes_ladder"] == []
    assert cfg["serve"]["default_deadline_ms"] == 0.0
    assert cfg["serve"]["max_wait_ms"] == 2.0
    assert cfg["control"]["kind"] in ("program_lowp", "reference_bf16")
    assert {"dist_err", "recall_miss", "bad_ids", "lost"} == set(
        cfg["limits"])


@pytest.mark.parametrize("cell", MAN["workloads"], ids=lambda w: w["name"])
def test_cell_reports_setup_another_end_to_end_and_a_layer(cell):
    e2e = {m["name"] for m in manifest.metrics_for(MAN, "end_to_end",
                                                   cell["name"])}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert manifest.metrics_for(MAN, "per_layer", cell["name"])
    assert cell["chips"] in (1, 4)


def test_bounds_and_sources():
    for m in MAN["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in MAN["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if "roofline" in m["name"]:
            assert m["name"].endswith("_roofline") and m["unit"] == "%"


def test_names_and_units_refuse_what_the_contract_refuses():
    for bad in ("a b", "x,y", "a/b", "", "-lead", "é"):
        assert not manifest.NAME_RE.match(bad)
    assert manifest.NAME_RE.match("flat10m.mesh4.bulk")
    assert manifest.UNIT_RE.match("queries/s")
    assert not manifest.UNIT_RE.match("queries per s")


def test_traffic_files_are_data():
    tdir = os.path.join(manifest.HERE, "traffic")
    for name in os.listdir(tdir):
        assert name.endswith(".json")
        with open(os.path.join(tdir, name)) as f:
            assert json.load(f)["kind"] in ("closed", "open_poisson")
