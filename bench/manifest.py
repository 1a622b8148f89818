"""``BENCHMARK.json`` and the files it names, each found by its name:

* a configuration: the file its entry names (``bench/configs/``), whose
  ``family`` names ``bench/families/<family>.py``;
* a traffic mix: ``bench/traffic/<traffic>.json``;
* a per-layer metric: its reader, ``bench/metrics/<name>.py``.

A later PR adds a cell, a mix or a metric by adding such files and
entries, and edits none of these.
"""

from __future__ import annotations

import copy
import importlib.util
import json
import os
import re
from typing import List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def workload(man: dict, name: str) -> dict:
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(man: dict, name: str, root: str = ROOT) -> dict:
    for c in man["configs"]:
        if c["name"] == name:
            return _json(os.path.join(root, c["file"]))
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    return _json(os.path.join(HERE, "traffic", name + ".json"))


def _module(kind: str, name: str):
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise KeyError(f"no {kind} module {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(name: str):
    """``bench/families/<name>.py``: ``build(cfg, x, warm, pool, mesh)
    -> System`` (``warm``: the ladder's warm-up queries)."""
    return _module("families", name)


def reader(name: str):
    """``bench/metrics/<name>.py``: ``read(ctx) -> float | None``."""
    return _module("metrics", name)


def metrics_for(man: dict, section: str, cell: str) -> List[dict]:
    """The entries of ``section`` (``end_to_end`` or ``per_layer``) that
    the cell reports: those with no ``workloads`` key, and those that
    list it."""
    return [m for m in man[section]
            if "workloads" not in m or cell in m["workloads"]]


def merged(base: dict, over: dict) -> dict:
    """``base`` with each group of ``over`` laid over the same group."""
    out = copy.deepcopy(base)
    for group, values in over.items():
        if isinstance(values, dict):
            out.setdefault(group, {}).update(values)
        else:
            out[group] = values
    return out


def problems(man: dict, root: str = ROOT) -> List[str]:
    """What in the manifest does not resolve or break its naming rules."""
    out = []
    e2e = {m["name"]: m for m in man["end_to_end"]}
    cells = {w["name"]: w for w in man["workloads"]}
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in man[kind]:
            if not NAME_RE.match(entry["name"]):
                out.append(f"{kind}: bad name {entry['name']!r}")
            if "unit" in entry and not UNIT_RE.match(entry["unit"]):
                out.append(f"{entry['name']}: bad unit {entry['unit']!r}")
    for w in man["workloads"]:
        for key in ("config", "traffic"):
            if not NAME_RE.match(w[key]):
                out.append(f"{w['name']}: bad {key} name {w[key]!r}")
        try:
            cfg = config(man, w["config"], root)
            family(cfg["family"])
            traffic(w["traffic"])
        except (KeyError, OSError) as e:
            out.append(f"{w['name']}: {e}")
            continue
        if cfg["chips"] != w["chips"]:
            out.append(f"{w['name']}: chips {w['chips']} != config's "
                       f"{cfg['chips']}")
        reported = {m["name"] for m in metrics_for(man, "end_to_end",
                                                   w["name"])}
        if "setup_s" not in reported or len(reported) < 2:
            out.append(f"{w['name']}: reports {sorted(reported)}")
        if not metrics_for(man, "per_layer", w["name"]):
            out.append(f"{w['name']}: reports no per-layer metric")
    for m in man["per_layer"]:
        try:
            reader(m["name"])
        except (KeyError, OSError) as e:
            out.append(str(e))
        if m["moves"] not in e2e:
            out.append(f"{m['name']}: moves unknown {m['moves']!r}")
            continue
        for cell in m.get("workloads", list(cells)):
            if cell not in cells:
                out.append(f"{m['name']}: unknown cell {cell!r}")
            elif m["moves"] not in {x["name"] for x in metrics_for(
                    man, "end_to_end", cell)}:
                out.append(f"{m['name']}: {cell} does not report "
                           f"{m['moves']}")
    return out
