"""What one cell of ``BENCHMARK.json`` names, loaded from its files.

A cell is a configuration (``configs/<config>.json``) under a traffic mix
(``traffic/<traffic>.json``).  Both are found by the names the cell gives;
nothing here knows any particular configuration or mix, so a new cell is
new files and a new entry, never an edit.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def workload(name: str) -> dict:
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def load_config(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def load_traffic(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file: the
    registered architecture with every number of the file's ``model``
    block laid over it.  A key the program does not know is an error."""
    from repro.configs import get_config
    base = get_config(conf["program_config"])
    known = {f.name for f in dataclasses.fields(base)}
    unknown = set(conf["model"]) - known
    if unknown:
        raise KeyError(f"{conf['name']}: unknown model keys {sorted(unknown)}")
    return dataclasses.replace(base, name=conf["name"], **conf["model"])
