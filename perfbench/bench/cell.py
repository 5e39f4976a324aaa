"""A cell, found by name: its configuration file, traffic file and metrics.

``BENCHMARK.json`` at the root names the cells.  Everything that belongs to
one configuration, one traffic mix or one per-layer metric sits in a file
of its own under ``perfbench/``:

* ``configs/<config>.json``: the sizes as run, the program's registry
  name, and the file name of the plain reference beside it;
* ``traffic/<traffic>.json``: the traffic parameters, the path driver that
  serves them, and the limits of the ``correct`` comparison;
* ``metrics/<metric>.py``: a reader ``read(ctx) -> float | None`` of one
  per-layer metric.
"""
from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def config(name: str) -> dict:
    return load_json(os.path.join(HERE, "configs", f"{name}.json"))


def traffic(name: str) -> dict:
    return load_json(os.path.join(HERE, "traffic", f"{name}.json"))


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_module(cfg: dict):
    return load_module(os.path.join(HERE, "configs", cfg["reference"]),
                       "perfbench_reference")


def metric_reader(name: str):
    return load_module(os.path.join(HERE, "metrics", f"{name}.py"),
                       "perfbench_metric_" + name.replace(".", "_")).read


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def metrics_for(bench: dict, cell: str, group: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    out = []
    for m in bench[group]:
        cells = m.get("workloads")
        if cells is not None:
            if cell in cells:
                out.append(m)
        elif group == "end_to_end":
            out.append(m)
        elif any(e["name"] == m["moves"]
                 for e in metrics_for(bench, cell, "end_to_end")):
            out.append(m)
    return out

