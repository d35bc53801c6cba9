"""Finding a cell's pieces by name.

``BENCHMARK.json`` names every cell, configuration and metric.  The
pieces live in files of their own under the benchmark's directory, found
by those names, so a new cell, mix or metric is a new file and a new
entry, never an edit:

* ``configs/<config>.json`` (the path ``BENCHMARK.json`` gives): one
  deployment — data model, session settings, guarantees, reference;
* ``mixes/<traffic>.json``: one traffic mix, read by ``traffic.py``;
* ``metrics/<metric>.py``: one metric's reader, a module with
  ``read(ctx) -> float | None`` (None: nothing to read, the metric is
  left out of the line);
* ``references/<reference>.py``: a plain reference, named by a config.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os


@dataclasses.dataclass
class Cell:
    root: str                 # directory holding BENCHMARK.json
    bench: dict               # BENCHMARK.json
    workload: dict            # the cell's entry
    config: dict              # the configuration file
    mix: dict                 # the traffic mix file
    end_to_end: list          # metric entries this cell reports
    per_layer: list

    @property
    def base(self) -> str:
        """The benchmark's own directory (first of ``paths``)."""
        return os.path.join(self.root, self.bench["paths"][0])


def _json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(root: str, workload: str) -> Cell:
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(os.path.join(root, configs[w["config"]]["file"]))
    base = os.path.join(root, bench["paths"][0])
    mix = _json(os.path.join(base, "mixes", w["traffic"] + ".json"))
    return Cell(root=root, bench=bench, workload=w, config=config, mix=mix,
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, workload)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, workload)])


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(cell: Cell, name: str):
    """The ``read`` function of ``metrics/<name>.py``."""
    path = os.path.join(cell.base, "metrics", name + ".py")
    return _module(path, "bench_metric_" + name.replace(".", "_")).read


def reference(cell: Cell):
    """The ``Reference`` class the configuration names."""
    name = cell.config["reference"]
    path = os.path.join(cell.base, "references", name + ".py")
    return _module(path, "bench_reference_" + name)
