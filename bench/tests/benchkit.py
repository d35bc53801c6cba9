"""Helpers for the benchmark's CPU tests: a checkout-like root holding a
``BENCHMARK.json`` whose cells keep their names but run tiny
configurations, so the harness can be driven end to end without a
chip."""
from __future__ import annotations

import copy
import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

B1 = "dense-analytics"

TINY_DATA = {"n_nodes": 240, "m_attach": 3, "lam_extra": 1.0,
             "lam_remove": 1.0, "events_per_unit": 8, "n_seed": 4}


def load_json(rel: str) -> dict:
    with open(os.path.join(ROOT, rel)) as fh:
        return json.load(fh)


def tiny_configs() -> dict:
    """The configuration at a size a test can hold: same layout and
    policy, a small graph."""
    dense = load_json("bench/configs/table3-dense-n5063.json")
    dense["data"] = dict(TINY_DATA)
    dense["session"].update(n_cap=256)
    dense["session"]["policy"]["budget_bytes"] = 8 * (256 * 256 + 256)
    return {"table3-dense-n5063": dense}


def tiny_mixes() -> dict:
    b = load_json("bench/mixes/analytics-closed.json")
    b["arrival"].update(clients=2, stream_per_client=256)
    b["warmup"]["seconds"] = 1.0
    b["trace"]["profile_seconds"] = 0.5
    return {"analytics-closed": b}


def make_root(tmp, configs=None, mixes=None, bench=None) -> str:
    """A root directory with ``BENCHMARK.json`` and ``bench/`` holding
    the real metric readers and references and the given (default:
    tiny) configurations and mixes."""
    root = os.path.join(str(tmp), "checkout")
    base = os.path.join(root, "bench")
    for sub in ("metrics", "references"):
        shutil.copytree(os.path.join(BENCH, sub), os.path.join(base, sub),
                        ignore=shutil.ignore_patterns("__pycache__"))
    os.makedirs(os.path.join(base, "configs"))
    os.makedirs(os.path.join(base, "mixes"))
    bench = copy.deepcopy(bench or load_json("BENCHMARK.json"))
    for name, cfg in (configs or tiny_configs()).items():
        with open(os.path.join(base, "configs", name + ".json"), "w") as fh:
            json.dump(cfg, fh)
    for name, mix in (mixes or tiny_mixes()).items():
        with open(os.path.join(base, "mixes", name + ".json"), "w") as fh:
            json.dump(mix, fh)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return root


def run_tiny(tmp, workload: str, seed: int = 5, seconds: float = 2.0,
             trace: bool = False, root: str | None = None) -> dict:
    """One run of a tiny cell on the CPU, as ``bench/run.py`` runs it
    past its look for a chip."""
    import time
    from harness import cell as run_cell
    from harness import spec
    root = root or make_root(tmp)
    c = spec.load(root, workload)
    work = os.path.join(str(tmp), "work", workload)
    os.makedirs(work, exist_ok=True)
    return run_cell.run(c, seed, seconds, trace, work_dir=work,
                        started=time.time())
