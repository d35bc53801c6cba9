"""The reduction from a device trace to busy time, idle share, device
time per program family and named idle gaps, on a small trace."""
import json
import os

import pytest

import benchkit  # noqa: F401 — puts the harness on the path
from harness import profile

FAMILIES = {"reconstruct": "two_phase|evolve|reconstruct|batch_measure",
            "scan": "hybrid|delta_only"}


def small():
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "trace_small.json")) as fh:
        d = json.load(fh)
    tr = profile.Trace(ops=[tuple(x) for x in d["ops"]],
                       modules=[tuple(x) for x in d["modules"]],
                       host=[tuple(x) for x in d["host"]],
                       start_ns=d["start_ns"], end_ns=d["end_ns"])
    return tr, d["expect"]


def test_busy_union_and_idle_share():
    tr, want = small()
    r = profile.reduce(tr, FAMILIES)
    assert r["busy_s"] == pytest.approx(want["busy_ns"] / 1e9)
    assert r["window_s"] == pytest.approx(1e-6)
    assert r["idle_share"] == pytest.approx(want["idle_share"])


def test_module_family_attribution():
    tr, want = small()
    r = profile.reduce(tr, FAMILIES)
    got = {k: round(v * 1e9) for k, v in r["families_s"].items()}
    assert got == want["families_ns"]
    assert r["top_modules"][0][0] == "jit_batch_edge_two_phase_point"


def test_gaps_named_by_host_work():
    tr, want = small()
    r = profile.reduce(tr, FAMILIES, top=3)
    got = [[n, round(s * 1e9)] for n, s in r["idle_gaps"]]
    assert got == want["gaps"]


def test_two_devices_average_busy_time():
    tr, _ = small()
    # the second device is busy 150-250 (under the first's busy time)
    # and 600-700
    tr.ops = tr.ops + [("fusion.9", 150, 100, 1), ("fusion.9", 600, 100, 1)]
    tr.devices = 2
    r = profile.reduce(tr)
    assert r["busy_s"] == pytest.approx((300 + 200) / 2 / 1e9)
    assert r["idle_gaps"][0][1] == pytest.approx(250e-9)


def test_module_names_lose_instance_suffixes():
    assert profile.module_name("jit_batch_evolve(12)") == "jit_batch_evolve"
    assert profile.module_name("jit_concatenate.3") == "jit_concatenate"
    assert profile.merge([(5, 9), (1, 3), (2, 4)]) == [(1, 4), (5, 9)]
