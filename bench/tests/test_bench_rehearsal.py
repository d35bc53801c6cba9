"""Each cell end to end on the CPU at a tiny size: mix, generator,
session, window, reference check and the last line's schema; the
harness finding new pieces as new files; ``run.py`` refusing to
measure without a chip."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import benchkit

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def _names(bench, kind, cell):
    return {m["name"] for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("cell", [benchkit.B1])
def test_tiny_cell_end_to_end(tmp_path, cell):
    r = benchkit.run_tiny(tmp_path, cell)
    json.dumps(r)
    assert list(r) == KEYS                   # checks come last
    assert r["correct"] is True and r["failed"] == 0
    assert r["attempted"] > 0
    bench = benchkit.load_json("BENCHMARK.json")
    assert set(r["metrics"]) == _names(bench, "end_to_end", cell)
    for m in r["metrics"].values():
        assert m["value"] > 0
    assert set(r["device"]) == {"platform", "kind", "count",
                                "memory_peak_bytes"}
    assert set(r["checks"]) == {"wrong_answers", "unanswered"}
    assert all(c["value"] <= c["limit"] for c in r["checks"].values())


@pytest.mark.parametrize("cell", [benchkit.B1])
def test_tiny_traced_run_reports_per_layer(tmp_path, cell):
    r = benchkit.run_tiny(tmp_path, cell, trace=True, seconds=3.0)
    assert r["correct"] is True
    assert {"busy_s", "window_s"} <= set(r["device"])
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    # host-side layers read on any platform; device ones need a device
    # plane, which the CPU's trace does not have
    bench = benchkit.load_json("BENCHMARK.json")
    host = {m["name"] for m in bench["per_layer"]
            if cell in m["workloads"] and m["source"] != "device_trace"}
    assert host and host <= set(r["metrics"]), host - set(r["metrics"])
    assert set(r["metrics"]) <= _names(bench, "per_layer", cell)
    assert list(r)[-1] == "checks"


def test_new_config_mix_and_metric_are_new_files(tmp_path):
    bench = benchkit.load_json("BENCHMARK.json")
    configs = benchkit.tiny_configs()
    mixes = benchkit.tiny_mixes()
    extra = json.loads(json.dumps(configs["table3-dense-n5063"]))
    extra["data"]["n_nodes"] = 120
    configs["throwaway-config"] = extra
    mix = json.loads(json.dumps(mixes["analytics-closed"]))
    mix["queries"] = [{"weight": 1, "kind": "point", "scope": "global",
                       "measure": "num_edges"}]
    mixes["throwaway-mix"] = mix
    bench["configs"].append({"name": "throwaway-config", "source": "x",
                             "file": "bench/configs/throwaway-config.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "throwaway", "chips": 1, "why": "x",
                               "config": "throwaway-config",
                               "traffic": "throwaway-mix"})
    bench["end_to_end"].append({"name": "answered_total", "unit": "queries",
                                "better": "higher", "bound": 0.25,
                                "source": "host_clock",
                                "workloads": ["throwaway"]})
    root = benchkit.make_root(tmp_path, configs, mixes, bench)
    with open(os.path.join(root, "bench", "metrics", "answered_total.py"),
              "w") as fh:
        fh.write("def read(ctx):\n    return len(ctx.answered())\n")
    r = benchkit.run_tiny(tmp_path, "throwaway", root=root)
    assert r["correct"] is True
    assert r["metrics"]["answered_total"]["value"] > 0
    assert set(r["metrics"]) == _names(bench, "end_to_end", "throwaway")


def _run_py(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", benchkit.B1,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_py_refuses_without_a_tpu():
    p = _run_py(benchkit.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_run_py_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(benchkit.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(benchkit.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(
                        "__pycache__", ".jax_cache", ".work"))
    p = _run_py(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
