"""``chip_smoke.py``'s control flow at a tiny size on the CPU: the phases
it runs on the chip must agree with its numpy reference here too, and
the script must refuse to run without a TPU."""
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke  # noqa: E402


@pytest.mark.parametrize("layout,n_cap,n_nodes", [("edge", 512, 400),
                                                  ("dense", 128, 128)])
def test_phase_matches_reference(layout, n_cap, n_nodes):
    report = chip_smoke.run_phase(layout, n_cap, n_nodes, seed=3)
    assert report["mismatches"] == []
    assert report["epochs"] >= 2 and report["queries"] >= 130
    assert report["watermark"] == report["t_max"] > report["t_split"]


def test_reference_replays_last_op_per_key():
    op = [0, 0, 0, 2, 2, 3, 2, 3]
    u = [0, 1, 2, 0, 1, 0, 0, 2]
    v = [0, 1, 2, 1, 2, 1, 1, 1]
    t = [1, 1, 1, 1, 2, 3, 4, 5]
    import numpy as np
    ref = chip_smoke.Reference(np.array([op, u, v, t]), 4)
    needs = {t: {("num_edges", None), ("degree", 1)} for t in range(1, 6)}
    got = ref.measures(needs)
    assert [got[t, "num_edges", None] for t in range(1, 6)] == [1, 2, 1, 2, 1]
    assert [got[t, "degree", 1] for t in range(1, 6)] == [1, 2, 1, 2, 1]


@pytest.mark.multidevice
def test_sharded_path_matches_one_device():
    """``--chips 4``'s comparison on four forced host devices: every
    shard mode dispatches and agrees with one device and the reference.
    A child process, because the device count is fixed at JAX start."""
    import subprocess
    root = os.path.join(os.path.dirname(__file__), "..")
    code = ("import jax, chip_smoke\n"
            "for layout, n in (('edge', 512), ('dense', 128)):\n"
            "    r = chip_smoke.run_sharded(layout, n, n, 3,"
            " jax.devices()[:4])\n"
            "    assert r['mismatches'] == [], r['mismatches'][:3]\n"
            "    print(layout, sorted(r['shard_modes']))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(root, "src") + os.pathsep + root)
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=root,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "edge ['batch', 'slots']" in r.stdout
    assert "dense ['batch', 'rows', 'slots']" in r.stdout


def test_refuses_to_run_without_a_tpu(capsys):
    assert chip_smoke.main([]) != 0
    assert "platform 'cpu'" in capsys.readouterr().err
