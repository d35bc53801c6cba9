"""The benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout, on a machine with the chips the cell
asks for; one process per run.  Generates the cell's data from the
seed, loads it into a ``repro.api.GraphSession``, warms up with the
cell's own traffic, measures for ``--seconds``, then checks what the
window served against the plain reference.  ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics (with a
profiled part of the window).  The last line of standard output is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, ``breakdown`` when traced, and ``checks`` last: every number
compared with its limit); the last lines of standard error are the
same checks.  Without a TPU, or with fewer chips than the cell asks
for, it exits non-zero and prints no result.

The persistent compile cache is ``bench/.jax_cache`` of the checkout,
whatever the environment says, so only the first run of a cell in a
checkout compiles.
"""
from __future__ import annotations

import time

STARTED = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"bench: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path[:0] = [SRC, BENCH]
    from harness import spec
    cell = spec.load(ROOT, args.workload)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(BENCH,
                                                           ".jax_cache")
    # the TPU library otherwise logs to a fixed directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devices = jax.devices()
    chips = int(cell.workload["chips"])
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench: {args.workload} needs {chips} TPU chip(s), found "
              f"{len(devices)} {devices[0].platform!r} device(s)",
              file=sys.stderr)
        return 1
    from repro.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", file=sys.stderr)

    from harness import cell as run_cell
    work = os.path.join(BENCH, ".work", args.workload)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    try:
        result = run_cell.run(cell, args.seed, args.seconds,
                              bool(args.trace), work_dir=work,
                              started=STARTED)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # the program's daemon threads and the TPU runtime are not torn down
    # by interpreter shutdown; the result is out, so leave at once
    os._exit(rc)
