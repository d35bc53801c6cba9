"""The control of the benchmark's check: the plain reference put in the
program's place with one of the configuration's guarantees broken, read
by the same comparison a run makes.  It has to come out as not correct.

    python3 bench/control.py --workload <name> --seeds 1,2,3 [--requests 350]

``stale``: every answer read one time unit early — a store serving a
snapshot one unit behind what it claims (breaks "a query at t <=
watermark is exact").

For each seed it generates the cell's data and ``--requests`` of the
window's requests as a run of that seed would (the same sampler;
350 by default, about what a dense-analytics window answers), and
prints one JSON line per seed with the numbers the run would compare.
Runs on the host; needs no chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def control(cell, seed: int, requests: int = 350) -> dict:
    from harness import cell as run_cell
    from harness import traffic
    from harness import spec
    data = run_cell.make_data(cell, seed)
    sampler = traffic.Sampler(cell.mix, 1, data["t_base"],
                              cell.config["data"]["n_nodes"])
    reqs = sampler.stream(np.random.default_rng([seed, 2]), requests,
                          np.random.default_rng([2]))
    ref_mod = spec.reference(cell)
    ref = ref_mod.Reference(data["cols"], cell.config["session"]["n_cap"])
    want = ref.answers(reqs)
    stale = ref.answers(reqs, shift=1)
    return {"seed": seed, "requests": len(reqs),
            "stale.wrong_answers": sum(not ref_mod.same(g, w)
                                       for g, w in zip(stale, want))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--requests", type=int, default=350)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]
    from harness import spec
    cell = spec.load(ROOT, args.workload)
    for s in args.seeds.split(","):
        print(json.dumps(control(cell, int(s), args.requests)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
