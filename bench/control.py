"""The control: the plain reference put in the system's place one step
down in precision (bfloat16 for the float32 the configurations state;
for BFS, whose levels bfloat16 holds exactly, the reference stopped one
level early).  It must come out as not correct against the cell's
limits.

    python3 bench/control.py --workload ca_road.sssp_c8 --seeds 1 2 3 \\
        --queries 24

For each seed it generates the cell's graph, takes the first
``--queries`` queries a run would send, and prints one JSON line with
each compared number beside its limit.  It uses no chip and none of the
system; the benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def control_numbers(reg, cell_name: str, seed: int, count: int) -> dict:
    cell = reg.cell(cell_name)
    cfg = reg.config(cell["config"])
    mix = reg.traffic(cell["traffic"])
    g = reg.generator(cfg["generator"]).generate(cfg["generator_params"],
                                                 seed)
    qs = reg.load_kind(mix["load"]).queries(g, mix, seed, count)
    numbers = reg.check(mix["algo"]).compare(
        g, [(q, None) for q in qs], control=True)
    limits = reg.limits(cell_name)
    return {name: {"value": v, "limit": limits[name]["limit"]}
            for name, v in numbers.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--queries", type=int, default=8)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from bench import harness
    reg = harness.Registry.load(ROOT)
    for seed in args.seeds:
        t = time.perf_counter()
        checks = control_numbers(reg, args.workload, seed, args.queries)
        correct = all(c["value"] <= c["limit"] for c in checks.values())
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "queries": args.queries,
                          "control_correct": correct, "checks": checks,
                          "seconds": time.perf_counter() - t}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
