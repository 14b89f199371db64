"""The control of a cell's comparison, at the cell's own size: the plain
reference computed in bfloat16 (the nearest precision below the float32
the configuration states), put in the program's place and compared with
the float64 reference as a run compares the program's answers. Every
limit is set below what this reads, so a control that passes means a
limit too loose. The benchmark's own runs never run it.

    python3 bench/control.py --workload <name> --seeds 1 2 3 [--supersteps 5]

``--supersteps`` is the engine's superstep count behind a window's answer
(PageRank's reference takes one update per superstep after the first).
One JSON line per seed on stdout.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

if __name__ == "__main__":
    _ROOT = Path(__file__).resolve().parent.parent
    sys.path[0:1] = [str(_ROOT), str(_ROOT / "src")]

from bench import common, harness  # noqa: E402


def readings(cell, seed: int, supersteps: int) -> dict:
    graph = cell.gen.generate(cell.config, seed)
    outcome = common.Outcome(work=0, steps=0, answers=[],
                             supersteps=supersteps, value_channels=1)
    want = cell.algo.reference(graph, cell.traffic, outcome, common.exact)
    ctrl = cell.algo.reference(graph, cell.traffic, outcome,
                               common.bfloat16)
    return cell.algo.compare(ctrl, want)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--supersteps", type=int, default=5)
    args = ap.parse_args(argv)
    cell = harness.resolve_cell(harness.load_spec(), args.workload)
    limits = cell.traffic["limits"]
    for seed in args.seeds:
        t = time.perf_counter()
        r = readings(cell, seed, args.supersteps)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "supersteps": args.supersteps, "control": r, "limits": limits,
            "fails_a_limit": any(r[k] > lim for k, lim in limits.items()),
            "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
