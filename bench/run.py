"""Run one benchmark cell once, from the root of a checkout:

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads, warms up, measures for ``--seconds`` (to the next boundary of
work), checks the answers against a plain reference and prints one JSON
line on stdout: the end-to-end metrics, or with ``--trace 1`` the
per-layer ones from a profiler trace of the window. It exits non-zero
with no result off TPU, with fewer chips than the cell asks for, with a
device kind missing from ``bench/peaks.json``, or with
``$REPRO_KERNEL_IMPL`` set.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parent.parent
# the checkout root (for ``bench``) and ``src`` (for the program), in place
# of this directory, whose subdirectories must not shadow other packages
sys.path[0:1] = [str(_ROOT), str(_ROOT / "src")]

from bench import harness  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


if __name__ == "__main__":
    sys.exit(harness.main(parse(), T_START))
