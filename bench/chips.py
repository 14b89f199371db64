"""Pieces of the benchmark that look at each chip of a multi-chip cell:
device memory per chip, and each chip's runs of a program in a traced
window (``bench.tracedata`` form), matched across chips."""
from __future__ import annotations

import re

from bench import tracedata
from bench.common import log

# collectives: on a chip that reaches one first, its time is spent waiting
# for the slowest peer
COLLECTIVE = re.compile(
    r"(all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute)")


def log_memory(what: str, devices) -> None:
    """Each chip's ``bytes_in_use`` and ``peak_bytes_in_use``, on stderr."""
    for d in devices:
        s = d.memory_stats() or {}
        log(f"{what}: device {d.id} bytes_in_use {s.get('bytes_in_use')} "
            f"peak_bytes_in_use {s.get('peak_bytes_in_use')}")


def program_runs(trace: dict, prefix: str, *,
                 skip=None) -> list:
    """The runs inside the window of a program whose name starts with
    ``prefix``, one list per device plane: each run as (start, end,
    nanoseconds of the operations it ran), without the operations whose
    instruction name matches ``skip``. Runs of one superstep on several
    chips overlap in time, so they are matched by ``matched``."""
    lo, hi = tracedata.window_ns(trace)
    out = []
    for plane in tracedata.device_planes(trace):
        ops = tracedata.line_events(plane, tracedata.OPS_LINE)
        runs = []
        for name, s, d in tracedata.line_events(plane,
                                                tracedata.MODULES_LINE):
            if not (name.startswith(prefix) and s >= lo and s + d <= hi):
                continue
            ns = sum(od for op, os_, od in ops
                     if os_ >= s and os_ + od <= s + d
                     and not (skip and skip.match(tracedata.op_name(op))))
            runs.append((s, s + d, ns))
        out.append(runs)
    return out


def matched(per_chip: list) -> list:
    """The runs of the first chip that overlap a run on every other chip,
    each as the list of the overlapping runs' nanoseconds, one per chip."""
    out = []
    for s, e, ns in per_chip[0] if per_chip else []:
        row = [ns]
        for runs in per_chip[1:]:
            best = max(runs, default=None,
                       key=lambda r: min(r[1], e) - max(r[0], s))
            if best is None or min(best[1], e) <= max(best[0], s):
                break
            row.append(best[2])
        else:
            out.append(row)
    return out
