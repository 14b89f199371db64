"""How much longer the slowest chip works than the average chip in a
superstep, in percent: for each ``jit_superstep`` run in the traced
window, matched across chips by overlap in time, the slowest chip's
device time over the mean of all chips', minus 1, averaged over the
runs. A chip's device time is its operations' time in the run without
the collectives, where a chip that arrives first waits for the last.
Nothing to read with fewer than two chips or no such run."""
from bench import chips, tracedata

PROGRAM = "jit_superstep"


def read(run):
    if not run.trace or len(tracedata.device_planes(run.trace)) < 2:
        return None
    rows = chips.matched(chips.program_runs(run.trace, PROGRAM,
                                            skip=chips.COLLECTIVE))
    rows = [r for r in rows if sum(r) > 0]
    if not rows:
        return None
    return 100.0 * sum(max(r) * len(r) / sum(r) - 1 for r in rows) / len(rows)
