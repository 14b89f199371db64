"""Device milliseconds of XLA sort instructions (``sort``, ``sort.<n>``)
per superstep program run in the traced window (all chips' sort time over the first chip's
superstep runs, so a sharded step counts every chip's sorts)."""
import re

from bench import tracedata

SORT = re.compile(r"sort(\.\d+)?$")
SUPERSTEP = "jit_superstep"


def read(run):
    if not run.trace or not tracedata.device_planes(run.trace):
        return None
    steps = tracedata.programs_in_window(run.trace, SUPERSTEP)
    if steps == 0:
        return None
    sorts = tracedata.ops_in_window(run.trace, SORT)
    return sum(d for _, _, d in sorts) / 1e6 / steps
