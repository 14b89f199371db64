"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals) / window, averaged over
the chips used."""
from bench import tracedata


def read(run):
    if not run.trace or not tracedata.device_planes(run.trace):
        return None
    busy, window = tracedata.device_time(run.trace)
    return 100.0 * (1.0 - busy / window)
