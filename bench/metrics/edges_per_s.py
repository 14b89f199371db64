"""Directed edge slots processed per second over the whole window: all
the work the window did (whole supersteps or whole jobs, as the traffic
defines its unit) over all of its time."""


def read(run):
    return run.work / run.window_s
