"""Profiler traces reduced to plain data, and the interval arithmetic the
per-layer metrics share.

A trace is held as ``{"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, duration_ns], ...]}]}]}``: JSON, so a small recorded
trace can live beside the tests. Device planes are ``/device:TPU:<n>``;
their ``XLA Ops`` line holds one event per operation the device ran and
their ``XLA Modules`` line one per program. A device operation's event
is named by its HLO text, ``%<instruction> = <type> <op>(<operands>)...``:
``op_name`` gives the instruction (``sort.10``, ``csr_spmv.1``) and
``operand_shapes`` the operands' shapes. Host threads are lines of
``/host:CPU``, where the benchmark's ``TraceAnnotation`` spans land
(``bench.window`` marks the measured window).
"""
from __future__ import annotations

import glob
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"


def load_xspace(log_dir: str) -> dict:
    """The one ``.xplane.pb`` a ``jax.profiler`` session wrote under
    ``log_dir``, as plain data."""
    from jax.profiler import ProfileData
    paths = glob.glob(str(Path(log_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace file under {log_dir}, "
                           f"found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    return {"planes": [
        {"name": plane.name, "lines": [
            {"name": line.name,
             "events": [[e.name, e.start_ns, e.duration_ns]
                        for e in line.events]}
            for line in plane.lines]}
        for plane in data.planes]}


def op_name(event_name: str) -> str:
    """The HLO instruction an ``XLA Ops`` event ran: ``sort.10``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


_CALL = re.compile(r"\s[a-z][\w-]*\(")          # " custom-call("
_OPERAND = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")   # "s32[1,1024]"


def operand_shapes(event_name: str) -> list:
    """(dtype, dims) of each operand in an event's HLO text, in order."""
    _, _, rest = event_name.partition(" = ")
    call = _CALL.search(rest)
    if call is None:
        return []
    args, depth = rest[call.end():], 1
    for end, ch in enumerate(args):
        depth += {"(": 1, ")": -1}.get(ch, 0)
        if depth == 0:
            args = args[:end]
            break
    return [(t, tuple(int(d) for d in dims.split(",") if d))
            for t, dims in _OPERAND.findall(args)]


def device_planes(trace: dict) -> list:
    return [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]


def line_events(plane: dict, line_name: str) -> list:
    return [e for ln in plane["lines"] if ln["name"] == line_name
            for e in ln["events"]]


def window_ns(trace: dict):
    """(start, end) of the ``bench.window`` span, or None."""
    for plane in trace["planes"]:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name == WINDOW_SPAN:
                    return start, start + dur
    return None


def clip(events, lo, hi) -> list:
    """(start, end) of each event, cut to [lo, hi]; empty ones dropped."""
    out = []
    for _, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((s, e))
    return out


def union(intervals) -> list:
    """Merge (start, end) intervals into disjoint ones, in order."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [tuple(m) for m in merged]


def busy_ns(plane: dict, lo, hi) -> float:
    """Nanoseconds of [lo, hi] in which some operation ran on the device."""
    return float(sum(e - s for s, e in
                     union(clip(line_events(plane, OPS_LINE), lo, hi))))


def ops_in_window(trace: dict, pattern) -> list:
    """Device operations inside the window whose instruction name matches
    ``pattern`` (a compiled regex), over all device planes."""
    lo, hi = window_ns(trace)
    return [e for p in device_planes(trace)
            for e in line_events(p, OPS_LINE)
            if pattern.match(op_name(e[0])) and e[1] >= lo
            and e[1] + e[2] <= hi]


def programs_in_window(trace: dict, prefix: str) -> int:
    """Program runs inside the window whose name starts with ``prefix``,
    on the first device."""
    lo, hi = window_ns(trace)
    return sum(1 for name, s, d in
               line_events(device_planes(trace)[0], MODULES_LINE)
               if name.startswith(prefix) and s >= lo and s + d <= hi)


def program_ops(trace: dict, prefix: str) -> list:
    """For each run inside the window of a program whose name starts with
    ``prefix``, on the first device: (its nanoseconds, {instruction:
    nanoseconds of the operations it ran})."""
    lo, hi = window_ns(trace)
    plane = device_planes(trace)[0]
    ops = line_events(plane, OPS_LINE)
    out = []
    for name, s, d in line_events(plane, MODULES_LINE):
        if not (name.startswith(prefix) and s >= lo and s + d <= hi):
            continue
        took = {}
        for op, os_, od in ops:
            if os_ >= s and os_ + od <= s + d:
                took[op_name(op)] = took.get(op_name(op), 0) + od
        out.append((d, took))
    return out


def device_time(trace: dict):
    """(busy seconds averaged over the device planes, window seconds)."""
    lo, hi = window_ns(trace)
    planes = device_planes(trace)
    busy = sum(busy_ns(p, lo, hi) for p in planes) / len(planes)
    return busy / 1e9, (hi - lo) / 1e9


def breakdown(trace: dict, top: int = 10) -> dict:
    """The device operations that took most time in the window (each
    named by the head of its HLO text, which shows its op and shapes),
    and the longest idle gaps of the first device inside the window, each
    named by the host span that covers most of it."""
    lo, hi = window_ns(trace)
    totals = {}
    for plane in device_planes(trace):
        for name, start, dur in line_events(plane, OPS_LINE):
            if start >= lo and start + dur <= hi:
                key = name.lstrip("%")[:120]
                totals[key] = totals.get(key, 0.0) + dur / 1e9
    ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    busy = union(clip(line_events(device_planes(trace)[0], OPS_LINE),
                      lo, hi))
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    host = [e for p in trace["planes"] if p["name"] == HOST_PLANE
            for ln in p["lines"] for e in ln["events"]
            if e[0] != WINDOW_SPAN]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[_host_label(host, s, e), (e - s) / 1e9]
                          for s, e in gaps]}


def _host_label(host_events, lo, hi) -> str:
    """The innermost host span that covers at least half of the gap (the
    shortest such), else the one that covers most of it."""
    covering = []
    for name, start, dur in host_events:
        cover = min(start + dur, hi) - max(start, lo)
        if cover > 0:
            covering.append((cover, dur, name))
    if not covering:
        return "no host span"
    half = [c for c in covering if 2 * c[0] >= hi - lo]
    if half:
        return min(half, key=lambda c: c[1])[2]
    return max(covering)[2]
