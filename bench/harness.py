"""Run one cell of ``BENCHMARK.json`` once: set up, measure a window,
check the answers against the plain reference, print one result line.

Everything that belongs to one configuration, traffic mix or metric sits
in files of its own, found by the names ``BENCHMARK.json`` gives:

- a configuration's ``file`` (data: sizes, source, deployment) names its
  ``generator``, ``bench/gens/<generator>.py``, which makes the graph on
  the device from the seed;
- ``bench/traffic/<traffic>.json`` (data: the traffic's parameters and the
  limits of its comparison) names its ``algorithm``,
  ``bench/algos/<algorithm>.py``, which drives the window through the
  program's normal entry and holds the plain reference;
- ``bench/metrics/<metric>.py`` reads one number from the run record.

A later cell, configuration, traffic or metric is new files and new
entries; no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
import shutil
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Optional

import numpy as np

from bench import tracedata
from bench.common import exact, log

ROOT = Path(__file__).resolve().parent.parent
# compile work inside the window: tracing, lowering and the backend
# compile, which wraps the persistent-cache lookup (so the cache's own
# retrieval event would count twice). Nested events are merged.
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class Refused(Exception):
    """The run cannot be measured here; no result is printed."""


def load_module(path: Path):
    """Import one file of the benchmark by its path (names may hold dots
    and dashes, which the import statement cannot)."""
    if not path.is_file():
        raise Refused(f"no file {path}")
    name = "bench_file_" + re.sub(r"\W", "_", str(path.relative_to(ROOT)
                                                   if path.is_relative_to(
                                                       ROOT) else path))
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    gen: object
    algo: object


def load_spec(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise Refused(f"no {path}")
    return json.loads(path.read_text())


def resolve_cell(spec: dict, name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration, traffic and code, found
    by the names in ``spec``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=name, chips=w["chips"], config=config, traffic=traffic,
        gen=load_module(root / "bench" / "gens" /
                        f"{config['generator']}.py"),
        algo=load_module(root / "bench" / "algos" /
                         f"{traffic['algorithm']}.py"))


def metric_reader(name: str, root: Path = ROOT):
    return load_module(root / "bench" / "metrics" / f"{name}.py").read


def check_device(devices, chips: int, peaks: dict) -> dict:
    """The peaks of the chip this run measures; refuses anything but as
    many TPU chips as the cell asks for, of a kind in the peaks table."""
    if not devices or devices[0].platform != "tpu":
        raise Refused(f"needs {chips} TPU chip(s); JAX sees "
                      f"{len(devices)} {devices[0].platform if devices else 'no'}"
                      f" device(s)")
    if len(devices) < chips:
        raise Refused(f"needs {chips} TPU chip(s); JAX sees {len(devices)}")
    kind = devices[0].device_kind
    if kind not in peaks:
        raise Refused(f"device kind {kind!r} is not in the peaks table")
    return peaks[kind]


class Window:
    """The measured window. The algorithm driver opens it when set-up ends
    and asks at each work boundary whether it has expired; with a trace
    directory, the profiler records exactly the window."""

    def __init__(self, seconds: float, t_start: float,
                 trace_dir: Optional[str] = None):
        self.seconds, self.t_start, self.trace_dir = seconds, t_start, \
            trace_dir
        self.setup_s = self.window_s = None
        self.wall = (None, None)

    def open(self) -> None:
        import jax
        self.setup_s = time.perf_counter() - self.t_start
        log(f"set-up done in {self.setup_s:.3f} s; window opens")
        if self.trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(tracedata.WINDOW_SPAN)
        self._span.__enter__()
        self._wall0 = time.time()
        self._t0 = time.perf_counter()

    def is_open(self) -> bool:
        return self.setup_s is not None and self.window_s is None

    def expired(self) -> bool:
        return time.perf_counter() - self._t0 >= self.seconds

    def close(self) -> None:
        import jax
        self.window_s = time.perf_counter() - self._t0
        self.wall = (self._wall0, time.time())
        self._span.__exit__(None, None, None)
        if self.trace_dir:
            jax.profiler.stop_trace()
        log(f"window closed after {self.window_s:.3f} s")


@dataclass
class RunRecord:
    """What the metric readers read."""
    cell: str
    setup_s: float
    window_s: float
    window_wall: tuple          # (start, end) on time.time()'s clock
    work: float                 # directed edge slots processed
    steps: int                  # supersteps in the window
    edge_slots: int
    vertices: int
    peak_bytes: Optional[int]   # fullest chip's peak_bytes_in_use
    peaks: dict                 # the chip's entry of bench/peaks.json
    value_channels: int         # vertex value channels sent from
    receivers: int              # vertices with at least one in-slot
    compile_spans: list         # (start, end) on time.time()'s clock
    trace: Optional[dict]       # tracedata form, None without --trace 1


def peak_bytes(devices) -> Optional[int]:
    stats = [d.memory_stats() for d in devices]
    if not all(stats):
        return None
    return max(s["peak_bytes_in_use"] for s in stats)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             devices, peaks: dict, t_start: float):
    """Set up, measure and check one run. Returns the run record, the
    numbers compared as {name: (value, limit)}, and the answers attempted
    and failed."""
    import jax
    spans = []

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    window = Window(seconds, t_start, trace_dir)

    def on_span(event, start, end, **_):
        if event in COMPILE_EVENTS:
            spans.append((start, end))
            if window.is_open():
                log(f"in the window: {event} {end - start:.3f} s")

    jax.monitoring.register_event_time_span_listener(on_span)
    try:
        with jax.profiler.TraceAnnotation("bench.generate"):
            graph = cell.gen.generate(cell.config, seed)
        log(f"graph: {graph.n} vertices, {graph.edge_slots} edge slots")
        outcome = cell.algo.drive(graph, cell.traffic, window)
        peak = peak_bytes(devices)
        tr = tracedata.load_xspace(trace_dir) if trace else None
    finally:
        jax.monitoring.unregister_event_time_span_listener(on_span)
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    record = RunRecord(
        cell=cell.name, setup_s=window.setup_s, window_s=window.window_s,
        window_wall=window.wall, work=outcome.work, steps=outcome.steps,
        edge_slots=graph.edge_slots, vertices=graph.n, peak_bytes=peak,
        peaks=peaks, value_channels=outcome.value_channels,
        receivers=int(np.count_nonzero(np.bincount(graph.dst,
                                                   minlength=graph.n))),
        compile_spans=spans, trace=tr)
    log(f"compile in the window: "
        f"{metric_reader('compile_s.window')(record):.3f} s")
    t = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.reference"):
        want = cell.algo.reference(graph, cell.traffic, outcome, exact)
        readings = [None if a is None else cell.algo.compare(a, want)
                    for a in outcome.answers]
    log(f"reference over {len(readings)} answer(s) in "
        f"{time.perf_counter() - t:.3f} s")
    limits = cell.traffic["limits"]
    checks = {k: (max((r[k] for r in readings if r), default=None), lim)
              for k, lim in limits.items()}
    failed = sum(1 for r in readings
                 if r is None or any(r[k] > lim for k, lim in limits.items()))
    return record, checks, len(readings), failed


def read_metrics(spec: dict, record: RunRecord, trace: bool,
                 root: Path = ROOT) -> dict:
    """This cell's end-to-end metrics (per-layer ones with ``trace``); a
    reader that finds nothing to read leaves its metric out."""
    out = {}
    for m in spec["per_layer" if trace else "end_to_end"]:
        if record.cell not in m.get("workloads", [record.cell]):
            continue
        value = metric_reader(m["name"], root)(record)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def execute(spec: dict, cell: Cell, seed: int, seconds: float, trace: bool,
            devices, peaks: dict, t_start: float,
            root: Path = ROOT) -> dict:
    """One run of ``cell``; returns the result line's object."""
    record, checks, attempted, failed = run_cell(
        cell, seed, seconds, trace, devices, peaks, t_start)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": record.peak_bytes}
    line = {"correct": attempted > 0 and failed == 0,
            "attempted": attempted, "failed": failed,
            "metrics": read_metrics(spec, record, trace, root),
            "device": device}
    if trace and record.trace and tracedata.device_planes(record.trace):
        device["busy_s"], device["window_s"] = \
            tracedata.device_time(record.trace)
        line["breakdown"] = tracedata.breakdown(record.trace)
        for k, (dur, ops) in enumerate(tracedata.program_ops(
                record.trace, "jit_superstep")):
            top = sorted(ops.items(), key=lambda kv: -kv[1])[:6]
            log(f"program {k} in the window: {dur / 1e9:.3f} s on the device;"
                + "".join(f" {n} {t / 1e9:.3f}" for n, t in top))
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in checks.items()}
    return line


def setup_compile_cache() -> str:
    """The program's persistent compilation cache (its own fixed
    directory), with every program cached however fast it compiled, so
    no run after a checkout's first compiles."""
    import jax
    from repro.launch.compile_cache import setup_compile_cache as program
    path = program()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def main(args, t_start: float) -> int:
    try:
        if os.environ.get("REPRO_KERNEL_IMPL"):
            raise Refused("REPRO_KERNEL_IMPL is set; the benchmark runs the "
                          "kernel choice the platform makes")
        spec = load_spec()
        cell = resolve_cell(spec, args.workload)
        try:
            import repro.core  # noqa: F401  the system under test
        except ImportError as e:
            raise Refused(f"the program is not in this checkout: {e}")
        peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
        import jax
        chip = check_device(jax.devices(), cell.chips, peaks)
        cache = setup_compile_cache()
        devices = jax.devices()[:cell.chips]
        log(f"{args.workload}: seed {args.seed}, {args.seconds} s, trace "
            f"{args.trace}; {devices[0].device_kind} x{len(devices)}, "
            f"compile cache {cache}")
        line = execute(spec, cell, args.seed, args.seconds, bool(args.trace),
                       devices, chip, t_start)
    except Refused as e:
        log(f"refused: {e}")
        return 1
    for k, c in line["checks"].items():
        log(f"check {k} = {c['value']} (limit {c['limit']})")
    print(json.dumps(line), flush=True)
    return 0
