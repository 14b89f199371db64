"""Paper Figure 10 (the headline claim): graceful in-memory -> out-of-core
degradation, the streaming-vs-synchronous executor race, and the OOC
auto-planner race.

Part 1 fixes the graph and shrinks the device-memory budget
(budget_partitions): in-memory (budget=P) vs increasingly streamed
executions. Process-centric systems fall off a cliff past ratio 1.0; an
out-of-core dataflow degrades with a gentle slope. Also measures the
delta-storage (LSM analogue) writeback savings.

Part 2 races the PIPELINED streaming executor (``stream=True``: prefetch
the next super-partition's upload and drain the previous result while the
current one computes) against the synchronous loop across
PageRank / SSSP / CC and super-partition counts, reporting the speedup
and the dispatch / compute-wait / commit wall-time split.

Part 3 races ``plan="auto"`` against representative static plans OUT-OF-
CORE — the full join x group-by x connector x sender-combine x storage
space is searchable there — and reports auto's steady-state slowdown vs
the best static plan plus any mid-run connector/storage picks.

Part 5 (``pipeline_race`` -> ``BENCH_pipeline.json``) races the
BARRIER-FREE superstep pipeline against the PR-4 pipelined executor:
per-destination inbox readiness + the background page-I/O engine vs the
global inter-superstep barrier + synchronous page I/O, in DRAM and on
the disk tier, reporting wall times, readiness-stall seconds and I/O
queue-depth percentiles.

``--sharded`` (-> ``BENCH_sharded.json``) races the REAL multi-device
driver (``core/sharded.py``): the same fixed graph on a 1/2/4/8-device
host mesh, per-device-count wall time, exchange-stall seconds and
all_to_all wire bytes, plus the planner's predicted exchange seconds
(net axis, calibrated the way the adaptive controller does it: a
net_scale fit on the first half of the measured exchange stalls,
validated against the second half).

Everything lands in machine-readable ``BENCH_ooc.json`` (per-config
steady-state wall times, streaming speedups, picked plans) so CI can
archive the perf trajectory across PRs. ``--smoke`` runs a tiny config
(CI keeps the OOC path and the README examples honest without burning
minutes). The wall times are taken on whatever backend runs the script —
the committed ``BENCH_ooc.json`` is CPU smoke output, not a device metric.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile

# must land before the repro import chain pulls in jax: the sharded race
# needs a multi-device host platform (same hack as launch/pregel_run)
if "--sharded" in sys.argv and "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np

from repro.core import PhysicalPlan, load_graph, run_host
from repro.core.ooc import run_out_of_core
from repro.graph import SSSP, ConnectedComponents, PageRank, rmat_graph
from repro.graph.generators import grid_graph

from benchmarks.common import record, time_supersteps


def budget_sweep(scale: float, P: int = 8):
    n = max(int(16_000 * scale), 16 * P)
    edges = rmat_graph(n, 10 * n, seed=4)
    prog = PageRank(n, iterations=6)
    plan = prog.suggested_plan
    vert = load_graph(edges, n, P=P, value_dims=2)
    mem = run_host(vert, prog, plan, max_supersteps=8)
    t_mem = time_supersteps(mem)
    record("ooc/in_memory", t_mem * 1e6, "budget=all")
    out = {"in_memory": t_mem}
    for budget in (P, P // 2, P // 4, P // 8):
        vert2 = load_graph(edges, n, P=P, value_dims=2)
        res = run_out_of_core(vert2, prog, plan, budget_partitions=budget,
                              max_supersteps=8)
        t = time_supersteps(res)
        ratio = P / budget
        out[f"budget_1_{ratio:g}"] = t
        record(f"ooc/budget_ratio_{ratio:g}x", t * 1e6,
               f"slowdown_vs_mem={t / t_mem:.2f}")
    # delta vs full writeback (LSM analogue) on a sparse-update workload
    sp = SSSP(source=0)
    out["writeback_bytes"] = {}
    for storage in ("inplace", "delta"):
        vert3 = load_graph(edges, n, P=P, value_dims=1)
        res = run_out_of_core(vert3, sp,
                              dataclasses.replace(plan, join="full_outer",
                                                  storage=storage),
                              budget_partitions=P // 2, max_supersteps=20)
        last = res.stats[-1]
        bytes_shipped = (last["delta_bytes"] if storage == "delta"
                         else last["full_bytes"])
        out["writeback_bytes"][storage] = bytes_shipped
        record(f"ooc/writeback_{storage}", bytes_shipped,
               "bytes shipped device->host")
    return out


def _io_split(res):
    """Steady-state per-superstep (dispatch, wait, commit) means."""
    recs = [s for s in res.stats
            if "wall_s" in s and not s.get("recompiled", False)]
    if not recs:
        recs = [s for s in res.stats if "wall_s" in s][1:]
    k = max(len(recs), 1)
    return {f: sum(s.get(f, 0.0) for s in recs) / k
            for f in ("dispatch_s", "collect_wait_s", "commit_s")}


def streaming_race(scale: float, P: int = 8):
    """The tentpole claim: the pipelined executor hides host<->device
    transfer behind compute, so per-superstep wall time approaches
    max(compute, transfer) instead of their sum."""
    n = max(int(16_000 * scale), 16 * P)
    workloads = [
        ("pagerank", PageRank(n, iterations=6), 2, 8,
         rmat_graph(n, 10 * n, seed=4), n),
        ("sssp", SSSP(source=0), 1, 12,
         rmat_graph(n, 10 * n, seed=4), n),
        ("cc", ConnectedComponents(), 1, 12,
         rmat_graph(n, 8 * n, seed=11), n),
    ]
    out = {}
    for name, prog, vd, ms, edges, nv in workloads:
        plan = dataclasses.replace(prog.suggested_plan, join="full_outer")
        per_budget = {}
        for budget in (P // 2, P // 4):
            n_sp = P // budget
            times = {}
            for mode, streaming in (("sync", False), ("stream", True)):
                vert = load_graph(edges, nv, P=P, value_dims=vd)
                res = run_out_of_core(vert, prog, plan,
                                      budget_partitions=budget,
                                      max_supersteps=ms,
                                      stream=streaming)
                times[mode] = time_supersteps(res)
                times[f"{mode}_io"] = _io_split(res)
            speedup = times["sync"] / max(times["stream"], 1e-12)
            per_budget[f"super_partitions_{n_sp}"] = {
                "sync_s": times["sync"], "stream_s": times["stream"],
                "speedup": speedup,
                "sync_io": times["sync_io"], "stream_io": times["stream_io"],
            }
            record(f"ooc/stream_{name}_sp{n_sp}", times["stream"] * 1e6,
                   f"sync={times['sync'] * 1e6:.1f}us,"
                   f"speedup={speedup:.2f}x")
        out[name] = per_budget
    best = max((cfg["speedup"] for w in out.values() for cfg in w.values()),
               default=0.0)
    out["best_speedup"] = best
    record("ooc/stream_best_speedup", best,
           "max streaming speedup over the synchronous loop")
    return out


def auto_race(scale: float, P: int = 8):
    """plan='auto' vs representative static plans, out-of-core."""
    n_pr = max(int(16_000 * scale), 16 * P)
    side = max(int(40 * scale ** 0.5), 12)
    workloads = [
        # message-dense, every value changes -> inplace/full_outer regime
        ("pagerank", PageRank(n_pr, iterations=6), 2, 8,
         rmat_graph(n_pr, 10 * n_pr, seed=4), n_pr),
        # high-diameter lattice: frontier + change density collapse ->
        # the left_outer + delta regime the planner must discover
        ("sssp_lattice", SSSP(source=0), 1, 100,
         grid_graph(side), side * side),
    ]
    out = {}
    for name, prog, vd, ms, edges, n in workloads:
        base = prog.suggested_plan
        statics = {
            "suggested": base,
            "merging": dataclasses.replace(
                base, connector="partitioning_merging"),
            "delta": dataclasses.replace(base, storage="delta"),
            "full_outer_inplace": dataclasses.replace(
                base, join="full_outer", storage="inplace"),
        }
        times = {}
        for cname, plan in statics.items():
            vert = load_graph(edges, n, P=P, value_dims=vd)
            res = run_out_of_core(vert, prog, plan,
                                  budget_partitions=P // 2,
                                  max_supersteps=ms)
            times[cname] = time_supersteps(res)
        vert = load_graph(edges, n, P=P, value_dims=vd)
        auto = run_out_of_core(vert, prog, "auto",
                               budget_partitions=P // 2, max_supersteps=ms)
        t_auto = time_supersteps(auto)
        best_name = min(times, key=times.get)
        best = times[best_name]
        switches = [s for s in auto.stats
                    if s.get("event") == "plan-switch"]
        picked_merging = (auto.plan.connector == "partitioning_merging" or
                          any(s.get("connector") == "partitioning_merging"
                              for s in switches))
        picked_delta = (auto.plan.storage == "delta" or
                        any(s.get("storage") == "delta" for s in switches))
        record(f"ooc/auto_{name}", t_auto * 1e6,
               f"vs_best_static({best_name})={t_auto / best:.2f},"
               f"switches={len(switches)},merging={picked_merging},"
               f"delta={picked_delta}")
        out[name] = {"auto": t_auto, "best_static": best,
                     "ratio": t_auto / best, "switches": len(switches),
                     "picked_merging": picked_merging,
                     "picked_delta": picked_delta,
                     "final_plan": dataclasses.asdict(auto.plan)}
    return out


def _tier_stats(res):
    """Mean pager hit rate + total spill traffic of one run."""
    recs = [s for s in res.stats if "cache_hit_rate" in s]
    if not recs:
        return {"hit_rate": 1.0, "spill_read_bytes": 0,
                "spill_write_bytes": 0}
    return {
        "hit_rate": sum(s["cache_hit_rate"] for s in recs) / len(recs),
        "spill_read_bytes": sum(s["spill_read_bytes"] for s in recs),
        "spill_write_bytes": sum(s["spill_write_bytes"] for s in recs),
    }


def disk_tier_race(scale: float, P: int = 8):
    """Part 4 (the disk-tier claim): the DRAM-only store vs the buffer
    cache spilling to disk under a tight memory budget, per eviction
    policy. The spill directory is a tmpdir torn down on exit — success
    OR failure — so CI never leaks page files. Writes the wall times,
    pager hit rates and spill traffic that BENCH_storage.json archives."""
    n = max(int(16_000 * scale), 16 * P)
    edges = rmat_graph(n, 10 * n, seed=4)
    prog = PageRank(n, iterations=6)
    plan = dataclasses.replace(prog.suggested_plan, join="full_outer")
    budget_parts = P // 2

    vert = load_graph(edges, n, P=P, value_dims=2)
    dram = run_out_of_core(vert, prog, plan,
                           budget_partitions=budget_parts,
                           max_supersteps=8)
    t_dram = time_supersteps(dram)
    record("storage/dram_only", t_dram * 1e6, "no disk tier")
    # size the DRAM budget to half the working set so the run must spill
    # (floor low enough that even the --smoke graph actually pages)
    working = sum(int(np.asarray(getattr(vert, k)).nbytes) for k in
                  ("vid", "halt", "value", "edge_src", "edge_dst",
                   "edge_val"))
    budget = max(working // 2, 96 * 1024)
    out = {"dram_only_s": t_dram, "working_set_bytes": working,
           "memory_budget_bytes": budget, "disk": {}}
    for policy in ("lru", "mru"):
        with tempfile.TemporaryDirectory(prefix="pregelix-spill-") as td:
            vert2 = load_graph(edges, n, P=P, value_dims=2)
            res = run_out_of_core(vert2, prog, plan,
                                  budget_partitions=budget_parts,
                                  max_supersteps=8,
                                  memory_budget_bytes=budget,
                                  disk_dir=td, eviction=policy)
            t = time_supersteps(res)
            tier = _tier_stats(res)
            out["disk"][policy] = {
                "wall_s": t, "slowdown_vs_dram": t / max(t_dram, 1e-12),
                **tier}
            record(f"storage/disk_{policy}", t * 1e6,
                   f"hit_rate={tier['hit_rate']:.2f},"
                   f"slowdown={t / max(t_dram, 1e-12):.2f}x")
    return out


def _stall_stats(res):
    """Total + steady-state-mean readiness stall (the device-idle gap
    between a superstep's last collect and the next superstep's first
    dispatch — what the barrier-free pipeline minimizes)."""
    recs = [s for s in res.stats if "readiness_stall_s" in s]
    steady = [s for s in recs if not s.get("recompiled", False)] or recs[1:]
    return {
        "total_s": sum(s["readiness_stall_s"] for s in recs),
        "steady_mean_s": (sum(s["readiness_stall_s"] for s in steady)
                          / max(len(steady), 1)),
    }


def _queue_depth_percentiles(res):
    """I/O queue-depth distribution of a run. Since PR 6 every superstep
    record carries real within-superstep percentiles
    (``io_queue_depth_p50/p90/max`` from the engine's depth histogram);
    report their run-level mean/max. Falls back to percentiles of the
    per-superstep peaks for runs without the engine histogram."""
    recs = [s for s in res.stats
            if "wall_s" in s and "io_queue_depth_p90" in s]
    if recs:
        k = len(recs)
        return {
            "p50": sum(s["io_queue_depth_p50"] for s in recs) / k,
            "p90": sum(s["io_queue_depth_p90"] for s in recs) / k,
            "max": max(s["io_queue_depth_max"] for s in recs),
        }
    depths = sorted(s.get("io_queue_depth", 0) for s in res.stats
                    if "wall_s" in s)
    if not depths:
        return {"p50": 0, "p90": 0, "max": 0}
    pick = lambda f: depths[min(int(f * (len(depths) - 1)), len(depths) - 1)]
    return {"p50": pick(0.5), "p90": pick(0.9), "max": depths[-1]}


def pipeline_race(scale: float, P: int = 8):
    """The PR-5 tentpole claim: removing the inter-superstep barrier
    (per-destination inbox readiness) and moving disk I/O to the
    background engine shortens the serial leg of every superstep.
    Races the PR-4 pipelined executor (stream=True, barrier_free=False)
    against the barrier-free one, in DRAM and on the disk tier (with
    and without the I/O engine), reporting wall times, readiness-stall
    seconds and I/O queue-depth percentiles for BENCH_pipeline.json."""
    n = max(int(64_000 * scale), 24 * P)
    edges = rmat_graph(n, 10 * n, seed=4)
    prog_of = lambda: PageRank(n, iterations=8)
    plan = dataclasses.replace(prog_of().suggested_plan, join="full_outer")
    budget_parts = P // 4 if P >= 4 else 1
    ms = 10

    def leg(name, **kw):
        vert = load_graph(edges, n, P=P, value_dims=2)
        res = run_out_of_core(vert, prog_of(), plan,
                              budget_partitions=budget_parts,
                              max_supersteps=ms, stream=True,
                              prefetch_depth=3, **kw)
        out = {"wall_s": time_supersteps(res),
               "readiness_stall": _stall_stats(res),
               "io_queue_depth": _queue_depth_percentiles(res)}
        record(f"pipeline/{name}", out["wall_s"] * 1e6,
               f"stall={out['readiness_stall']['steady_mean_s'] * 1e6:.1f}"
               f"us/superstep")
        return out

    out = {"n_vertices": n, "super_partitions": P // budget_parts}
    # DRAM tier: isolates the barrier removal alone. Compute dominates
    # here, so the win is the (small) serial rebuild share.
    out["dram"] = {
        "barrier": leg("dram_barrier", barrier_free=False),
        "barrier_free": leg("dram_barrier_free", barrier_free=True),
    }
    out["dram"]["speedup"] = (
        out["dram"]["barrier"]["wall_s"]
        / max(out["dram"]["barrier_free"]["wall_s"], 1e-12))
    record("pipeline/dram_speedup", out["dram"]["speedup"],
           "barrier removal alone (DRAM tier)")
    # DISK tier — the headline race: the PR-4 pipelined executor
    # (global barrier + synchronous page I/O on the dispatcher/collector
    # thread) vs this PR's executor (per-destination readiness + the
    # background I/O engine), under real paging pressure. This is where
    # the two serialization points the PR removes actually bind.
    vert = load_graph(edges, n, P=P, value_dims=2)
    working = sum(int(np.asarray(getattr(vert, k)).nbytes) for k in
                  ("vid", "halt", "value", "edge_src", "edge_dst",
                   "edge_val"))
    budget = max(working // 2, 96 * 1024)
    del vert
    out["disk"] = {"memory_budget_bytes": budget}
    for name, kw in (
            ("barrier_sync_io", dict(barrier_free=False, io_threads=0)),
            ("barrier_free_sync_io", dict(barrier_free=True,
                                          io_threads=0)),
            ("barrier_free_engine", dict(barrier_free=True,
                                         io_threads=1)),
    ):
        with tempfile.TemporaryDirectory(prefix="pregelix-pipe-") as td:
            out["disk"][name] = leg(
                f"disk_{name}", memory_budget_bytes=budget, disk_dir=td,
                eviction="mru", **kw)
    out["disk"]["speedup"] = (
        out["disk"]["barrier_sync_io"]["wall_s"]
        / max(out["disk"]["barrier_free_engine"]["wall_s"], 1e-12))
    out["speedup"] = out["disk"]["speedup"]
    # steady-state means, NOT totals: the first superstep's stall is
    # dominated by the jit compile, which both legs pay equally and
    # which would wash the ratio out to ~1
    out["stall_reduction"] = (
        out["disk"]["barrier_sync_io"]["readiness_stall"]["steady_mean_s"]
        / max(out["disk"]["barrier_free_sync_io"]["readiness_stall"]
              ["steady_mean_s"], 1e-12))
    record("pipeline/speedup", out["speedup"],
           "barrier-free + io engine vs the PR-4 executor "
           "(barrier + sync page io, disk tier)")
    return out


def trace_capture(scale: float, trace_out: str, P: int = 8,
                  report_out: str = None):
    """Traced disk-tier run -> Chrome trace-event JSON artifact.

    A DEDICATED run, separate from every timed leg, so span recording
    never skews the BENCH numbers. Barrier-free pipeline on the disk
    tier with TWO I/O-engine workers and a tight DRAM budget: the trace
    must show the dispatcher/collector main thread plus both
    ``pregelix-io-*`` workers (>= 3 OS threads) with fault / readahead /
    writeback spans overlapping compute and the readiness-stall gap.
    CI validates the artifact with ``python -m repro.obs.export``.

    With ``report_out`` the SAME run also feeds the plan-audit ledger
    and the memory watcher, and a ``pregelix-run-report/v1`` JSON lands
    there — validated with ``python -m repro.obs.report --validate``."""
    from repro.obs import (explain, memwatch, report, trace,
                           write_chrome_trace)
    n = max(int(16_000 * scale), 16 * P)
    edges = rmat_graph(n, 10 * n, seed=4)
    prog = PageRank(n, iterations=6)
    plan = dataclasses.replace(prog.suggested_plan, join="full_outer")
    vert = load_graph(edges, n, P=P, value_dims=2)
    working = sum(int(np.asarray(getattr(vert, k)).nbytes) for k in
                  ("vid", "halt", "value", "edge_src", "edge_dst",
                   "edge_val"))
    # quarter-of-working-set budget: enough paging pressure that the
    # engine's fault/readahead/writeback spans actually appear
    budget = max(working // 4, 64 * 1024)
    trace.start()
    if report_out:
        explain.start()
        memwatch.start()
    res = None
    try:
        with tempfile.TemporaryDirectory(prefix="pregelix-trace-") as td:
            res = run_out_of_core(vert, prog, plan,
                                  budget_partitions=max(P // 4, 1),
                                  max_supersteps=6, stream=True,
                                  barrier_free=True,
                                  memory_budget_bytes=budget,
                                  disk_dir=td,
                                  eviction="mru", io_threads=2)
    finally:
        tracer = trace.stop()
        aud = explain.stop() if report_out else None
        mem = memwatch.stop() if report_out else None
    summary = write_chrome_trace(trace_out, tracer)
    record("obs/trace_spans", summary["spans"],
           f"threads={summary['span_threads']},"
           f"cats={','.join(sorted(summary['categories']))}")
    if report_out and res is not None:
        rep = report.build_report(
            stats=res.stats, explain=aud, memwatch=mem,
            meta={"bench": "trace_capture", "scale": scale,
                  "n_vertices": n, "parts": P,
                  "memory_budget_bytes": budget,
                  "supersteps": res.supersteps,
                  "wall_s": res.wall_s})
        report.write_report(report_out, rep)
        errs = report.validate_report(rep)
        if errs:
            raise SystemExit(f"{report_out}: {len(errs)} schema "
                             f"violation(s): {errs}")
        record("obs/report_supersteps", len(rep["supersteps"]),
               f"mean_drift={rep['summary']['mean_drift']:.3f}")
    return summary


def _steady_exchange(res):
    """Per-superstep (stall_s, bytes) lists, recompile steps dropped —
    same steady-state policy as time_supersteps."""
    recs = [s for s in res.stats
            if "wall_s" in s and not s.get("recompiled", False)]
    if not recs:
        recs = [s for s in res.stats if "wall_s" in s][1:]
    return ([float(s.get("exchange_stall_s", 0.0)) for s in recs],
            [int(s.get("exchange_bytes", 0)) for s in recs])


def sharded_scaling(scale: float, P: int = 8,
                    device_counts=(1, 2, 4, 8)):
    """The ISSUE-8 tentpole curve: the SAME graph raced across mesh
    sizes on the real sharded driver (``run_sharded``: all_to_all
    exchange inside one shard_map'd superstep). Per device count:
    steady-state wall seconds, exchange-stall seconds, all_to_all wire
    bytes, and the planner's predicted exchange seconds — net_scale fit
    on the FIRST half of the measured stalls (the controller's clamp,
    [0.125, 8]), checked against the SECOND half so 'predicted within 2x
    of measured' is a held-out claim, not a tautology."""
    import jax

    from repro.core import run_sharded
    from repro.planner.cost import (EMULATED_MACHINE, GraphStats,
                                    Observation, estimate)

    n = max(int(16_000 * scale), 16 * P)
    edges = rmat_graph(n, 10 * n, seed=4)
    prog = PageRank(n, iterations=6)
    plan = prog.suggested_plan
    avail = len(jax.devices())
    counts = [d for d in device_counts if d <= avail and P % d == 0]
    out = {"n_vertices": n, "P": P, "devices_available": avail,
           "curve": {}}
    g = None
    for N in counts:
        vert = load_graph(edges, n, P=P, value_dims=2)
        if g is None:
            g = GraphStats(
                n_vertices=n,
                n_edges=int((np.asarray(vert.edge_src) >= 0).sum()),
                n_partitions=P,
                vertex_capacity=int(vert.vid.shape[1]),
                edge_capacity=int(vert.edge_src.shape[1]),
                value_dims=prog.value_dims, msg_dims=prog.msg_dims)
        res = run_sharded(vert, prog, plan, devices=N, max_supersteps=8)
        wall = time_supersteps(res)
        stalls, xbytes = _steady_exchange(res)
        mean_stall = float(np.mean(stalls)) if stalls else 0.0
        row = {"devices": N, "wall_s": wall,
               "supersteps": res.supersteps,
               "exchange_stall_s": float(np.sum(stalls)),
               "exchange_stall_mean_s": mean_stall,
               "exchange_bytes": int(np.sum(xbytes))}
        # planner's exchange prediction (net axis) vs the measured span
        obs = Observation(frontier_density=1.0, sharded=N > 1,
                          n_workers=N)
        analytic = estimate(plan, g, obs, EMULATED_MACHINE).net_seconds
        row["analytic_exchange_s"] = analytic
        if N > 1 and analytic > 0 and len(stalls) >= 2:
            half = max(len(stalls) // 2, 1)
            fit = float(np.clip(np.mean(stalls[:half]) / analytic,
                                0.125, 8.0))
            held_out = float(np.mean(stalls[half:]) or mean_stall)
            predicted = analytic * fit
            ratio = predicted / max(held_out, 1e-12)
            row.update(net_scale_fit=fit, predicted_exchange_s=predicted,
                       predicted_over_measured=ratio,
                       within_2x=bool(0.5 <= ratio <= 2.0))
        else:
            row.update(net_scale_fit=1.0, predicted_exchange_s=analytic,
                       predicted_over_measured=None, within_2x=None)
        out["curve"][str(N)] = row
        record(f"sharded/devices_{N}", wall * 1e6,
               f"exchange_stall_s={row['exchange_stall_s']:.4f},"
               f"exchange_MiB={row['exchange_bytes'] / 2**20:.2f}")
    return out


def validate_sharded(payload: dict) -> bool:
    """Schema check for BENCH_sharded.json (CI gate; scalability.py
    reuses it). Raises SystemExit on a malformed artifact."""
    curve = payload.get("curve")
    if not isinstance(curve, dict) or not curve:
        raise SystemExit("BENCH_sharded.json: missing/empty 'curve'")
    need = ("devices", "wall_s", "supersteps", "exchange_stall_s",
            "exchange_bytes", "analytic_exchange_s",
            "predicted_exchange_s")
    for key, row in curve.items():
        for f in need:
            if f not in row:
                raise SystemExit(
                    f"BENCH_sharded.json: curve[{key}] missing '{f}'")
        if not row["wall_s"] > 0:
            raise SystemExit(
                f"BENCH_sharded.json: curve[{key}] wall_s <= 0")
        if row["devices"] > 1 and not row["exchange_bytes"] > 0:
            raise SystemExit(
                f"BENCH_sharded.json: curve[{key}] has {row['devices']} "
                "workers but zero all_to_all wire bytes")
    multi = [r for r in curve.values()
             if r["devices"] > 1 and r.get("within_2x") is not None]
    if multi:
        ok = sum(1 for r in multi if r["within_2x"])
        print(f"sharded: predicted exchange within 2x of measured for "
              f"{ok}/{len(multi)} multi-device points", flush=True)
    return True


def main(scale: float = 1.0, out_path: str = "BENCH_ooc.json",
         disk: bool = False, storage_out: str = "BENCH_storage.json",
         pipeline_out: str = "BENCH_pipeline.json",
         trace_out: str = "BENCH_trace.json",
         sharded: bool = False, sharded_out: str = "BENCH_sharded.json",
         report_out: str = "BENCH_report.json"):
    if sharded:
        sh = {"scale": scale, **sharded_scaling(scale)}
        validate_sharded(sh)
        with open(sharded_out, "w") as f:
            json.dump(sh, f, indent=1)
        walls = {r["devices"]: r["wall_s"] for r in sh["curve"].values()}
        print(f"wrote {sharded_out} (device counts {sorted(walls)}, "
              f"wall_s {', '.join(f'{walls[d]:.4f}' for d in sorted(walls))})",
              flush=True)
        return sh
    out = {"scale": scale}
    out["budget_sweep"] = budget_sweep(scale)
    out["streaming"] = streaming_race(scale)
    out["auto"] = auto_race(scale)
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {out_path} (best streaming speedup "
          f"{out['streaming']['best_speedup']:.2f}x)", flush=True)
    pipe = {"scale": scale, "pipeline": pipeline_race(scale)}
    with open(pipeline_out, "w") as f:
        json.dump(pipe, f, indent=1)
    print(f"wrote {pipeline_out} (barrier-free speedup "
          f"{pipe['pipeline']['speedup']:.2f}x, stall reduction "
          f"{pipe['pipeline']['stall_reduction']:.1f}x)", flush=True)
    if disk:
        st = {"scale": scale, "disk_tier": disk_tier_race(scale)}
        with open(storage_out, "w") as f:
            json.dump(st, f, indent=1)
        hit = max(v["hit_rate"] for v in st["disk_tier"]["disk"].values())
        print(f"wrote {storage_out} (best disk-tier hit rate "
              f"{hit:.2f})", flush=True)
        ts = trace_capture(scale, trace_out, report_out=report_out)
        print(f"wrote {trace_out} ({ts['spans']} spans on "
              f"{ts['span_threads']} threads, categories "
              f"{','.join(sorted(ts['categories']))})", flush=True)
        if report_out:
            print(f"wrote {report_out} (plan-audit + memory-pressure "
                  f"run report from the traced run)", flush=True)
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--out", default="BENCH_ooc.json",
                    help="machine-readable results (CI uploads this)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny config for CI (graph ~800 vertices)")
    ap.add_argument("--disk", action="store_true",
                    help="also race the disk tier (tmpdir spill dir, "
                         "cleaned up even on failure) and write "
                         "--storage-out")
    ap.add_argument("--storage-out", default="BENCH_storage.json",
                    help="disk-tier results (CI uploads this)")
    ap.add_argument("--pipeline-out", default="BENCH_pipeline.json",
                    help="barrier-free vs barrier pipeline race results "
                         "(wall times, readiness-stall seconds, I/O "
                         "queue-depth percentiles; CI uploads this)")
    ap.add_argument("--trace-out", default="BENCH_trace.json",
                    help="Chrome trace-event JSON from a dedicated "
                         "traced disk-tier run (with --disk; CI "
                         "validates and uploads this)")
    ap.add_argument("--sharded", action="store_true",
                    help="race ONLY the multi-device sharded driver "
                         "across 1/2/4/8 host devices and write "
                         "--sharded-out (sets XLA_FLAGS pre-import)")
    ap.add_argument("--sharded-out", default="BENCH_sharded.json",
                    help="sharded scaling curve (CI uploads this)")
    ap.add_argument("--report-out", default="BENCH_report.json",
                    help="pregelix-run-report/v1 JSON from the traced "
                         "disk-tier run (with --disk): plan-audit "
                         "ledger + memory-pressure peaks; CI validates "
                         "with python -m repro.obs.report and uploads "
                         "this. Empty string disables")
    ap.add_argument("--validate-sharded", metavar="PATH", default=None,
                    help="validate an existing BENCH_sharded.json and "
                         "exit (CI gate)")
    args = ap.parse_args()
    if args.validate_sharded:
        with open(args.validate_sharded) as f:
            validate_sharded(json.load(f))
        print(f"{args.validate_sharded}: ok", flush=True)
        raise SystemExit(0)
    main(0.05 if args.smoke else args.scale, args.out,
         disk=args.disk, storage_out=args.storage_out,
         pipeline_out=args.pipeline_out, trace_out=args.trace_out,
         sharded=args.sharded, sharded_out=args.sharded_out,
         report_out=args.report_out)
