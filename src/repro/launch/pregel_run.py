"""Pregelix job launcher: real runs on the host's devices, sharded runs
over a device mesh, and the --dryrun lowering for a production mesh.

    PYTHONPATH=src python -m repro.launch.pregel_run --algo sssp \
        --dataset webmap-tiny --parts 4
"""
import argparse
import json
import math
import os
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import (N_OVERFLOW, EngineConfig, GlobalState, MsgRel,
                        PhysicalPlan, VertexRel, make_superstep)
from repro.graph import SSSP, ConnectedComponents, PageRank
from repro.launch import hlo_cost
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.planner.cost import MACHINES

ALGOS = {
    "pagerank": lambda n: PageRank(n, iterations=15),
    "sssp": lambda n: SSSP(source=0),
    "cc": lambda n: ConnectedComponents(),
}

# graph scale ladder: 'paper-large' is Webmap-Large (1.4B vertices / 8B
# edges); 'bigger-4x' is 4x that — Big(ger) Graph Analytics on a 512-chip
# multi-pod mesh.
GRAPH_SCALES = {
    "paper-large": (1_413_511_390, 8_050_112_169),
    "bigger-4x": (5_654_045_560, 32_200_448_676),
}


def dryrun_capacities(n_vertices: int, n_edges: int, P_total: int):
    """Per-partition vertex/edge slot capacities the dry-run lowers with
    (the load_graph slack factors applied to uniform partitioning)."""
    Np = int(math.ceil(n_vertices / P_total * 1.3)) + 1
    Ep = int(math.ceil(n_edges / P_total * 1.2)) + 1
    return Np, Ep


def abstract_graph_state(n_vertices: int, n_edges: int, P_total: int,
                         program, plan: PhysicalPlan, mesh):
    Np, Ep = dryrun_capacities(n_vertices, n_edges, P_total)
    if plan.sender_combine:
        cap = min(int((Ep / P_total + 8) * 1.5), Np + 8)
    else:
        cap = int((Ep / P_total + 8) * 1.5)
    ec = EngineConfig(n_parts=P_total, bucket_cap=max(cap, 8),
                      frontier_cap=int(Np * plan.frontier_capacity) + 8,
                      axis_name=tuple(mesh.axis_names))
    V, D = program.value_dims, program.msg_dims
    M = P_total * ec.bucket_cap
    sds = jax.ShapeDtypeStruct
    vert = VertexRel(
        vid=sds((P_total, Np), jnp.int32),
        halt=sds((P_total, Np), jnp.bool_),
        value=sds((P_total, Np, V), jnp.float32),
        edge_src=sds((P_total, Ep), jnp.int32),
        edge_dst=sds((P_total, Ep), jnp.int32),
        edge_val=sds((P_total, Ep), jnp.float32))
    msg = MsgRel(dst=sds((P_total, M), jnp.int32),
                 payload=sds((P_total, M, D), jnp.float32),
                 valid=sds((P_total, M), jnp.bool_))
    gs = GlobalState(halt=sds((), jnp.bool_),
                     aggregate=sds((program.agg_dims,), jnp.float32),
                     superstep=sds((), jnp.int32),
                     overflow=sds((N_OVERFLOW,), jnp.int32),
                     active_count=sds((), jnp.int32),
                     msg_count=sds((), jnp.int32))
    return vert, msg, gs, ec


def pregel_dryrun(algo: str, scale: str, mesh_kind: str,
                  plan) -> dict:
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    P_total = mesh.devices.size
    axes = tuple(mesh.axis_names)
    n_v, n_e = GRAPH_SCALES[scale]
    program = ALGOS[algo](n_v)
    if plan == "auto":
        # static choice at superstep-0 statistics (all vertices active);
        # the host drivers re-choose mid-run, the dry-run cannot
        from repro.planner import GraphStats, Observation, choose
        Np, Ep = dryrun_capacities(n_v, n_e, P_total)
        g = GraphStats(n_vertices=n_v, n_edges=n_e, n_partitions=P_total,
                       vertex_capacity=Np, edge_capacity=Ep,
                       value_dims=program.value_dims,
                       msg_dims=program.msg_dims)
        plan, _ = choose(program, g, Observation(frontier_density=1.0))
        print(f"  auto-plan -> join={plan.join} groupby={plan.groupby} "
              f"connector={plan.connector} "
              f"sender_combine={plan.sender_combine}", flush=True)
    vert, msg, gs, ec = abstract_graph_state(n_v, n_e, P_total, program,
                                             plan, mesh)
    step = make_superstep(program, plan, ec)

    part = P(axes)  # partition axis sharded over the whole (multi-pod) mesh
    spec_of = lambda sds_tree, leading: jax.tree.map(
        lambda x: P(*( [leading] + [None] * (len(x.shape) - 1))), sds_tree)
    in_specs = (spec_of(vert, axes), spec_of(msg, axes),
                jax.tree.map(lambda x: P(), gs))
    out_specs = in_specs
    fn = jax.shard_map(step, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)

    t0 = time.time()
    with mesh:
        lowered = jax.jit(fn).lower(vert, msg, gs)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost = hlo_cost.analyze(compiled.as_text())
    # the production mesh is made of TPU v5e chips
    chip = MACHINES["TPU v5 lite"]
    terms = {"compute_s": cost.flops / chip.peak_flops,
             "memory_s": cost.bytes / chip.hbm_bw,
             "collective_s": cost.coll_bytes / chip.link_bw}
    return {
        "arch": f"pregelix-{algo}", "shape": scale, "mesh": mesh_kind,
        "status": "ok", "kind": "superstep", "chips": P_total,
        "plan": dataclass_dict(plan),
        "compile_s": round(time.time() - t0, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "total_per_device_bytes": (mem.argument_size_in_bytes +
                                       mem.temp_size_in_bytes),
        },
        "per_device": {"flops": cost.flops, "bytes": cost.bytes,
                       "collective_bytes": cost.coll_bytes,
                       "collectives": dict(cost.coll_detail)},
        "roofline": {**terms,
                     "dominant": max(terms, key=terms.get),
                     "bound_s": max(terms.values())},
    }


def dataclass_dict(p):
    import dataclasses
    return dataclasses.asdict(p)


def _fake_host_devices(argv) -> None:
    """Give the CPU backend fake devices for a --dryrun (512) or a
    sharded --devices N run, unless the user set their own XLA_FLAGS.
    Must run before JAX initializes its backends."""
    n = 0
    if "--dryrun" in argv:
        n = 512
    elif "--devices" in argv:
        try:
            n = int(argv[argv.index("--devices") + 1])
        except (ValueError, IndexError):
            n = 0
    if n > 1 and "XLA_FLAGS" not in os.environ:
        os.environ["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={n}"


def main():
    _fake_host_devices(sys.argv)
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun", action="store_true")
    ap.add_argument("--algo", default="pagerank", choices=list(ALGOS))
    ap.add_argument("--scale", default="paper-large",
                    choices=list(GRAPH_SCALES))
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both", "host",
                             "production"],
                    help="--dryrun: single|multi|both pod lowering. "
                         "Real runs: host = 1-D mesh over the host's "
                         "devices (see --devices), production = the "
                         "(16,16) pod mesh; both select the sharded "
                         "multi-device driver (core/sharded.py)")
    ap.add_argument("--devices", type=int, default=0,
                    help="run the real (non-dryrun) job SHARDED over this "
                         "many devices via run_sharded: supersteps "
                         "execute under shard_map with the bucket "
                         "exchange as a jax.lax.all_to_all. On CPU the "
                         "launcher fakes the device count via XLA_FLAGS "
                         "automatically; composes with --ooc for "
                         "per-worker tiered stores")
    ap.add_argument("--join", default="full_outer")
    ap.add_argument("--groupby", default="scatter")
    ap.add_argument("--connector", default="partitioning")
    ap.add_argument("--sender-combine", type=int, default=1)
    ap.add_argument("--partition", default="hash", choices=["hash","range"])
    ap.add_argument("--kernel-impl", default="auto",
                    choices=["auto", "ref", "pallas", "pallas_tpu"],
                    help="superstep hot-path kernel dispatch "
                         "(kernels/backend.py): auto resolves per backend "
                         "(compiled Pallas on TPU, jnp reference "
                         "elsewhere); pallas forces the kernels "
                         "(interpret mode off-TPU); ref forces the jnp "
                         "path. With --auto-plan the planner prices both "
                         "and the chosen plan carries the winner")
    ap.add_argument("--auto-plan", action="store_true",
                    help="let the cost-based planner pick (and, in the "
                         "real-run mode, mid-run re-pick) the plan")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--out", default="results/dryrun")
    # non-dryrun demo mode
    ap.add_argument("--dataset", default="webmap-tiny")
    ap.add_argument("--parts", type=int, default=4)
    ap.add_argument("--ooc", action="store_true",
                    help="run out-of-core: stream super-partitions "
                         "through the device within --budget-partitions")
    ap.add_argument("--budget-partitions", type=int, default=0,
                    help="device-memory budget in partitions for --ooc "
                         "(default: parts // 2)")
    ap.add_argument("--stream", dest="stream", action="store_true",
                    default=True,
                    help="pipeline the --ooc super-partition stream: "
                         "prefetch the next upload and drain the previous "
                         "result while the current one computes (default)")
    ap.add_argument("--no-stream", dest="stream", action="store_false",
                    help="synchronous --ooc loop: upload, step, block, "
                         "collect per super-partition")
    ap.add_argument("--barrier-free", dest="barrier_free",
                    action="store_true", default=True,
                    help="barrier-free superstep pipeline (default): "
                         "rebuild each destination's inbox chunk and "
                         "apply its mutations per-destination, "
                         "overlapped with the next superstep's compute "
                         "— no global inter-superstep barrier")
    ap.add_argument("--no-barrier-free", dest="barrier_free",
                    action="store_false",
                    help="keep the global superstep barrier (the PR-4 "
                         "executor): full inbox rebuild + mutation "
                         "apply between supersteps")
    ap.add_argument("--io-threads", type=int, default=None,
                    help="background page-I/O engine worker threads for "
                         "the --ooc disk tier (default: 1 when "
                         "--disk-dir is set, else 0); readahead of the "
                         "next destination's pages + coalesced "
                         "dirty-page drain off the critical path")
    ap.add_argument("--readahead-pages", type=int, default=8,
                    help="max pages the I/O engine prefetches per "
                         "dispatch tick (disk tier only)")
    ap.add_argument("--disk-dir", default=None,
                    help="--ooc disk tier: spill directory for the "
                         "buffer cache's page files (enables the "
                         "HBM <-> DRAM <-> disk hierarchy)")
    ap.add_argument("--memory-budget-bytes", type=int, default=None,
                    help="--ooc disk tier: host-DRAM byte budget for "
                         "the page cache (requires --disk-dir); cold "
                         "pages spill to disk and fault back on access")
    ap.add_argument("--eviction", default="lru", choices=["lru", "mru"],
                    help="--ooc disk tier page-replacement policy: lru, "
                         "or mru (resists the superstep's cyclic "
                         "sequential scan)")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="snapshot the run every N supersteps into "
                         "--checkpoint-dir (required with --recover so "
                         "a failure has something to restore)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="directory for checkpoints (npz for host/"
                         "sharded, hard-linked page snapshots for --ooc)")
    ap.add_argument("--recover", action="store_true",
                    help="run under the failure manager's recovery "
                         "supervisor: recoverable failures (worker loss, "
                         "disk I/O, page/checkpoint corruption) restore "
                         "the latest VALID checkpoint onto the surviving "
                         "workers and replay; application errors forward")
    ap.add_argument("--max-retries", type=int, default=3,
                    help="recovery attempts before the failure is "
                         "forwarded (default 3); also the per-worker "
                         "recoverable-failure budget before blacklisting")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record a span timeline of the run and write it "
                         "as Chrome trace-event JSON to PATH (load in "
                         "chrome://tracing or https://ui.perfetto.dev)")
    ap.add_argument("--progress", action="store_true",
                    help="print one human-readable line per superstep "
                         "(active frontier, messages, wall, cache hit "
                         "rate, readiness stall, current plan)")
    ap.add_argument("--metrics", action="store_true",
                    help="print the per-superstep metrics registry "
                         "snapshots (counters / gauges / histogram "
                         "percentiles) collected in SuperstepStats")
    ap.add_argument("--report", default=None, metavar="PATH",
                    help="write a schema-validated run report "
                         "(pregelix-run-report/v1 JSON) to PATH: the "
                         "per-superstep predicted-vs-measured plan audit, "
                         "controller decision log, and HBM/DRAM/SSD tier "
                         "occupancy peaks; validate or diff with "
                         "python -m repro.obs.report")
    ap.add_argument("--explain", action="store_true",
                    help="print the plan-audit ledger after the run: one "
                         "row per superstep with the chosen plan's "
                         "predicted cost terms next to the measured leg "
                         "times and a log-ratio drift score, plus every "
                         "replan/recalibrate decision with the candidate "
                         "price table it was made from")
    args = ap.parse_args()

    plan = "auto" if args.auto_plan else PhysicalPlan(
        join=args.join, groupby=args.groupby,
        connector=args.connector,
        sender_combine=bool(args.sender_combine),
        partition=args.partition,
        kernel_impl=args.kernel_impl)
    if args.dryrun:
        out_dir = Path(args.out)
        out_dir.mkdir(parents=True, exist_ok=True)
        meshes = (["single", "multi"] if args.mesh == "both"
                  else [args.mesh])
        failed = 0
        for mk in meshes:
            name = f"{args.tag}_pregelix-{args.algo}_{args.scale}_{mk}.json"
            print(f"[pregel-dryrun] {args.algo} x {args.scale} x {mk}",
                  flush=True)
            try:
                rec = pregel_dryrun(args.algo, args.scale, mk, plan)
            except Exception as e:  # noqa: BLE001
                import traceback
                rec = {"status": "error", "error": repr(e),
                       "traceback": traceback.format_exc()[-3000:]}
            (out_dir / name).write_text(json.dumps(rec, indent=1))
            if rec["status"] == "ok":
                r = rec["roofline"]
                print(f"  ok compile={rec['compile_s']}s "
                      f"mem/dev={rec['memory']['total_per_device_bytes']/2**30:.2f}GiB "
                      f"dominant={r['dominant']}", flush=True)
            else:
                failed += 1
                print("  error:", rec["error"][:200], flush=True)
        if failed:
            raise SystemExit(f"[pregel-dryrun] {failed} of {len(meshes)} "
                             "lowerings failed")
        return

    # small-scale real run (CPU demo)
    import numpy as np
    from repro.core import gather_values, load_graph, run_host
    from repro.graph import DATASETS
    from repro.obs import (explain, fmt_plan, memwatch, progress_line,
                           report, trace, write_chrome_trace)
    edges, n = DATASETS[args.dataset]()
    program = ALGOS[args.algo](n)
    vert = load_graph(edges, n, P=args.parts,
                      value_dims=program.value_dims)
    from repro.runtime import faults
    faults.install_from_env()   # REPRO_FAULT_PLAN: chaos harness
    if args.recover and not args.checkpoint_dir:
        ap.error("--recover needs --checkpoint-dir (and a nonzero "
                 "--checkpoint-every) so a failure has a snapshot "
                 "to restore")
    ft_kw = dict(checkpoint_every=args.checkpoint_every,
                 checkpoint_dir=args.checkpoint_dir,
                 recover=args.recover, max_retries=args.max_retries)
    if args.trace:
        trace.start()
    if args.report or args.explain:
        explain.start()
        memwatch.start()
    show = None
    if args.progress:
        plan_tag = None if plan == "auto" else plan

        def show(i, rec):
            print(progress_line(rec, plan_tag, n_vertices=n), flush=True)
    sharded = args.devices > 1 or args.mesh in ("host", "production")
    if sharded:
        from repro.core.sharded import run_sharded
        from repro.launch.mesh import make_host_mesh
        mesh = (make_production_mesh() if args.mesh == "production"
                else make_host_mesh(args.devices or None))
        n_dev = int(mesh.devices.size)
        kimp = (args.kernel_impl if args.auto_plan
                and args.kernel_impl != "auto" else None)
        ooc_kw = {}
        tier = ""
        if args.ooc:
            per_worker = args.parts // n_dev
            budget = args.budget_partitions
            if budget and per_worker % budget:
                ap.error(f"--budget-partitions {budget} must divide the "
                         f"per-worker block {per_worker} "
                         f"(--parts {args.parts} / {n_dev} devices)")
            if not budget:
                budget = next(b for b in
                              range(max(per_worker // 2, 1), 0, -1)
                              if per_worker % b == 0)
            if args.memory_budget_bytes and not args.disk_dir:
                ap.error("--memory-budget-bytes requires --disk-dir "
                         "(a budget needs somewhere to spill)")
            ooc_kw = dict(budget_partitions=budget,
                          disk_dir=args.disk_dir,
                          memory_budget_bytes=args.memory_budget_bytes,
                          io_threads=args.io_threads,
                          readahead_pages=args.readahead_pages,
                          eviction=args.eviction)
            tier = (f", ooc budget={budget}/{per_worker} per worker" +
                    (f", disk tier at {args.disk_dir}/worker*"
                     f" [{args.eviction}]" if args.disk_dir else ""))
        if args.ooc and (args.checkpoint_every or args.recover):
            # sharded npz checkpointing is in-memory mode only; recover
            # without checkpoints would only restart from scratch
            ft_kw = dict(recover=args.recover,
                         max_retries=args.max_retries)
        res = run_sharded(vert, program, plan, mesh=mesh,
                          max_supersteps=40, kernel_impl=kimp,
                          on_superstep=show, **ooc_kw, **ft_kw)
        mode = f"sharded x{n_dev} devices{tier}"
        ex = [s for s in res.stats if "exchange_stall_s" in s]
        if ex:
            print(f"exchange: {sum(s['exchange_stall_s'] for s in ex):.3f}s "
                  f"stall, "
                  f"{sum(s['exchange_bytes'] for s in ex) / 2**20:.1f} MiB "
                  f"over {len(ex)} supersteps on {n_dev} workers")
    elif args.ooc:
        from repro.core.ooc import run_out_of_core
        budget = args.budget_partitions
        if budget and args.parts % budget:
            ap.error(f"--budget-partitions {budget} must divide "
                     f"--parts {args.parts}")
        if not budget:   # largest divisor of parts that is <= parts // 2
            budget = next(b for b in range(max(args.parts // 2, 1), 0, -1)
                          if args.parts % b == 0)
        if args.memory_budget_bytes and not args.disk_dir:
            ap.error("--memory-budget-bytes requires --disk-dir "
                     "(a budget needs somewhere to spill)")
        # pin the kernel dispatch inside the auto-planner's search space
        # (a concrete plan already carries it from the CLI knob)
        kimp = (args.kernel_impl if args.auto_plan
                and args.kernel_impl != "auto" else None)
        res = run_out_of_core(vert, program, plan,
                              budget_partitions=budget, max_supersteps=40,
                              kernel_impl=kimp,
                              stream=args.stream,
                              barrier_free=args.barrier_free,
                              memory_budget_bytes=args.memory_budget_bytes,
                              disk_dir=args.disk_dir,
                              eviction=args.eviction,
                              io_threads=args.io_threads,
                              readahead_pages=args.readahead_pages,
                              on_superstep=show, **ft_kw)
        tier = (f", disk tier at {args.disk_dir} "
                f"[{args.eviction}]" if args.disk_dir else "")
        exe = ("synchronous" if not args.stream else
               "barrier-free" if args.barrier_free else "streaming")
        mode = (f"out-of-core (budget={budget}/{args.parts} partitions, "
                f"{exe}{tier})")
    else:
        host_cb = ((lambda i, v, m, g, rec: show(i, rec))
                   if show is not None else None)
        kimp = (args.kernel_impl if args.auto_plan
                and args.kernel_impl != "auto" else None)
        res = run_host(vert, program, plan, max_supersteps=40,
                       kernel_impl=kimp, on_superstep=host_cb, **ft_kw)
        mode = "in-memory"
    vals = gather_values(res.vertex, n)
    print(f"{args.algo} on {args.dataset} [{mode}]: "
          f"{res.supersteps} supersteps, {res.wall_s:.2f}s wall")
    for ev in getattr(res, "recovery", ()) or ():
        print(f"recovery #{ev.get('attempt')}: restored from "
              f"{ev.get('restored_from') or 'initial relations'} onto "
              f"{ev.get('healthy_workers')} worker(s) "
              f"(blacklist {ev.get('blacklist') or '[]'}) after "
              f"{ev.get('error')}")
    if args.ooc and args.disk_dir:
        recs = [s for s in res.stats if "cache_hit_rate" in s]
        if recs:
            hr = sum(s["cache_hit_rate"] for s in recs) / len(recs)
            sb = sum(s["spill_read_bytes"] + s["spill_write_bytes"]
                     for s in recs)
            qd = max((s.get("io_queue_depth", 0) for s in recs),
                     default=0)
            print(f"disk tier: mean page hit rate {hr:.2f}, "
                  f"{sb / 2**20:.1f} MiB spilled, "
                  f"io queue depth peak {qd}")
    if args.ooc:
        recs = [s for s in res.stats if "readiness_stall_s" in s]
        if recs:
            stall = sum(s["readiness_stall_s"] for s in recs)
            print(f"readiness stall: {stall:.3f}s total over "
                  f"{len(recs)} supersteps "
                  f"({'barrier-free' if args.barrier_free and args.stream else 'barrier'})")
    if args.auto_plan:
        switches = [s for s in res.stats
                    if s.get("event") == "plan-switch"]
        print(f"final plan: join={res.plan.join} "
              f"groupby={res.plan.groupby} "
              f"connector={res.plan.connector} "
              f"sender_combine={res.plan.sender_combine} "
              f"storage={res.plan.storage}; "
              f"{len(switches)} plan switch(es)")
        for s in switches:
            print(f"  superstep {s['superstep']}: -> join={s['join']} "
                  f"connector={s['connector']} "
                  f"sender_combine={s['sender_combine']} "
                  f"storage={s.get('storage', '-')}")
    print("per-superstep:", [round(s['wall_s'], 3) for s in res.stats
                             if 'wall_s' in s])
    if args.metrics:
        for s in res.stats:
            m = s.get("metrics")
            if not m:
                continue
            print(f"metrics @ superstep {s.get('superstep', '?')}:")
            for name in sorted(m):
                snap = m[name]
                if isinstance(snap, dict):   # histogram percentiles
                    body = "  ".join(
                        f"{k}={v:.4g}" for k, v in snap.items())
                else:
                    body = f"{snap:.6g}"
                print(f"  {name:<22} {body}")
    if args.report or args.explain:
        aud = explain.stop()
        mem = memwatch.stop()
        rep = report.build_report(
            stats=res.stats, explain=aud, memwatch=mem,
            recovery=getattr(res, "recovery", None),
            meta={"algo": args.algo, "dataset": args.dataset,
                  "mode": mode, "parts": args.parts,
                  "plan": fmt_plan(res.plan),
                  "supersteps": res.supersteps,
                  "wall_s": res.wall_s})
        if args.explain:
            print(report.to_markdown(rep))
        if args.report:
            report.write_report(args.report, rep)
            errs = report.validate_report(rep)
            print(f"report: {args.report} "
                  f"({len(rep['supersteps'])} supersteps, "
                  f"{len(rep['decisions'])} decisions, "
                  f"{len(errs)} schema violation(s))")
    if args.trace:
        tracer = trace.stop()
        summary = write_chrome_trace(args.trace, tracer)
        print(f"trace: {args.trace} "
              f"({summary['spans']} spans on "
              f"{summary['span_threads']} thread(s); load in "
              f"chrome://tracing or ui.perfetto.dev)")
    print("value head:", vals[:5, 0])


if __name__ == "__main__":
    main()
