"""Job drivers.

* ``run_jit``  — whole computation as one ``lax.while_loop`` (fastest;
                 fixed capacities; overflow aborts via GS flag).
* ``run_host`` — Python superstep loop around the jitted superstep: this is
                 the driver that can checkpoint at superstep boundaries
                 (paper Section 5.5), collect per-superstep statistics
                 (Section 5.7 statistics collector), and transparently GROW
                 message capacity on overflow by re-running the superstep
                 from the retained previous state (the static-shape
                 analogue of an operator spilling to disk).
* ``run_out_of_core`` — lives in core/ooc.py.
"""
from __future__ import annotations

import dataclasses
import sys
import time
from dataclasses import dataclass, field
from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.plan import FRONTIER_FLOOR, PhysicalPlan
from repro.core.program import VertexProgram
from repro.core.relations import (OVF_BUCKET, OVF_EDGE, OVF_FRONTIER,
                                  OVF_MUTATION, GlobalState, MsgRel,
                                  VertexRel, empty_msgs, init_gs,
                                  out_degrees)
from repro.core.superstep import EngineConfig, make_superstep
from repro.kernels import backend as kbackend
from repro.obs import explain, memwatch, trace
from repro.obs.metrics import MetricsRegistry

PlanArg = Union[PhysicalPlan, str]   # a PhysicalPlan or the string "auto"

# The in-memory drivers' host legs, each traced as a ``pregel.<leg>``
# span on the profiler's clock: leg -> ``repro.obs.trace`` category.
HOST_LEGS = {"dispatch": "dispatch", "wait": "compute",
             "readback": "collect", "exchange": "exchange",
             "regrow": "replan", "replan": "replan", "refit": "replan",
             "checkpoint": "checkpoint", "callback": "dispatch"}


def host_leg(leg: str, superstep: int, **args):
    """The ``trace.annotate`` span of one host leg of a superstep, tagged
    with the number that superstep's stats record carries."""
    return trace.annotate("pregel." + leg, HOST_LEGS[leg],
                          superstep=superstep, **args)


def apply_kernel_impl(plan: PlanArg, kernel_impl: Optional[str],
                      auto_space: Optional[dict]):
    """Thread a driver-level ``kernel_impl`` override into either a
    concrete plan (replace the field) or the "auto" search space (pin the
    kernel_impls dimension so the initial choice AND every mid-run switch
    carry it)."""
    if kernel_impl is None:
        return plan, auto_space
    if isinstance(plan, PhysicalPlan):
        return dataclasses.replace(plan, kernel_impl=kernel_impl), \
            auto_space
    auto_space = dict(auto_space or {})
    auto_space.setdefault("kernel_impls", (kernel_impl,))
    return plan, auto_space


def plan_gather_layout(plan: PhysicalPlan, vert: VertexRel):
    """Device-resident gather layout for the kernel path, or None when the
    resolved plan doesn't consume one. Depends only on edge_src (which the
    engine never rewrites — mutations touch edge_dst/edge_val), so one
    layout serves a whole run; recompute only on plan switches. Records
    the ``gather.items`` and ``gather.tiles`` counters: their ratio says
    how often a tile of the edge stream straddles a row block."""
    if not kbackend.wants_edge_layout(plan):
        return None
    layout = kbackend.plan_edge_layout(np.asarray(vert.edge_src),
                                       vert.capacity)
    items, tiles = kbackend.edge_layout_counts(layout, vert.num_partitions,
                                               vert.capacity)
    trace.counter("gather.items", items)
    trace.counter("gather.tiles", tiles)
    print(f"gather layout: {items} items over {tiles} tiles "
          f"({items / tiles:.4f} a tile)", file=sys.stderr, flush=True)
    return jnp.asarray(layout)


@dataclass
class RunResult:
    vertex: VertexRel
    gs: GlobalState
    supersteps: int
    stats: list = field(default_factory=list)
    wall_s: float = 0.0
    plan: Optional[PhysicalPlan] = None   # plan in effect at the end
    recovery: list = field(default_factory=list)  # supervisor events


def _resolve_plan(vert, program, plan: PlanArg, *, adaptive: bool,
                  auto_config=None, auto_space=None, graph_stats=None,
                  machine=None, obs0=None):
    """plan="auto" -> (cost-model-chosen plan, AdaptiveController|None).
    `graph_stats` short-circuits the vertex scan (the OOC resume path
    rebuilds the counts page-at-a-time and never holds a VertexRel).
    `machine` defaults to the ``MACHINES`` entry of the device JAX runs
    on; `obs0` seeds the initial observation (sharded=True / n_workers
    for the network axis)."""
    if isinstance(plan, PhysicalPlan):
        return plan, None
    if plan != "auto":
        raise ValueError(f"plan must be a PhysicalPlan or 'auto', "
                         f"got {plan!r}")
    from repro.planner import (AdaptiveConfig, machine_for,
                               resolve_auto_plan)
    config = auto_config or AdaptiveConfig()
    if machine is None:
        machine = machine_for()
    if config.calibrate:
        # one-shot startup calibration (opt-in): lower a probe superstep
        # per backend and refit the analytic cost constants against the
        # trip-count-aware HLO analyzer instead of trusting the
        # hand-tuned K_COMPUTE / K_SCATTER / SORT_PASS_FRAC
        from repro.planner.cost import GraphStats, calibrate_machine
        machine = calibrate_machine(
            program, graph_stats or GraphStats.from_vertex(vert, program),
            machine)
    return resolve_auto_plan(
        vert, program, adaptive=adaptive, config=config,
        machine=machine, space_kw=auto_space, g=graph_stats, obs0=obs0)


def default_engine_config(vert: VertexRel, program: VertexProgram,
                          plan: PhysicalPlan, *, slack: float = 1.5,
                          axis_name=None) -> EngineConfig:
    from repro.core.plan import bucket_capacity
    P, Np = vert.vid.shape
    Ep = vert.edge_src.shape[1]
    return EngineConfig(n_parts=P,
                        bucket_cap=bucket_capacity(plan, Ep, Np, P,
                                                   slack=slack),
                        frontier_cap=int(Np * plan.frontier_capacity) + 8,
                        axis_name=axis_name)


def init_vertex_values(vert: VertexRel, program: VertexProgram,
                       gs: GlobalState) -> VertexRel:
    deg = out_degrees(vert)
    value = program.init_value(vert.vid, deg, gs)
    return dataclasses.replace(vert, value=jnp.where(
        (vert.vid >= 0)[..., None], value, 0.0))


def grow_overflowed(ec: EngineConfig, delta, *,
                    vertex_capacity: int = 0) -> EngineConfig:
    """Grow only the capacities whose per-source overflow counter grew
    (`delta` = the GlobalState.overflow increase of the failed step), to
    at least double and at least what the dropped tuples need: every
    partition dropped no more than the total, so old + total always fits
    the redo, and one overflow costs one recompile, not one per doubling.
    Edge-stream overflow is attributed to the frontier: the edge
    compaction capacity is derived from frontier_cap (EF = 8 *
    frontier_cap in gen_messages). A frontier_cap of 0 (the "Np/2"
    EngineConfig default) is resolved against `vertex_capacity` first so
    the growth cannot wedge at 0."""
    delta = np.asarray(delta)
    kw = {}
    if delta[OVF_BUCKET] > 0:
        kw["bucket_cap"] = max(ec.bucket_cap * 2,
                               ec.bucket_cap + int(delta[OVF_BUCKET]))
    if delta[OVF_FRONTIER] > 0 or delta[OVF_EDGE] > 0:
        cur = ec.frontier_cap or max(vertex_capacity // 2, 1)
        edges = max(cur * 8, 64) + int(delta[OVF_EDGE])
        kw["frontier_cap"] = max(cur * 2, cur + int(delta[OVF_FRONTIER]),
                                 -(-edges // 8))
    if delta[OVF_MUTATION] > 0:
        kw["mutation_cap"] = max(ec.mutation_cap * 2,
                                 ec.mutation_cap + int(delta[OVF_MUTATION]))
    return dataclasses.replace(ec, **kw)


def run_jit(vert: VertexRel, program: VertexProgram,
            plan: PlanArg = PhysicalPlan(), *,
            max_supersteps: int = 50,
            ec: Optional[EngineConfig] = None,
            kernel_impl: Optional[str] = None) -> RunResult:
    t0 = time.time()
    # "auto" resolves once up front (whole-loop jit: no mid-run switching)
    plan, _ = _resolve_plan(vert, program, plan, adaptive=False)
    if kernel_impl is not None:
        plan = dataclasses.replace(plan, kernel_impl=kernel_impl)
    ec = ec or default_engine_config(vert, program, plan)
    step = make_superstep(program, plan, ec)
    layout = plan_gather_layout(plan, vert)
    gs = init_gs(program.agg_dims)
    vert = init_vertex_values(vert, program, gs)
    msg = empty_msgs(vert.num_partitions, ec.n_parts * ec.bucket_cap,
                     program.msg_dims)

    def cond(state):
        v, m, g = state
        return (~g.halt) & (g.superstep < max_supersteps) & \
            jnp.all(g.overflow == 0)

    def body(state):
        return step(*state, None, layout)

    v, m, g = jax.jit(
        lambda s: jax.lax.while_loop(cond, body, s))((vert, msg, gs))
    jax.block_until_ready(g.superstep)
    if int(np.asarray(g.overflow).sum()) > 0:
        raise RuntimeError(
            f"capacity overflow (bucket/frontier/mutation/edge = "
            f"{np.asarray(g.overflow).tolist()} dropped); "
            "use run_host (auto-grows) or raise the capacities")
    return RunResult(vertex=v, gs=g, supersteps=int(g.superstep),
                     wall_s=time.time() - t0, plan=plan)


def run_host(vert: VertexRel, program: VertexProgram,
             plan: PlanArg = PhysicalPlan(), *,
             max_supersteps: int = 50,
             ec: Optional[EngineConfig] = None,
             checkpoint_every: int = 0,
             checkpoint_dir: Optional[str] = None,
             resume_from: Optional[str] = None,
             resume_parts: Optional[int] = None,
             recover: bool = False,
             max_retries: int = 3,
             on_superstep: Optional[Callable] = None,
             failure_injector: Optional[Callable] = None,
             auto_config=None,
             auto_space: Optional[dict] = None,
             kernel_impl: Optional[str] = None) -> RunResult:
    """Host-loop driver with statistics, checkpointing, capacity growth and
    (for tests) failure injection. plan="auto" turns on the cost-based
    planner: the initial plan is chosen for superstep 0's all-active
    frontier and re-chosen at superstep boundaries as observed frontier
    density crosses the model's thresholds (planner.adaptive).

    ``resume_from=<ckpt npz>`` restarts from a checkpoint (optionally
    re-hashed onto ``resume_parts`` partitions — the elastic restore).
    ``recover=True`` runs the whole job under the failure manager's
    recovery supervisor: a recoverable failure (WorkerFailure, disk
    I/O, typed corruption) restores the latest VALID checkpoint onto
    the surviving partitions and replays; application errors forward."""
    from repro.planner.stats import StatsCollector
    from repro.runtime import faults
    from repro.runtime.checkpoint import save_checkpoint

    if recover:
        from repro.runtime.checkpoint import latest_checkpoint
        from repro.runtime.failure import supervised_run
        P0 = vert.num_partitions

        def _attempt(healthy, resume):
            return run_host(
                vert, program, plan, max_supersteps=max_supersteps,
                ec=ec, checkpoint_every=checkpoint_every,
                checkpoint_dir=checkpoint_dir, resume_from=resume,
                resume_parts=(healthy if resume is not None
                              and healthy < P0 else None),
                recover=False, on_superstep=on_superstep,
                failure_injector=failure_injector,
                auto_config=auto_config, auto_space=auto_space,
                kernel_impl=kernel_impl)

        def _pick(bad):
            if not checkpoint_dir:
                return None
            return latest_checkpoint(checkpoint_dir, skip=bad,
                                     verify=True)

        return supervised_run(_attempt, _pick, n_workers=P0,
                              max_retries=max_retries,
                              initial_resume=resume_from)

    t0 = time.time()
    i0, rmsg, rgs = 0, None, None
    if resume_from is not None:
        from repro.runtime.checkpoint import load_checkpoint, repartition
        vert, rmsg, rgs = load_checkpoint(resume_from)
        if resume_parts is not None \
                and resume_parts != vert.num_partitions:
            vert, rmsg = repartition(vert, rmsg, resume_parts)
        i0 = int(rgs.superstep)
    plan, auto_space = apply_kernel_impl(plan, kernel_impl, auto_space)
    plan, controller = _resolve_plan(vert, program, plan, adaptive=True,
                                     auto_config=auto_config,
                                     auto_space=auto_space)
    ec = ec or default_engine_config(vert, program, plan)
    if rmsg is not None and rmsg.capacity > ec.n_parts * ec.bucket_cap:
        # the checkpointed inbox is wider than the derived config (it
        # grew mid-run): adopt its capacity instead of truncating it
        ec = dataclasses.replace(
            ec, bucket_cap=-(-rmsg.capacity // ec.n_parts))
    if explain.enabled():
        # plan-audit ledger: bind the run context so each superstep's
        # stats record can be re-priced under the in-effect plan
        from repro.planner.cost import machine_for
        explain.attach(
            program, vert=vert,
            g=controller.g if controller is not None else None,
            plan=plan,
            machine=(controller.machine if controller is not None
                     else machine_for()),
            space_kw=auto_space)
    step = jax.jit(make_superstep(program, plan, ec))
    layout = plan_gather_layout(plan, vert)
    if rgs is not None:
        gs, msg = rgs, _regrow_msgs(rmsg, ec)
    else:
        gs = init_gs(program.agg_dims)
        vert = init_vertex_values(vert, program, gs)
        msg = empty_msgs(vert.num_partitions, ec.n_parts * ec.bucket_cap,
                         program.msg_dims)
    n_live = (controller.g.n_vertices if controller is not None
              else int(jnp.sum(vert.vid >= 0)))
    metrics = MetricsRegistry()
    coll = StatsCollector(n_partitions=vert.num_partitions,
                          vertex_capacity=vert.capacity,
                          msg_dims=program.msg_dims, n_vertices=n_live,
                          metrics=metrics)
    m_regrows = metrics.counter("host.regrows")
    m_switches = metrics.counter("host.plan_switches")
    stats = []
    i = i0
    recompiled = True  # first step includes the jit compile
    while i < max_supersteps:
        faults.superstep_tick(i, "host")
        ts = time.time()
        this_recompiled = recompiled
        recompiled = False
        prev = (vert, msg, gs)
        # a recompile (after a regrow, replan or refit) lands in the
        # next ``pregel.dispatch``
        n = i + 1
        with host_leg("dispatch", n):
            vert2, msg2, gs2 = step(vert, msg, gs, None, layout)
        with host_leg("wait", n):
            jax.block_until_ready(gs2.superstep)
        with host_leg("readback", n):
            ovf_delta = np.asarray(gs2.overflow) - np.asarray(gs.overflow)
        if (ovf_delta > 0).any():
            # grow ONLY the overflowed capacities x2 and REDO this
            # superstep from `prev` (per-source counters keep a frontier
            # overflow from dragging the bucket tensors along)
            with host_leg("regrow", n):
                ec = grow_overflowed(ec, ovf_delta,
                                     vertex_capacity=vert.capacity)
                step = jax.jit(make_superstep(program, plan, ec))
                vert, msg, gs = prev
                msg = _regrow_msgs(msg, ec)
            stats.append(coll.event(
                i, "regrow", bucket_cap=ec.bucket_cap,
                frontier_cap=ec.frontier_cap,
                mutation_cap=ec.mutation_cap,
                sources=np.flatnonzero(ovf_delta > 0).tolist()).as_dict())
            m_regrows.inc()
            recompiled = True
            if controller is not None:
                controller.note_shape_change()
            continue
        vert, msg, gs = vert2, msg2, gs2
        i += 1
        with host_leg("readback", n):
            rec = coll.record(i, active=int(gs.active_count),
                              messages=int(gs.msg_count),
                              wall_s=time.time() - ts,
                              recompiled=this_recompiled)
        stats.append(rec.as_dict())
        if explain.enabled():
            # audit the plan that EXECUTED this superstep (a switch
            # below only affects the next one)
            explain.superstep(rec, plan=plan, bucket_cap=ec.bucket_cap)
        if memwatch.enabled():
            memwatch.configure(ec=ec, Np=vert.capacity,
                               Ep=vert.edge_src.shape[1],
                               value_dims=program.value_dims,
                               msg_dims=program.msg_dims)
            memwatch.sample(i)
        switched = False
        if controller is not None and not bool(gs.halt):
            # mid-run replanning: switch the physical plan when observed
            # frontier density pushes another plan below the current one
            with host_leg("replan", n):
                new_plan = controller.observe(rec,
                                              bucket_cap=ec.bucket_cap)
                if new_plan is not None:
                    from repro.planner import migrate_msgs
                    msg = migrate_msgs(msg, plan, new_plan, ec.n_parts)
                    plan = new_plan
                    if plan.join == "left_outer":
                        act = int(gs.active_count) // \
                            max(vert.num_partitions, 1) + 1
                        ec = dataclasses.replace(
                            ec, frontier_cap=min(max(FRONTIER_FLOOR, act * 4),
                                                 vert.capacity + 8))
                    # dropping the sender combine needs room for uncombined
                    # sends: grow the buckets now instead of paying an
                    # overflow-redo on the next superstep
                    need = default_engine_config(vert, program, plan)
                    if need.bucket_cap > ec.bucket_cap:
                        ec = dataclasses.replace(ec,
                                                 bucket_cap=need.bucket_cap)
                        msg = _regrow_msgs(msg, ec)
                    step = jax.jit(make_superstep(program, plan, ec))
                    layout = plan_gather_layout(plan, vert)
                    stats.append(coll.event(
                        i, "plan-switch", join=plan.join,
                        groupby=plan.groupby, connector=plan.connector,
                        sender_combine=plan.sender_combine,
                        storage=plan.storage,
                        frontier_cap=ec.frontier_cap).as_dict())
                    m_switches.inc()
                    recompiled = True
                    switched = True
                    controller.note_shape_change()
        # adaptive frontier refit (left-outer plan): when the live set
        # collapses, shrink the frontier capacity so each superstep only
        # pays O(|frontier|) — one recompile, amortized across supersteps
        if plan.join == "left_outer" and not switched:
            act = int(gs.active_count) // max(vert.num_partitions, 1) + 1
            if act * 4 < ec.frontier_cap and ec.frontier_cap > \
                    FRONTIER_FLOOR:
                with host_leg("refit", n):
                    ec = dataclasses.replace(
                        ec, frontier_cap=max(FRONTIER_FLOOR, act * 2))
                    step = jax.jit(make_superstep(program, plan, ec))
                stats.append(coll.event(
                    i, "frontier-refit",
                    frontier_cap=ec.frontier_cap).as_dict())
                recompiled = True
                if controller is not None:
                    controller.note_shape_change()
        if controller is not None and not bool(gs.halt):
            # periodic cost-model re-calibration (opt-in): refit the
            # analytic constants after lowered shapes changed, at most
            # once per AdaptiveConfig.recalibrate_every supersteps
            recal = controller.maybe_recalibrate(program, i)
            if recal is not None:
                stats.append(coll.event(i, "recalibrate",
                                        **recal).as_dict())
        if failure_injector is not None:
            failure_injector(i, vert, msg, gs)
        if checkpoint_every and i % checkpoint_every == 0 \
                and checkpoint_dir:
            with host_leg("checkpoint", n):
                save_checkpoint(checkpoint_dir, i, vert, msg, gs)
        if on_superstep is not None:
            with host_leg("callback", n):
                on_superstep(i, vert, msg, gs, rec.as_dict())
        with host_leg("readback", n):
            halted = bool(gs.halt)
        if halted:
            break
    return RunResult(vertex=vert, gs=gs, supersteps=i, stats=stats,
                     wall_s=time.time() - t0, plan=plan)


def _regrow_msgs(msg: MsgRel, ec: EngineConfig) -> MsgRel:
    """Pad capacity per source-run (preserves the (n_parts, C) run layout
    that the merging connector's receiver group-by relies on). Restored
    checkpoints whose capacity is not run-structured are end-padded (their
    first superstep must use a sorting group-by, which the default plans
    do)."""
    P = msg.dst.shape[0]
    n, C_new = ec.n_parts, ec.bucket_cap
    if msg.capacity % n:
        pad = n * C_new - msg.capacity
        if pad <= 0:
            return msg
        return MsgRel(
            dst=jnp.pad(msg.dst, ((0, 0), (0, pad)), constant_values=-1),
            payload=jnp.pad(msg.payload, ((0, 0), (0, pad), (0, 0))),
            valid=jnp.pad(msg.valid, ((0, 0), (0, pad))))
    C_old = msg.capacity // n
    pad = C_new - C_old
    if pad <= 0:
        return msg

    def r(a, fill):
        a = a.reshape((P, n, C_old) + a.shape[2:])
        widths = [(0, 0), (0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 3)
        a = jnp.pad(a, widths, constant_values=fill)
        return a.reshape((P, n * C_new) + a.shape[3:])

    return MsgRel(dst=r(msg.dst, -1), payload=r(msg.payload, 0),
                  valid=r(msg.valid, False))
