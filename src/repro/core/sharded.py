"""Multi-device sharded superstep driver (the paper's cluster story on a
real device mesh).

``run_host`` rolls one host's frontier; ``run_out_of_core`` streams
super-partitions through ONE device. This driver is the missing axis:
``run_sharded`` maps the partition dimension onto a ``jax.make_mesh`` of
N devices and runs the bucketed m-to-n exchange as a REAL
``jax.lax.all_to_all`` (``connector.exchange_shard_map``) instead of the
emulated transpose. Worker w owns the contiguous global partitions
[w * P/N, (w+1) * P/N) — exactly the tiled all_to_all chunking of the
bucket axis, which is what makes the sharded run bit-for-bit equal to
the emulated transport (``tests/test_sharded.py``).

Two modes:

* **In-memory** (default): one shard_map-wrapped jitted superstep per
  iteration (program ``jit_superstep``), with the message exchange split
  out as its OWN jitted all_to_all stage (``EngineConfig.exchange_apart``,
  program ``jit_exchange``) so the driver can time it — each superstep
  records a ``pregel.exchange`` span plus ``exchange_bytes`` /
  ``exchange_stall_s`` counters, the measurements behind the planner's
  network axis (``MachineModel.net_bw``, ``Observation.net_scale``). On
  the kernel path (``kbackend.wants_edge_layout``) each worker gathers
  its edges with ``csr_spmv`` through a gather layout planned from its
  own block. GS folds via the superstep's own psum reductions;
  vote-to-halt, overflow-regrow, adaptive replanning and frontier refit
  all work exactly as in ``run_host``.

* **Out-of-core** (``budget_partitions`` set): every worker gets its OWN
  ``TieredStore`` (+ background ``IOEngine`` when a disk dir is set, at
  ``disk_dir/worker{w}``) so the storage tiers shard with the graph.
  Workers stream their partition blocks through the device in lockstep
  rounds; each round's collected buckets cross the mesh through the raw
  (worker-major) all_to_all and LAND into per-destination-round inbox
  pages. The per-destination readiness protocol extends to the
  distributed setting: a destination round dispatches only when ALL
  remote sources have landed its runs (``ExchangeReadiness``). A mid-run
  regrow can span the exchange — already-landed pages are end-padded to
  the new run width (valid entries are a bucket prefix, so padding
  preserves the run layout) and the overflowed round is redone.
  Mutating programs are not supported sharded+OOC (the host mutation
  inbox is not distributed yet).

CI exercises all of it on CPU under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
from __future__ import annotations

import dataclasses
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as PSpec

from repro.core import connector
from repro.core.driver import (PlanArg, RunResult, _regrow_msgs,
                               _resolve_plan, apply_kernel_impl,
                               default_engine_config, grow_overflowed,
                               host_leg, init_vertex_values,
                               plan_gather_layout)
from repro.core.plan import FRONTIER_FLOOR, PhysicalPlan
from repro.core.program import VertexProgram
from repro.core.relations import (GlobalState, MsgRel, VertexRel,
                                  empty_msgs, init_gs)
from repro.core.superstep import ROUTE, EngineConfig, make_superstep
from repro.kernels import backend as kbackend
from repro.obs import explain, memwatch, trace
from repro.obs.metrics import MetricsRegistry

_MSG_W = lambda D: (1 + D) * 4 + 4   # dst + payload + valid (as int32)


def _shard_map(fn, mesh, in_specs, out_specs):
    """The driver's shard_map: specs are written by hand, so the
    replication check is off."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _lead_spec(axes):
    """Leading-axis sharding spec builder: dim 0 over the mesh axes."""
    return lambda x: PSpec(*([axes] + [None] * (len(x.shape) - 1)))


def sharded_superstep(program: VertexProgram, plan: PhysicalPlan,
                      ec: EngineConfig, mesh, *, layout: bool = False):
    """The in-memory sharded superstep as two jitted ``shard_map``
    programs over ``mesh``: ``superstep(vert, msg, gs[, layouts])`` (with
    ``ec.exchange_apart``, it returns the pre-exchange buckets as its
    messages) and ``exchange(buckets)``, the all_to_all. With ``layout``,
    the superstep takes the stacked (N, cap) per-worker gather layouts of
    ``plan_gather_layout(..., mesh=mesh)`` and hands each worker its own
    row."""
    axes = tuple(mesh.axis_names)
    fn = make_superstep(program, plan, ec)

    # named, so the profiler names the programs ``jit_superstep`` and
    # ``jit_exchange``, as the trace readers look for them
    if layout:
        def superstep(v, m, g, lay):
            return fn(v, m, g, None, lay[0])
    else:
        def superstep(v, m, g):
            return fn(v, m, g, None, None)

    # out_specs are written by hand: the body contains psums over the
    # mesh axes, so eval_shape outside shard_map would fail on the
    # unbound axis names
    rows, deep = PSpec(axes, None), PSpec(axes, None, None)
    v_specs = VertexRel(vid=rows, halt=rows, value=deep, edge_src=rows,
                        edge_dst=rows, edge_val=rows)
    m_specs = MsgRel(dst=rows, payload=deep, valid=rows)
    g_specs = jax.tree.map(lambda _: PSpec(), init_gs(program.agg_dims))
    bkt_specs = MsgRel(dst=deep, payload=PSpec(axes, None, None, None),
                       valid=deep)
    in_specs = (v_specs, m_specs, g_specs) + ((rows,) if layout else ())
    out_specs = (v_specs, bkt_specs, g_specs)
    step = jax.jit(_shard_map(superstep, mesh, in_specs, out_specs))

    @jax.named_scope(ROUTE)
    def exchange(m: MsgRel) -> MsgRel:
        r_dst, r_pay, r_val = connector.exchange_shard_map(
            m.dst, m.payload, m.valid, axes)
        P_l = m.dst.shape[0]
        flat = lambda a: a.reshape((P_l, -1) + a.shape[3:])
        return MsgRel(dst=flat(r_dst), payload=flat(r_pay),
                      valid=flat(r_val))

    ex = jax.jit(_shard_map(exchange, mesh, (bkt_specs,), m_specs))
    return step, ex


def _compile_first(program: VertexProgram, plan: PhysicalPlan,
                   ec: EngineConfig, mesh, vert: VertexRel):
    """``sharded_superstep``'s programs for ``plan``/``ec`` compiled ahead
    of a fresh run's first superstep, from the shapes it will take: the
    relation as ``init_vertex_values`` leaves it, empty messages, the
    initial global state and, on the kernel path, the (N, cap) layouts of
    blocks sorted by source (``kbackend.edge_layout_shapes``). Returns
    (superstep, exchange, the superstep's argument specs, and the
    (program, plan, ec) they were compiled for)."""
    axes = tuple(mesh.axis_names)
    N = int(mesh.devices.size)
    lead = _lead_spec(axes)

    def spec(tree, pspec):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, pspec(x))), tree)

    gs = init_gs(program.agg_dims)
    args = [spec(jax.eval_shape(
                lambda v: init_vertex_values(v, program, gs), vert), lead),
            spec(jax.eval_shape(lambda: empty_msgs(
                vert.num_partitions, ec.n_parts * ec.bucket_cap,
                program.msg_dims)), lead),
            spec(jax.eval_shape(lambda: gs), lambda _: PSpec())]
    layout = kbackend.wants_edge_layout(plan)
    if layout:
        cap = kbackend.edge_layout_shapes(
            vert.num_partitions // N, vert.edge_src.shape[1],
            vert.capacity).shape[0]
        args.append(jax.ShapeDtypeStruct(
            (N, cap), jnp.int32, sharding=NamedSharding(mesh,
                                                        PSpec(axes, None))))
    step, ex = sharded_superstep(program, plan, ec, mesh, layout=layout)
    lowered = step.lower(*args)
    step = lowered.compile()
    buckets = jax.tree.map(
        lambda o, s: jax.ShapeDtypeStruct(o.shape, o.dtype, sharding=s),
        lowered.out_info[1], step.output_shardings[1])
    return step, ex.lower(buckets).compile(), args, (program, plan, ec)


def _resolve_sharded(vert, program: VertexProgram, plan: PhysicalPlan,
                     ec: Optional[EngineConfig], axes) -> EngineConfig:
    """The engine configuration of a fresh in-memory run of ``plan``."""
    ec = ec or default_engine_config(vert, program, plan)
    return dataclasses.replace(ec, axis_name=axes, exchange_apart=True)


def _submit(fn, *args):
    """``fn(*args)`` on a thread of its own; returns its Future."""
    pool = ThreadPoolExecutor(1)
    try:
        return pool.submit(fn, *args)
    finally:
        pool.shutdown(wait=False)


def compile_ahead(vert: VertexRel, program: VertexProgram,
                  plan: PhysicalPlan, *, mesh,
                  kernel_impl: Optional[str] = None,
                  ec: Optional[EngineConfig] = None):
    """Start compiling the first superstep and exchange of
    ``run_sharded(vert, program, plan, mesh=mesh, kernel_impl=..., ec=...)``
    on a thread, from ``vert``'s shapes alone (a relation, or
    ``vertex_rel_shapes``' description of the one ``load_graph`` will
    return), so a caller can compile while it loads the graph. Returns
    the Future that ``run_sharded(..., ahead=)`` takes; a run whose
    program, plan, engine configuration or arguments differ from what was
    compiled ignores it and compiles its own."""
    if not isinstance(plan, PhysicalPlan):
        raise ValueError("compile_ahead needs a concrete PhysicalPlan")
    plan, _ = apply_kernel_impl(plan, kernel_impl, None)
    ec = _resolve_sharded(vert, program, plan, ec, tuple(mesh.axis_names))
    return _submit(_compile_first, program, plan, ec, mesh, vert)


def _use_compiled(ahead, program, plan, ec, state, layout, step, exchange):
    """The programs ``_compile_first`` compiled, bound to ``layout``, when
    they were compiled for ``program``, ``plan`` and ``ec`` and the first
    superstep's arguments are what they were compiled for; else (or if
    compiling ahead failed: the jitted programs then compile on their
    first call and raise what there is to raise) ``step`` and
    ``exchange`` unchanged."""
    try:
        c_step, c_ex, specs, made_for = ahead.result()
    except Exception:  # noqa: BLE001
        return step, exchange
    if made_for[0] is not program or made_for[1:] != (plan, ec):
        return step, exchange
    args = list(state) + ([] if layout is None else [layout])
    same = len(args) == len(specs) and all(
        jax.tree.structure(a) == jax.tree.structure(s) and all(
            x.shape == y.shape and x.dtype == y.dtype
            and x.sharding.is_equivalent_to(y.sharding, x.ndim)
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(s)))
        for a, s in zip(args, specs))
    if not same:
        return step, exchange
    if layout is None:
        return c_step, c_ex
    return (lambda v, m, g, lay=layout: c_step(v, m, g, lay)), c_ex


def _exchange_wire_bytes(P: int, n_parts: int, C: int, D: int,
                         n_workers: int) -> int:
    """Capacity-based bytes the all_to_all moves BETWEEN workers: the
    bucket block is (P, n_parts, C) slots of (dst+payload+valid), and
    (N-1)/N of every worker's slots target remote workers."""
    total = P * n_parts * C * _MSG_W(D)
    return int(total * (n_workers - 1) / max(n_workers, 1))


def _fit_devices(P: int, healthy: int) -> int:
    """Largest worker count ≤ ``healthy`` that P partitions divide over —
    the elastic re-mesh rule. P itself never changes on recovery, so the
    replay stays bit-for-bit (per-partition results are device-count
    invariant); only the blocks-per-worker mapping shrinks."""
    for n in range(min(max(healthy, 1), P), 0, -1):
        if P % n == 0:
            return n
    return 1


class ExchangeReadiness:
    """Distributed per-destination readiness bookkeeping.

    The barrier-free OOC executor dispatches a destination when all LOCAL
    sources have produced its runs; on a mesh the sources are remote. A
    destination round (dst_worker, dst_round) becomes dispatchable for
    superstep i+1 once every (src_worker, src_round) pair of superstep i
    has landed its runs into the destination's inbox page — tracked here,
    asserted at dispatch, and surfaced as the distributed readiness
    stall when a dispatch has to wait."""

    def __init__(self, n_workers: int, n_rounds: int):
        self.n_workers = n_workers
        self.n_rounds = n_rounds
        self._landed: dict = {}   # (dst_w, dst_r) -> {(src_w, src_r)}

    def land(self, dst_worker: int, dst_round: int, src_round: int):
        """Record that ALL source workers' round-`src_round` runs landed
        for (dst_worker, dst_round) — one all_to_all delivers every
        source worker's chunk at once."""
        s = self._landed.setdefault((dst_worker, dst_round), set())
        s.update((w, src_round) for w in range(self.n_workers))

    def ready(self, dst_worker: int, dst_round: int) -> bool:
        got = self._landed.get((dst_worker, dst_round), ())
        return len(got) == self.n_workers * self.n_rounds

    def ready_round(self, dst_round: int) -> bool:
        return all(self.ready(w, dst_round)
                   for w in range(self.n_workers))

    def missing(self, dst_worker: int, dst_round: int) -> list:
        got = self._landed.get((dst_worker, dst_round), set())
        return sorted({(w, r) for w in range(self.n_workers)
                       for r in range(self.n_rounds)} - got)


def run_sharded(vert: VertexRel, program: VertexProgram,
                plan: PlanArg = PhysicalPlan(), *,
                mesh=None, devices: Optional[int] = None,
                max_supersteps: int = 50,
                ec: Optional[EngineConfig] = None,
                on_superstep: Optional[Callable] = None,
                auto_config=None, auto_space: Optional[dict] = None,
                kernel_impl: Optional[str] = None,
                budget_partitions: int = 0,
                disk_dir: Optional[str] = None,
                memory_budget_bytes: Optional[int] = None,
                io_threads: Optional[int] = None,
                readahead_pages: int = 8,
                eviction: str = "lru",
                checkpoint_every: int = 0,
                checkpoint_dir: Optional[str] = None,
                resume_from: Optional[str] = None,
                recover: bool = False,
                max_retries: int = 3,
                machine=None, ahead=None) -> RunResult:
    """Run `program` on a device mesh. ``mesh`` (or ``devices`` for a 1-D
    host mesh) sets the worker count N; the P partitions shard over it in
    contiguous blocks: worker w owns [w P/N, (w+1) P/N). A relation that
    ``load_graph(..., mesh=mesh)`` already placed so is used where it
    lies, without another copy; any other is placed block by block. With
    ``budget_partitions`` set, each worker streams its block through the
    device ``budget_partitions`` at a time from its own tiered store
    (per-worker OOC).

    ``kernel_impl`` / a plan's ``kernel_impl`` choose the kernels as in
    ``run_host`` (``auto``: compiled Pallas on a TPU, the jnp reference
    elsewhere). On the in-memory kernel path each worker's ``csr_spmv``
    gather runs on a layout planned from its own block
    (``plan_gather_layout``), re-planned on a plan switch. A fresh
    in-memory run compiles its first superstep and exchange on a thread
    while the workers initialize and plan their layouts, or takes them
    from ``ahead``, the Future of an earlier ``compile_ahead`` call.

    ``on_superstep`` is called as ``on_superstep(i, stats_dict)`` after
    superstep i; in-memory, a return value that ``is True`` ends the run
    there, and ``RunResult.vertex`` holds the state of that boundary.

    ``checkpoint_every``/``checkpoint_dir`` snapshot the gathered global
    relations as npz at superstep boundaries (in-memory mode only);
    ``resume_from=<ckpt npz>`` restarts from one. ``recover=True`` runs
    under the failure manager's recovery supervisor: a recoverable
    failure blacklists the failed worker, restores the latest VALID
    checkpoint, re-meshes onto the largest divisor of P that fits the
    surviving device count (P itself never changes, so the replay is
    bit-for-bit — per-partition results are device-count invariant),
    and replays."""
    from repro.launch.mesh import make_host_mesh
    from repro.runtime import faults

    t0 = time.time()
    if mesh is None:
        mesh = make_host_mesh(devices)
    axes = tuple(mesh.axis_names)
    N = int(mesh.devices.size)
    P = vert.num_partitions
    if P % N:
        raise ValueError(f"n_partitions {P} must divide over {N} devices")
    if machine is None:
        from repro.planner import machine_for
        machine = machine_for()

    if recover:
        from repro.runtime.checkpoint import latest_checkpoint
        from repro.runtime.failure import supervised_run

        def _attempt(healthy, resume):
            return run_sharded(
                vert, program, plan, mesh=None,
                devices=_fit_devices(P, healthy),
                max_supersteps=max_supersteps, ec=ec,
                on_superstep=on_superstep, auto_config=auto_config,
                auto_space=auto_space, kernel_impl=kernel_impl,
                budget_partitions=budget_partitions, disk_dir=disk_dir,
                memory_budget_bytes=memory_budget_bytes,
                io_threads=io_threads, readahead_pages=readahead_pages,
                eviction=eviction, checkpoint_every=checkpoint_every,
                checkpoint_dir=checkpoint_dir, resume_from=resume,
                recover=False, machine=machine)

        def _pick(bad):
            if not checkpoint_dir:
                return None
            return latest_checkpoint(checkpoint_dir, skip=bad,
                                     verify=True)

        return supervised_run(_attempt, _pick, n_workers=N,
                              max_retries=max_retries,
                              initial_resume=resume_from)

    if budget_partitions:
        if checkpoint_every or resume_from:
            raise ValueError("sharded npz checkpointing is in-memory "
                             "mode only (per-worker OOC stores keep "
                             "their state on their own disk tiers)")
        return _run_sharded_ooc(
            vert, program, plan, mesh=mesh, axes=axes, n_workers=N,
            max_supersteps=max_supersteps, ec=ec,
            budget_partitions=budget_partitions, disk_dir=disk_dir,
            memory_budget_bytes=memory_budget_bytes,
            io_threads=io_threads, readahead_pages=readahead_pages,
            eviction=eviction, machine=machine, kernel_impl=kernel_impl,
            auto_space=auto_space, on_superstep=on_superstep, t0=t0)

    from repro.planner.cost import Observation
    from repro.planner.stats import StatsCollector
    from repro.runtime.checkpoint import save_checkpoint

    i0, rmsg, rgs = 0, None, None
    if resume_from is not None:
        from repro.runtime.checkpoint import load_checkpoint
        vert, rmsg, rgs = load_checkpoint(resume_from)
        if vert.num_partitions != P:
            raise ValueError(
                f"checkpoint has {vert.num_partitions} partitions; the "
                f"sharded driver resumes at a fixed P={P}")
        i0 = int(rgs.superstep)
    plan, auto_space = apply_kernel_impl(plan, kernel_impl, auto_space)
    obs0 = Observation(frontier_density=1.0, sharded=True, n_workers=N)
    plan, controller = _resolve_plan(vert, program, plan, adaptive=True,
                                     auto_config=auto_config,
                                     auto_space=auto_space,
                                     machine=machine, obs0=obs0)
    ec = _resolve_sharded(vert, program, plan, ec, axes)
    if rmsg is not None and rmsg.capacity > ec.n_parts * ec.bucket_cap:
        ec = dataclasses.replace(
            ec, bucket_cap=-(-rmsg.capacity // ec.n_parts))
    if explain.enabled():
        explain.attach(
            program, vert=vert,
            g=controller.g if controller is not None else None,
            plan=plan, machine=machine, space_kw=auto_space)
    if memwatch.enabled():
        memwatch.configure(ec=ec, Np=vert.capacity,
                           Ep=vert.edge_src.shape[1],
                           value_dims=program.value_dims,
                           msg_dims=program.msg_dims)

    lead = _lead_spec(axes)
    # device_put onto the sharding an array already has is a no-op
    put_lead = lambda tree: jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, lead(x))), tree)
    put_rep = lambda tree: jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, PSpec())), tree)

    def build_step(plan, ec):
        """The superstep and exchange for ``plan``/``ec``, the superstep
        bound to the gather layouts in effect now."""
        step, ex = sharded_superstep(program, plan, ec, mesh,
                                     layout=layout is not None)
        if layout is None:
            return step, ex
        return (lambda v, m, g, lay=layout: step(v, m, g, lay)), ex

    if rgs is not None:
        ahead = None
        gs = put_rep(rgs)
        vert = put_lead(vert)
        msg = put_lead(_regrow_msgs(rmsg, ec))
    else:
        if ahead is None and i0 < max_supersteps:
            # the first superstep's programs compile while the workers
            # initialize and plan their layouts
            ahead = _submit(_compile_first, program, plan, ec, mesh, vert)
        gs = init_gs(program.agg_dims)
        # place first: each worker initializes its own partitions
        # instead of the whole graph landing on the first device
        vert = put_lead(init_vertex_values(put_lead(vert), program, gs))
        gs = put_rep(gs)
        msg = put_lead(empty_msgs(P, ec.n_parts * ec.bucket_cap,
                                  program.msg_dims))
    layout = plan_gather_layout(plan, vert, mesh)
    step, exchange = build_step(plan, ec)
    if ahead is not None:
        step, exchange = _use_compiled(ahead, program, plan, ec,
                                       (vert, msg, gs), layout, step,
                                       exchange)

    n_live = (controller.g.n_vertices if controller is not None
              else int(jnp.sum(vert.vid >= 0)))
    metrics = MetricsRegistry()
    coll = StatsCollector(n_partitions=P, vertex_capacity=vert.capacity,
                          msg_dims=program.msg_dims, n_vertices=n_live,
                          metrics=metrics)
    m_exb = metrics.counter("exchange.bytes")
    m_exs = metrics.counter("exchange.stall_s")
    m_regrows = metrics.counter("host.regrows")
    m_switches = metrics.counter("host.plan_switches")
    stats = []
    i = i0
    recompiled = True
    while i < max_supersteps:
        faults.superstep_tick(i, "sharded")
        ts = time.time()
        this_recompiled = recompiled
        recompiled = False
        prev = (vert, msg, gs)
        n = i + 1
        with host_leg("dispatch", n):
            vert2, buckets, gs2 = step(vert, msg, gs)
        with host_leg("wait", n):
            jax.block_until_ready(gs2.superstep)
        with host_leg("readback", n):
            ovf_delta = np.asarray(gs2.overflow) - np.asarray(gs.overflow)
        if (ovf_delta > 0).any():
            with host_leg("regrow", n):
                ec = grow_overflowed(ec, ovf_delta,
                                     vertex_capacity=vert.capacity)
                step, exchange = build_step(plan, ec)
                vert, msg, gs = prev
                msg = put_lead(_regrow_msgs(msg, ec))
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
        # ---- the all_to_all exchange, as its own timed stage ----------
        faults.hit("sharded.exchange", f"s{i}")
        ex_bytes = _exchange_wire_bytes(P, ec.n_parts, ec.bucket_cap,
                                        program.msg_dims, N)
        with host_leg("exchange", n, bytes=ex_bytes, workers=N):
            t_ex = time.time()
            msg = exchange(buckets)
            jax.block_until_ready(msg.valid)
            ex_stall = time.time() - t_ex
        m_exb.inc(ex_bytes)
        m_exs.inc(ex_stall)
        trace.counter("exchange.bytes", ex_bytes)
        vert, gs = vert2, gs2
        i += 1
        with host_leg("readback", n):
            rec = coll.record(i, active=int(gs.active_count),
                              messages=int(gs.msg_count),
                              wall_s=time.time() - ts,
                              recompiled=this_recompiled,
                              sharded=True, n_workers=N,
                              exchange_bytes=ex_bytes,
                              exchange_stall_s=ex_stall)
        stats.append(rec.as_dict())
        if explain.enabled():
            explain.superstep(rec, plan=plan, bucket_cap=ec.bucket_cap)
        if memwatch.enabled():
            memwatch.sample(i)
        switched = False
        if controller is not None and not bool(gs.halt):
            with host_leg("replan", n):
                new_plan = controller.observe(rec, bucket_cap=ec.bucket_cap)
                if new_plan is not None:
                    from repro.planner import migrate_msgs
                    msg = put_lead(migrate_msgs(msg, plan, new_plan,
                                                ec.n_parts))
                    plan = new_plan
                    if plan.join == "left_outer":
                        act = int(gs.active_count) // max(P, 1) + 1
                        ec = dataclasses.replace(
                            ec, frontier_cap=min(max(FRONTIER_FLOOR, act * 4),
                                                 vert.capacity + 8))
                    need = default_engine_config(vert, program, plan)
                    if need.bucket_cap > ec.bucket_cap:
                        ec = dataclasses.replace(ec,
                                                 bucket_cap=need.bucket_cap)
                        msg = put_lead(_regrow_msgs(msg, ec))
                    layout = plan_gather_layout(plan, vert, mesh)
                    step, exchange = build_step(plan, ec)
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
        if plan.join == "left_outer" and not switched:
            act = int(gs.active_count) // max(P, 1) + 1
            if act * 4 < ec.frontier_cap and \
                    ec.frontier_cap > FRONTIER_FLOOR:
                with host_leg("refit", n):
                    ec = dataclasses.replace(
                        ec, frontier_cap=max(FRONTIER_FLOOR, act * 2))
                    step, exchange = build_step(plan, ec)
                stats.append(coll.event(
                    i, "frontier-refit",
                    frontier_cap=ec.frontier_cap).as_dict())
                recompiled = True
                if controller is not None:
                    controller.note_shape_change()
        if checkpoint_every and i % checkpoint_every == 0 \
                and checkpoint_dir:
            with host_leg("checkpoint", n):
                save_checkpoint(checkpoint_dir, i, vert, msg, gs)
        if on_superstep is not None:
            with host_leg("callback", n):
                stop = on_superstep(i, rec.as_dict()) is True
            if stop:
                break
        with host_leg("readback", n):
            halted = bool(gs.halt)
        if halted:
            break
    return RunResult(vertex=vert, gs=gs, supersteps=i, stats=stats,
                     wall_s=time.time() - t0, plan=plan)


# ---------------------------------------------------------------------
# out-of-core sharded: per-worker tiered stores, lockstep rounds
# ---------------------------------------------------------------------

_VFIELDS = ("vid", "halt", "value", "edge_src", "edge_dst", "edge_val")


def _run_sharded_ooc(vert, program, plan, *, mesh, axes, n_workers,
                     max_supersteps, ec, budget_partitions, disk_dir,
                     memory_budget_bytes, io_threads, readahead_pages,
                     eviction, machine, kernel_impl, auto_space,
                     on_superstep, t0):
    from repro.planner.cost import Observation
    from repro.planner.stats import StatsCollector
    from repro.runtime import faults
    from repro.storage.tiered import TieredStore

    if getattr(program, "mutates", False):
        raise NotImplementedError(
            "mutating programs are not supported in sharded OOC mode "
            "(the host mutation inbox is not distributed); run in-memory "
            "sharded or single-host OOC")
    N = n_workers
    P = vert.num_partitions
    P_w = P // N                     # partitions owned per worker
    b = int(budget_partitions)       # resident partitions per worker
    if P_w % b:
        raise ValueError(f"budget_partitions {b} must divide the "
                         f"per-worker block {P_w}")
    R = P_w // b                     # lockstep rounds per superstep
    D, V = program.msg_dims, program.value_dims

    plan, auto_space = apply_kernel_impl(plan, kernel_impl, auto_space)
    if not isinstance(plan, PhysicalPlan):
        auto_space = dict(auto_space or {})
        auto_space.setdefault("kernel_impls", ("ref",))
    # "auto" resolves ONCE (non-adaptive): every round re-jits on a plan
    # switch, so mid-run switching would thrash the jit cache at R times
    # the in-memory rate — future work
    obs0 = Observation(frontier_density=1.0, sharded=True, n_workers=N,
                       ooc=True, super_partitions=R)
    plan, _ = _resolve_plan(vert, program, plan, adaptive=False,
                            auto_space=auto_space, machine=machine,
                            obs0=obs0)
    base_ec = ec or default_engine_config(vert, program, plan)
    ec = dataclasses.replace(base_ec, axis_name=axes, ooc_collect=True)
    Np = vert.capacity
    if explain.enabled():
        # static plan here (resolved once): the shadow auditor still
        # re-prices it per superstep against the measured legs
        explain.attach(program, vert=vert, plan=plan, machine=machine,
                       space_kw=auto_space)
    if memwatch.enabled():
        memwatch.configure(ec=ec, Np=Np, Ep=vert.edge_src.shape[1],
                           value_dims=V, msg_dims=D,
                           budget_bytes=(memory_budget_bytes * N
                                         if memory_budget_bytes
                                         else None))

    metrics = MetricsRegistry()
    n_live = int(np.asarray(vert.vid >= 0).sum())
    coll = StatsCollector(n_partitions=P, vertex_capacity=Np,
                          msg_dims=D, n_vertices=n_live, metrics=metrics)
    m_exb = metrics.counter("exchange.bytes")
    m_exs = metrics.counter("exchange.stall_s")
    m_regrows = metrics.counter("host.regrows")

    # ---- per-worker tiered stores (the OOC tiers shard with the graph)
    threads = (io_threads if io_threads is not None
               else (1 if disk_dir else 0))
    stores = []
    for w in range(N):
        wdir = f"{disk_dir}/worker{w}" if disk_dir else None
        stores.append(TieredStore(
            n_sp=R, budget_bytes=memory_budget_bytes, disk_dir=wdir,
            policy=eviction, io_threads=threads,
            readahead_pages=readahead_pages, metrics=metrics))

    gs = init_gs(program.agg_dims)
    vert = init_vertex_values(vert, program, gs)
    for w in range(N):
        blk = slice(w * P_w, (w + 1) * P_w)
        for f in _VFIELDS:
            stores[w].register(f, np.asarray(getattr(vert, f))[blk])
    del vert

    lead = _lead_spec(axes)
    rep = lambda x: PSpec()
    put_lead = lambda tree: jax.tree.map(
        lambda x: jax.device_put(jnp.asarray(x),
                                 NamedSharding(mesh, lead(x))), tree)
    put_rep = lambda tree: jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, PSpec())), tree)

    def build_step(ec, C_in):
        """Jitted shard_map superstep for resident blocks of N*b
        partitions with an inbox of run width C_in, plus the raw
        (worker-major) all_to_all for its collected buckets."""
        fn = make_superstep(program, plan, ec)

        body = lambda v, m, g: fn(v, m, g, None, None)
        # hand-written specs (psums in the body rule out eval_shape
        # outside shard_map); the inbox run width C_in only affects
        # SHAPES, which jit re-specializes on — the specs are rank-fixed
        v_specs = VertexRel(vid=PSpec(axes, None),
                            halt=PSpec(axes, None),
                            value=PSpec(axes, None, None),
                            edge_src=PSpec(axes, None),
                            edge_dst=PSpec(axes, None),
                            edge_val=PSpec(axes, None))
        m_specs = MsgRel(dst=PSpec(axes, None),
                         payload=PSpec(axes, None, None),
                         valid=PSpec(axes, None))
        g_specs = jax.tree.map(rep, init_gs(program.agg_dims))
        bkt_specs = MsgRel(dst=PSpec(axes, None, None),
                           payload=PSpec(axes, None, None, None),
                           valid=PSpec(axes, None, None))
        in_specs = (v_specs, m_specs, g_specs)
        # 5-tuple under ooc_collect: (vert, buckets, gs, counts,
        # mut_buckets); mutating programs are rejected up front so the
        # mutation buckets are always the static None leaf
        out_specs = (v_specs, bkt_specs, g_specs, PSpec(axes, None),
                     None)
        step = jax.jit(_shard_map(body, mesh, in_specs, out_specs))

        def ex_body(m: MsgRel) -> MsgRel:
            # RAW worker-major all_to_all: the landing pass reorders
            # into per-destination pages itself
            r_dst, r_pay, r_val = connector.exchange_shard_map(
                m.dst, m.payload, m.valid, axes, dst_major=False)
            return MsgRel(dst=r_dst, payload=r_pay, valid=r_val)

        ex = jax.jit(_shard_map(ex_body, mesh, (bkt_specs,), bkt_specs))
        return step, ex

    gen = 0
    gen_width = {0: ec.bucket_cap}   # inbox run width per generation
    step, exchange = build_step(ec, gen_width[0])
    ready_prev = None   # landings that built the current inbox gen

    def empty_inbox(C_in):
        return (np.full((b, P, C_in), -1, np.int32),
                np.zeros((b, P, C_in, D), np.float32),
                np.zeros((b, P, C_in), bool))

    def read_inbox(w, r):
        try:
            d = stores[w].get_page(("inbox", gen, r, "dst"))
            p = stores[w].get_page(("inbox", gen, r, "pay"))
            v = stores[w].get_page(("inbox", gen, r, "val"))
            return d, p, v
        except KeyError:
            return empty_inbox(gen_width[gen])

    stats = []
    i = 0
    supersteps_done = 0
    halted = False
    recompiled = True
    while i < max_supersteps and not halted:
        faults.superstep_tick(i, "sharded")
        ts = time.time()
        this_recompiled = recompiled
        recompiled = False
        nxt: dict = {}           # (worker, dst_round) -> (d, p, v) pages
        readiness = ExchangeReadiness(N, R)
        fold_active = 0
        fold_msgs = 0
        fold_agg = np.zeros((program.agg_dims,), np.float32)
        fold_halt = True
        ex_stall_total = 0.0
        ex_bytes_total = 0
        stall_total = 0.0
        delta_bytes = full_bytes = 0
        r = 0
        while r < R:
            # ---- distributed readiness gate: every source must have
            # landed this destination round's runs before dispatch
            t_gate = time.time()
            if ready_prev is not None and not ready_prev.ready_round(r):
                missing = [ready_prev.missing(w, r) for w in range(N)]
                raise RuntimeError(
                    f"superstep {i} round {r} dispatched before all "
                    f"sources landed: missing {missing}")
            stall_total += time.time() - t_gate
            # ---- assemble the resident block (N*b partitions)
            with trace.span("dispatch", "dispatch", superstep=i, round=r):
                vblk = {f: np.concatenate(
                    [stores[w].read(f, r) for w in range(N)])
                    for f in _VFIELDS}
                inbox = [read_inbox(w, r) for w in range(N)]
                C_in = gen_width[gen]
                mblk = MsgRel(
                    dst=np.concatenate([x[0] for x in inbox])
                    .reshape(N * b, P * C_in),
                    payload=np.concatenate([x[1] for x in inbox])
                    .reshape(N * b, P * C_in, D),
                    valid=np.concatenate([x[2] for x in inbox])
                    .reshape(N * b, P * C_in))
                vdev = put_lead(VertexRel(**vblk))
                mdev = put_lead(mblk)
                gdev = put_rep(gs)
            vert2, buckets, gs2, counts, _ = step(vdev, mdev, gdev)
            jax.block_until_ready(gs2.superstep)
            ovf_delta = (np.asarray(gs2.overflow) -
                         np.asarray(gs.overflow))
            if (ovf_delta > 0).any():
                # regrow SPANNING the exchange: grow, re-jit, end-pad the
                # pages already landed for gen+1 to the new run width,
                # and redo this round (nothing of round r landed yet)
                ec = grow_overflowed(ec, ovf_delta, vertex_capacity=Np)
                step, exchange = build_step(ec, gen_width[gen])
                C_new = ec.bucket_cap
                for key, (pd, pp, pv) in list(nxt.items()):
                    pad = C_new - pd.shape[2]
                    if pad > 0:
                        nxt[key] = (
                            np.pad(pd, ((0, 0), (0, 0), (0, pad)),
                                   constant_values=-1),
                            np.pad(pp, ((0, 0), (0, 0), (0, pad),
                                        (0, 0))),
                            np.pad(pv, ((0, 0), (0, 0), (0, pad))))
                stats.append(coll.event(
                    i, "regrow", bucket_cap=ec.bucket_cap,
                    frontier_cap=ec.frontier_cap, round=r,
                    sources=np.flatnonzero(ovf_delta > 0).tolist())
                    .as_dict())
                m_regrows.inc()
                trace.instant("regrow", "replan", superstep=i, round=r)
                recompiled = True
                continue
            C = ec.bucket_cap
            # ---- the all_to_all exchange stage (timed)
            t_ex = time.time()
            exchanged = exchange(buckets)
            jax.block_until_ready(exchanged.valid)
            t_done = time.time()
            ex_bytes = _exchange_wire_bytes(N * b, P, C, D, N)
            trace.complete("exchange", "exchange", t_ex, t_done,
                           superstep=i, round=r, bytes=ex_bytes)
            ex_stall_total += t_done - t_ex
            ex_bytes_total += ex_bytes
            m_exb.inc(ex_bytes)
            m_exs.inc(t_done - t_ex)
            # ---- land the worker-major runs into per-destination pages
            t_land = time.time()
            xd = np.asarray(exchanged.dst)
            xp = np.asarray(exchanged.payload)
            xv = np.asarray(exchanged.valid)
            with trace.span("commit", "commit", superstep=i, round=r):
                for w in range(N):
                    blk = slice(w * b, (w + 1) * b)
                    # y[p, j*P_w + t] = src worker j local p -> my dst t
                    yd = xd[blk].reshape(b, N, P_w, C)
                    yp = xp[blk].reshape(b, N, P_w, C, D)
                    yv = xv[blk].reshape(b, N, P_w, C)
                    for rd in range(R):
                        key = (w, rd)
                        if key not in nxt:
                            nxt[key] = empty_inbox(C)
                        pd, pp, pv = nxt[key]
                        tsl = slice(rd * b, (rd + 1) * b)
                        ssl = slice(r * b, (r + 1) * b)
                        # page run index = GLOBAL src partition
                        # j*P_w + r*b + p; valid entries stay a prefix
                        pd.reshape(b, N, P_w, C)[:, :, ssl] = \
                            yd[:, :, tsl].transpose(2, 1, 0, 3)
                        pp.reshape(b, N, P_w, C, D)[:, :, ssl] = \
                            yp[:, :, tsl].transpose(2, 1, 0, 3, 4)
                        pv.reshape(b, N, P_w, C)[:, :, ssl] = \
                            yv[:, :, tsl].transpose(2, 1, 0, 3)
                        readiness.land(w, rd, r)
                # ---- commit the updated vertex blocks per worker store
                nv = {f: np.asarray(getattr(vert2, f))
                      for f in ("vid", "halt", "value", "edge_dst",
                                "edge_val")}
                fold_halt &= bool(np.all(nv["halt"] | (nv["vid"] < 0)))
                for w in range(N):
                    blk = slice(w * b, (w + 1) * b)
                    for f in ("vid", "halt", "value", "edge_dst",
                              "edge_val"):
                        new = nv[f][blk]
                        old = stores[w].read(f, r)
                        if plan.storage == "delta":
                            mask = (new != old).reshape(b, -1).any(1)
                            delta_bytes += int(mask.sum()) * \
                                new[0].nbytes if b else 0
                            stores[w].write_rows(f, r, mask, new[mask])
                        else:
                            delta_bytes += new.nbytes
                            stores[w].write(f, r, new)
                        full_bytes += new.nbytes
                    if threads and r + 1 < R:
                        stores[w].readahead(
                            [(f, r + 1) for f in _VFIELDS])
            stall_total += time.time() - t_land
            fold_active += int(gs2.active_count)
            fold_msgs += int(gs2.msg_count)
            fold_agg += np.asarray(gs2.aggregate)
            r += 1
        # ---- GS fold across rounds (the rolling-fold analogue)
        i += 1
        supersteps_done = i
        new_gen = gen + 1
        gen_width[new_gen] = ec.bucket_cap
        for (w, rd), (pd, pp, pv) in nxt.items():
            stores[w].put_page(("inbox", new_gen, rd, "dst"), pd)
            stores[w].put_page(("inbox", new_gen, rd, "pay"), pp)
            stores[w].put_page(("inbox", new_gen, rd, "val"), pv)
        for w in range(N):
            for rd in range(R):
                for f in ("dst", "pay", "val"):
                    try:
                        stores[w].delete_page(("inbox", gen, rd, f))
                    except KeyError:
                        pass
        gen = new_gen
        ready_prev = readiness
        conv = bool(np.asarray(program.is_converged(gs)))
        halted = (fold_halt and fold_msgs == 0) or conv
        gs = GlobalState(
            halt=jnp.asarray(halted),
            aggregate=jnp.asarray(fold_agg, jnp.float32).reshape(
                np.asarray(gs.aggregate).shape),
            superstep=gs.superstep + 1,
            overflow=gs.overflow,
            active_count=jnp.asarray(fold_active, jnp.int32),
            msg_count=jnp.asarray(fold_msgs, jnp.int32))
        tier = {}
        for w in range(N):
            for k, v in stores[w].take_interval().items():
                tier[k] = tier.get(k, 0) + v
        extra = dict(ooc=True, sharded=True, n_workers=N,
                     super_partitions=R, streaming=False,
                     barrier_free=False,
                     exchange_bytes=ex_bytes_total,
                     exchange_stall_s=ex_stall_total,
                     readiness_stall_s=stall_total,
                     delta_bytes=delta_bytes, full_bytes=full_bytes,
                     change_density=(delta_bytes / full_bytes
                                     if full_bytes else 1.0),
                     storage=plan.storage,
                     spill=any(s.spilling for s in stores))
        # per-superstep pager interval keys are "hits"/"misses"
        # (BufferPool.take_interval), summed across the worker stores
        hits = tier.get("hits", 0)
        total_lookups = hits + tier.get("misses", 0)
        if total_lookups:
            extra["cache_hit_rate"] = hits / total_lookups
        for k in ("spill_read_bytes", "spill_write_bytes"):
            if k in tier:
                extra[k] = tier[k]
        rec = coll.record(i, active=fold_active, messages=fold_msgs,
                          wall_s=time.time() - ts,
                          recompiled=this_recompiled, **extra)
        stats.append(rec.as_dict())
        if explain.enabled():
            explain.superstep(rec, plan=plan, bucket_cap=ec.bucket_cap)
        if memwatch.enabled():
            # N workers each keep b partitions resident at once
            memwatch.sample(i, stores=stores, resident_parts=N * b)
        if on_superstep is not None:
            on_superstep(i, rec.as_dict())
    # ---- final gather (the HDFS-write analogue, per worker)
    out = {f: np.concatenate([stores[w].gather(f) for w in range(N)])
           for f in _VFIELDS}
    for s in stores:
        s.close()
    vert_out = VertexRel(**{f: jnp.asarray(out[f]) for f in _VFIELDS})
    return RunResult(vertex=vert_out, gs=gs, supersteps=supersteps_done,
                     stats=stats, wall_s=time.time() - t0, plan=plan)
