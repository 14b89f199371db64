"""Out-of-core execution (the paper's central claim, Sections 2.3/5.4/7.2).

On Hyracks, operators spill to disk through the buffer cache, so the same
plans run in-memory and out-of-core. The TPU-adapted memory hierarchy is
three tiers: HBM <-> host DRAM <-> DISK. The Vertex relation and the
run-structured message inbox live in a ``storage.TieredStore`` — a
page-granular buffer cache (``storage/pager.py``) chunked one page per
(relation, super-partition) with a configurable DRAM byte budget
(``memory_budget_bytes``), evicting cold pages to mmap-backed spill files
(``--disk-dir``; ``storage/spillfile.py``) and faulting them back on
access. Each superstep streams SUPER-PARTITIONS (groups of partitions
sized to a device-memory budget) through the jitted partial superstep;
prefetch is disk -> DRAM -> HBM and commit is HBM -> DRAM with lazy
write-back to disk, both hidden behind compute by the pipelined executor
below. With no disk dir and no budget the store degenerates to the pure
DRAM tier — the previous two-level hierarchy — and results are
bit-for-bit identical either way (the disk tier only moves bytes).

Eviction is pluggable (``eviction="lru" | "mru"``): the superstep's page
access pattern is a cyclic sequential scan over super-partitions, which
floods LRU (hit rate 0 when the working set outgrows the budget); MRU
retains a stable prefix of the cycle and converges to hit rate
budget/working-set (the GraphH hot-data-cache observation). In-flight
pipeline slots PIN their pages so prefetched state cannot be evicted
under them.

PIPELINED STREAMING (``stream=True``, the default): the executor keeps up
to ``prefetch_depth`` super-partitions in flight. A DISPATCHER uploads
super-partition s+1's vertex slices and inbox runs with non-blocking
``jax.device_put`` and enqueues its jitted step while s is still
computing; a COLLECTOR consumes completed super-partitions — out of
dispatch order when a later one finishes first — committing each one's
host write-back while the device works on the next. Steady-state wall
time per superstep therefore approaches ``max(compute, transfer)``
instead of their sum (the GraphD/GraphH overlap discipline, arXiv
1601.05590 / 1705.05595). The uploaded vertex block is DONATED to its
updated output (``superstep.jit_superstep``), so a pipeline slot costs
one resident vertex block, not two. ``stream=False`` degenerates to the
synchronous upload -> step -> block -> collect loop (a window of 1).

BARRIER-FREE SUPERSTEP PIPELINE (``barrier_free=True``, the default with
``stream=True``): PR 3/4 still paid two global stalls per superstep —
the whole-inbox rebuild + mutation apply + GS fold ran serially between
supersteps with the device idle, and (on the disk tier) page faults and
dirty write-backs ran synchronously on the dispatcher/collector thread.
Both are gone:

* **Per-destination inbox-run readiness.** A destination super-partition
  of superstep i+1 is dispatchable the moment all P source partitions of
  superstep i have LANDED THEIR RUNS for it (their collected out-blocks)
  — the run-width trim and the GS chain pin that moment to the last
  collect, so what used to be a global barrier of serial work collapses
  into a per-destination ``prepare`` step: rebuild ONLY destination q's
  inbox chunk, apply ONLY q's mutation-inbox columns, then dispatch q —
  while the device is already computing earlier destinations, the host
  rolls the frontier forward by preparing the later ones. Per-superstep
  serial work drops from O(inbox) to O(inbox / n_sp).
* **Rolling fold.** The GS fold, vote-to-halt, write-back/combinability/
  mutation measurements all commit per-destination at collect time (in
  super-partition order for the float aggregate — bit-for-bit with the
  synchronous loop); the executor only SYNCHRONIZES the frontier for
  plan switches (the one-off run sort a merging switch needs is folded
  into the next chunk builds), regrows (the deferred-overflow drain),
  and checkpoints (which eagerly prepare the full generation so the
  saved inbox is complete).
* **Background page I/O** (``storage/io_engine.py``): with a disk tier,
  ``io_threads`` worker threads own the disk legs — the dispatcher
  announces the next dispatchable destination's pages (``readahead``,
  bounded by ``readahead_pages``) so they fault in off the critical
  path, and cold dirty pages drain in eviction order (coalesced) so
  evictions find clean victims and never block on a synchronous write.

The statistics stream records the per-superstep ``readiness_stall_s``
(device-idle gap between a superstep's last collect and the next
superstep's first dispatch — the quantity this mode minimizes) and the
I/O engine's queue depth; ``benchmarks/out_of_core.py`` races
barrier-free against the PR-4 barrier executor into
``BENCH_pipeline.json``.

Because results land asynchronously, the overflow/regrow protocol is
DEFERRED: host state for a super-partition commits only when its result
is collected clean. When a collected result reports overflow, the
collector drains the pipeline — committing in-flight super-partitions
that finished clean, marking overflowed ones for redo — then doubles
ONLY the overflowed capacities (per-source ``GlobalState.overflow``
counters), re-jits, end-pads the already-committed bucket blocks, and
re-dispatches the redo set from retained host state. Float-sensitive
reductions (the user aggregate) are folded in super-partition order at
the rolling fold, so streaming runs are bit-for-bit identical to
synchronous ones.

The host inbox is RUN-STRUCTURED: the per-super-partition bucket tensors
coming off the device — ``(sp, P, C)`` with valid entries occupying a
PREFIX of every ``(src, dst)`` bucket (``connector.bucket_by_owner``'s
layout contract) — are restacked destination-major into per-destination
chunks ``(sp, P_src, C)`` (the host-side analogue of the emulated
exchange) and trimmed to the widest occupied run. The rebuild runs one
destination super-partition at a time through the pager, so peak DRAM
for the exchange is inbox/n_sp, not the full inbox. Because each
destination partition's message block is exactly ``n_parts`` sender runs
of equal width — dst-sorted whenever the sender sorts — the merging
receiver's run-capacity assumption holds host-side and ``plan="auto"``
searches the FULL join x group-by x connector x sender-combine x storage
space here, switching any of them with a re-jit at a superstep boundary.

MUTATIONS span super-partitions through a HOST MUTATION INBOX mirroring
the message one: under ``ec.ooc_collect`` the superstep buckets insert
proposals by owner over all P partitions and hands them back
(``superstep.apply_mutations``) instead of exchanging them in-device
(which only spans the resident super-partition). The collector spills
the collected ``(sp, P, Cm)`` blocks through the same pager; the
per-destination prepare applies them host-side with the same
scatter/resolve semantics the in-memory path uses — so inserting
programs are exact across super-partition boundaries. (Whether any
proposal will land — the vote-to-halt input — is decided from the
collected blocks at commit time, so the fold never waits for the apply.)

storage="delta" (LSM analogue): only CHANGED vertex values are written
back to the host store each superstep instead of the full value array —
the deferred-merge write path, right for sparse-update workloads; on the
disk tier a super-partition with no changed rows never even dirties its
page, so converged regions cost zero disk write-back. Both policies'
write-back bytes are measured every superstep and feed the cost model's
storage dimension (``planner/cost.py`` ``storage_writeback``); the
statistics stream also carries the pager's PER-SUPERSTEP hit rate and
spill bytes (interval counters, reset each superstep — the planner
observes current paging behavior, not cumulative), the measured message
COMBINABILITY (messages/distinct-destination — the signal behind the
sender_combine replan dimension), the mutation rate, and the dispatch /
collect-wait / commit wall-time split, so the planner prices plans with
the critical-path rule (``max(device, host_link, disk)`` plus the serial
readiness leg) when the pipelined executor is active.

Checkpoints hard-link/copy the spill files at the FILE level
(``runtime/checkpoint.py`` ``save_ooc_checkpoint``) — no DRAM
re-serialization — and ``resume_from=`` restarts a job directly from a
checkpoint directory, faulting pages in on first touch. The checkpoint
meta also persists the AdaptiveController's hysteresis state
(window/streak/cooldown), so a resume right before a pending plan switch
does not re-pay the patience window.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.driver import (PlanArg, RunResult, _resolve_plan,
                               default_engine_config, grow_overflowed)
from repro.core.plan import FRONTIER_FLOOR, STORAGES, PhysicalPlan
from repro.core.program import VertexProgram
from repro.core.relations import GlobalState, MsgRel, VertexRel, init_gs
from repro.core.superstep import EngineConfig, jit_superstep
from repro.kernels import backend as kbackend
from repro.obs import explain, memwatch, trace
from repro.obs.metrics import MetricsRegistry
from repro.storage import TieredStore

# the OOC planner searches both storage policies on top of the full
# per-superstep space (in-memory drivers inherit the base plan's storage:
# they never pay a write-back, so the dimension would only produce ties)
_OOC_AUTO_SPACE = {"storages": STORAGES}

# host-resident relations (the chunked pages of the TieredStore)
_RELS = ("vid", "halt", "value", "edge_src", "edge_dst", "edge_val")
_OUT = ("out_dst", "out_pay", "out_val")     # collected sender buckets
_MUT = ("mut_dst", "mut_pay", "mut_val")     # collected insert proposals
_INBOX = ("inbox_dst", "inbox_pay", "inbox_val")


@dataclasses.dataclass
class _InFlight:
    """One dispatched, uncollected super-partition (async device refs)."""
    s: int
    v2: VertexRel
    buckets: MsgRel
    g2: GlobalState
    counts: jax.Array      # (sp, P) per-bucket occupancy, device-computed
    mut: Optional[tuple]   # (dst, payload, valid) insert buckets or None


@dataclasses.dataclass
class _Done:
    """One committed super-partition (host-side results; the bucket and
    mutation blocks themselves live as pages in the TieredStore)."""
    counts: np.ndarray    # (sp, P) per-bucket occupancy of the out block
    halt_ok: bool
    active: int
    agg: np.ndarray
    delta_bytes: int
    full_bytes: int
    has_mut: bool


def _round_run_width(max_count: int, cap: int) -> int:
    """Trim width for the inbox runs: next power of two >= the widest
    occupied run, clamped to [1, bucket_cap]. Power-of-two rounding keeps
    the set of distinct jitted message shapes logarithmic in cap, so the
    jit cache amortizes across supersteps as the frontier breathes."""
    w = 1
    while w < max_count:
        w *= 2
    return max(1, min(w, cap))


def _sort_inbox_runs(inbox):
    """Sort every (dst, src) run of a host inbox chunk by dst — the
    host-side mirror of ``planner.adaptive.migrate_msgs`` for a mid-run
    switch onto the merging connector when the previous plan produced
    UNSORTED runs (plain partitioning without a sender combine). Invalid
    slots key as int32 max, so the stable sort keeps valid entries a run
    prefix."""
    d, p, v = inbox
    key = np.where(v, d, np.iinfo(np.int32).max)
    order = np.argsort(key, axis=2, kind="stable")
    return (np.take_along_axis(d, order, axis=2),
            np.take_along_axis(p, order[..., None], axis=2),
            np.take_along_axis(v, order, axis=2))


def _pad_run_width(block, C_new: int):
    """End-pad a collected (sp, P, C_old) bucket block to C_old=C_new.
    Valid entries occupy a prefix per bucket, so end-padding with invalid
    slots preserves the run layout (cf. driver._regrow_msgs)."""
    d, p, v = block
    pad = C_new - d.shape[2]
    if pad <= 0:
        return block
    return (np.pad(d, ((0, 0), (0, 0), (0, pad)), constant_values=-1),
            np.pad(p, ((0, 0), (0, 0), (0, pad), (0, 0))),
            np.pad(v, ((0, 0), (0, 0), (0, pad))))


def _host_slot_of(dst, valid, Np: int, P: int, partition: str):
    """Host-side mirror of superstep._slot_of (the vid -> local slot
    map), for applying the mutation inbox at the barrier. Slots past
    the capacity clamp to the drop row Np — the device scatter drops
    out-of-bounds insert vids, and np.add.at would raise instead."""
    if partition == "range":
        owner = np.minimum(dst // Np, P - 1)
        slot = np.where(valid, dst - owner * Np, Np)
    else:
        slot = np.where(valid, dst // P, Np)
    return np.minimum(slot, Np)


def _distinct_run_dsts(b_dst: np.ndarray, b_val: np.ndarray) -> int:
    """Distinct destinations PER (source, dst-partition) RUN of one
    collected bucket block — the duplicates a SENDER-side combine could
    actually collapse (global distinct would also count cross-source
    fan-in, which no sender can remove). Sort each run and count value
    boundaries; invalid slots key as int max. Measured at COMMIT time —
    overlapped by the pipeline — instead of during the serial inbox
    rebuild, so the barrier-free fold has the combinability signal the
    moment the last result lands. The trim only drops invalid slots, so
    this equals the old rebuild-time measurement exactly. Caveat: when
    the producing plan already combined, every run is duplicate-free and
    the measured ratio is ~1 — the model then prices the inbox leg
    neutrally and the sender-combine decision falls to the sort-cost
    terms, which is the honest post-combine view."""
    key = np.where(b_val, b_dst, np.iinfo(np.int32).max)
    srt = np.sort(key, axis=2)
    new_run = np.ones(srt.shape, bool)
    new_run[:, :, 1:] = srt[:, :, 1:] != srt[:, :, :-1]
    return int((new_run & (srt != np.iinfo(np.int32).max)).sum())


def _apply_mutation_chunk(store: TieredStore, program, plan, P: int,
                          sp: int, n_sp: int, gen: int, q: int):
    """Apply destination super-partition ``q``'s collected insert
    proposals to the host store — the per-destination half of the host
    mutation inbox (the barrier-free prepare calls it right before
    dispatching ``q``; the barrier path calls it for every q at the
    fold). Mirrors the in-memory ``superstep.apply_mutations``
    scatter/resolve exactly: per destination partition, sum conflicting
    proposals per slot, count them, recover the vid, run
    ``program.resolve``, and install the result (vid set, value replaced,
    halt cleared) where any proposal landed. Touches one destination
    super-partition's columns, so peak DRAM is mut-inbox / n_sp."""
    d = np.concatenate([store.get_page(("mut_dst", gen, s, q))
                        for s in range(n_sp)])    # (P, sp, Cm)
    pv = np.concatenate([store.get_page(("mut_pay", gen, s, q))
                         for s in range(n_sp)])   # (P, sp, Cm, V)
    ok = np.concatenate([store.get_page(("mut_val", gen, s, q))
                         for s in range(n_sp)])   # (P, sp, Cm)
    V = pv.shape[-1]
    vid_pg = store.read("vid", q)
    Np = vid_pg.shape[1]
    touched = False
    val_pg = halt_pg = None
    for p_local in range(sp):
        dd = d[:, p_local, :].reshape(-1)
        oo = ok[:, p_local, :].reshape(-1)
        if not oo.any():
            continue
        vv = pv[:, p_local, :, :].reshape(-1, V)
        slot = _host_slot_of(dd, oo, Np, P, plan.partition)
        # same dtypes as the device per_part (float32 sums, int32
        # counts): a custom resolve must see identical promotion
        # rules host-side or parity breaks in the last ulp
        summed = np.zeros((Np + 1, V), np.float32)
        np.add.at(summed, slot,
                  np.where(oo[:, None], vv, np.float32(0.0)))
        cnt = np.zeros((Np + 1,), np.int32)
        np.add.at(cnt, slot, oo)
        newvid = np.full((Np + 1,), -1, np.int32)
        np.maximum.at(newvid, slot,
                      np.where(oo, dd, -1).astype(np.int32))
        resolved = np.asarray(program.resolve(
            newvid[:Np], summed[:Np], cnt[:Np]), np.float32)
        take = cnt[:Np] > 0
        if not take.any():
            continue
        if not touched:
            val_pg = store.read("value", q)
            halt_pg = store.read("halt", q)
            touched = True
        vid_pg[p_local][take] = newvid[:Np][take]
        val_pg[p_local][take] = resolved[take]
        halt_pg[p_local][take] = False
    if touched:
        # pages were mutated in place: re-put to mark them dirty
        store.write("vid", q, vid_pg)
        store.write("value", q, val_pg)
        store.write("halt", q, halt_pg)


def _adopt_checkpoint(store: TieredStore, z: dict, src):
    """Install a spill-directory checkpoint into a fresh store (pages
    hard-linked/copied at the file level; on the disk tier nothing is
    read into DRAM until first touch). ``z``/``src`` come from the
    caller's ``load_ooc_meta``. Returns the restored GlobalState."""
    for nm in _RELS:
        for s in range(store.n_sp):
            store.adopt_page((nm, s), src / f"{nm}_{s}.npy", relation=nm)
    for nm in _INBOX:
        for q in range(store.n_sp):
            store.adopt_page((nm, 0, q), src / f"{nm}_{q}.npy",
                             immutable=True)
    return GlobalState(
        halt=jnp.asarray(bool(z["halt"])),
        aggregate=jnp.asarray(z["aggregate"]),
        superstep=jnp.asarray(int(z["superstep"]), jnp.int32),
        overflow=jnp.asarray(z["overflow"]),
        active_count=jnp.asarray(int(z["active"]), jnp.int32),
        msg_count=jnp.asarray(int(z["msgs"]), jnp.int32))


class _ShapeVert:
    """Shape-only stand-in for a VertexRel (resume path: the capacity
    policies only read ``.vid.shape`` / ``.edge_src.shape``)."""

    def __init__(self, P, Np, Ep):
        self.vid = np.empty((P, Np), np.bool_)
        self.edge_src = np.empty((P, Ep), np.bool_)


def run_out_of_core(vert: Optional[VertexRel], program: VertexProgram,
                    plan: PlanArg = PhysicalPlan(), *,
                    budget_partitions: int,
                    max_supersteps: int = 50,
                    ec: Optional[EngineConfig] = None,
                    auto_config=None,
                    auto_space: Optional[dict] = None,
                    kernel_impl: Optional[str] = None,
                    stream: bool = True,
                    prefetch_depth: int = 2,
                    barrier_free: bool = True,
                    memory_budget_bytes: Optional[int] = None,
                    disk_dir: Optional[str] = None,
                    eviction: str = "lru",
                    io_threads: Optional[int] = None,
                    readahead_pages: int = 8,
                    checkpoint_every: int = 0,
                    checkpoint_dir: Optional[str] = None,
                    resume_from: Optional[str] = None,
                    recover: bool = False,
                    max_retries: int = 3,
                    on_superstep=None) -> RunResult:
    """budget_partitions = how many partitions fit in device memory at once
    (the HBM budget). P % budget_partitions must be 0. plan="auto" picks
    the plan from the cost model and re-picks it at superstep boundaries —
    over the FULL plan space including connector and storage (messages
    live host-side between supersteps in run-structured buffers, so any
    switch is just a re-jit — no in-flight layout migration).

    stream=True (default) pipelines the super-partition stream: up to
    ``prefetch_depth`` super-partitions are in flight at once, hiding
    host<->device transfer behind compute; stream=False is the
    synchronous loop (a pipeline window of 1). Results are bit-for-bit
    identical either way.

    barrier_free=True (default; requires stream=True) removes the global
    inter-superstep barrier: the inbox rebuild and mutation apply run
    per destination, interleaved with the next superstep's dispatches
    (per-destination readiness), and the executor only synchronizes for
    plan switches, regrows and checkpoints. Results are bit-for-bit
    identical to the barrier executor and the synchronous loop.

    DISK TIER: ``memory_budget_bytes`` caps the host-DRAM bytes the
    run's relations and inbox may occupy at once; cold pages spill to
    mmap-backed files under ``disk_dir`` (required when a budget is set)
    and fault back in on access. ``eviction`` picks the page-replacement
    policy: "lru", or "mru" — which resists the superstep's cyclic
    sequential scan (see ``storage/pager.py``). ``io_threads`` (default:
    1 whenever a disk dir is configured, else 0) moves the disk legs to
    a background page-I/O engine — readahead of the next dispatchable
    destination's pages (at most ``readahead_pages`` per tick) plus a
    coalesced dirty-page drain — so the dispatcher/collector never touch
    disk on the critical path. Results are bit-for-bit identical to the
    pure-DRAM tier.

    ``checkpoint_every``/``checkpoint_dir`` snapshot the host store at
    superstep boundaries by hard-linking/copying its spill files (no
    DRAM re-serialization); ``resume_from=<checkpoint dir>`` restarts
    from such a snapshot — ``vert`` may then be None.

    OBSERVABILITY: every pipeline leg records a span when ``repro.obs``
    tracing is on (``trace.start()`` / ``pregel_run --trace``) —
    prepare/dispatch on the main loop, collect-wait/commit per collected
    super-partition, the readiness stall as an explicit span from the
    previous superstep's last collect to the next first dispatch, plus
    replan/regrow/checkpoint events; the I/O-engine workers record their
    own fault/writeback spans on their threads. A per-run
    ``MetricsRegistry`` (shared with the store's I/O engine) merges its
    interval snapshot into every record's ``extra["metrics"]``.
    ``on_superstep(i, rec_dict)`` is called after each superstep's
    record lands — the live progress hook ``pregel_run --progress``
    uses.

    ``recover=True`` runs the job under the failure manager's recovery
    supervisor: a recoverable failure (WorkerFailure, disk I/O, typed
    page/checkpoint corruption) restores the latest VALID committed
    checkpoint under ``checkpoint_dir`` — deep-verified, skipping any
    snapshot whose restore surfaced corruption — and replays from it.
    Replays resume at the checkpoint's own partition layout, so the
    recovered run converges bit-for-bit with an unfailed one."""
    from repro.planner.stats import StatsCollector
    from repro.runtime import faults as chaos
    from repro.runtime.checkpoint import save_ooc_checkpoint

    if recover:
        from repro.runtime.checkpoint import latest_ooc_checkpoint
        from repro.runtime.failure import supervised_run
        n_workers = (vert.vid.shape[0] // budget_partitions
                     if vert is not None else max(1, max_retries + 1))

        def _attempt(healthy, resume):
            if resume is None and vert is None:
                raise RuntimeError(
                    "no valid checkpoint to restore and no initial "
                    "relations to restart from")
            return run_out_of_core(
                vert, program, plan,
                budget_partitions=budget_partitions,
                max_supersteps=max_supersteps, ec=ec,
                auto_config=auto_config, auto_space=auto_space,
                kernel_impl=kernel_impl, stream=stream,
                prefetch_depth=prefetch_depth, barrier_free=barrier_free,
                memory_budget_bytes=memory_budget_bytes,
                disk_dir=disk_dir, eviction=eviction,
                io_threads=io_threads, readahead_pages=readahead_pages,
                checkpoint_every=checkpoint_every,
                checkpoint_dir=checkpoint_dir, resume_from=resume,
                recover=False, on_superstep=on_superstep)

        def _pick(bad):
            if not checkpoint_dir:
                return None
            return latest_ooc_checkpoint(checkpoint_dir, skip=bad,
                                         deep=True)

        return supervised_run(_attempt, _pick, n_workers=n_workers,
                              max_retries=max_retries,
                              initial_resume=resume_from)

    t0 = time.time()
    sp = budget_partitions
    if checkpoint_every and not checkpoint_dir:
        raise ValueError("checkpoint_every needs a checkpoint_dir — "
                         "otherwise the job would silently run "
                         "without any checkpoints")
    barrier_free = bool(barrier_free and stream)
    if io_threads is None:
        io_threads = 1 if disk_dir else 0
    store = None
    try:
        ck_meta = ck_gs = ck_src = None
        if resume_from is not None:
            # shapes come from the checkpoint pages; vert is not needed
            from repro.runtime.checkpoint import load_ooc_meta
            ck_meta, ck_gs, ck_src = load_ooc_meta(resume_from)
            n_sp = ck_meta["n_sp"]
            P = n_sp * sp
            if ck_meta.get("sp", sp) != sp:
                raise ValueError(
                    f"checkpoint streams {ck_meta.get('sp')} "
                    f"partitions per super-partition; got "
                    f"budget_partitions={sp}")
        else:
            P = vert.vid.shape[0]
            assert P % sp == 0
            n_sp = P // sp
        metrics = MetricsRegistry()
        store = TieredStore(n_sp=n_sp, budget_bytes=memory_budget_bytes,
                            disk_dir=disk_dir, policy=eviction,
                            io_threads=io_threads,
                            readahead_pages=readahead_pages,
                            metrics=metrics)
        gen = 0            # inbox generation (one per superstep fold)
        if resume_from is not None:
            gs = _adopt_checkpoint(store, ck_gs, ck_src)
            i = int(ck_meta["superstep"])
            Np = store.read("vid", 0).shape[1]
            Ep = store.read("edge_src", 0).shape[1]
            C_in = store.get_page(("inbox_dst", 0, 0)).shape[2]
            shape_vert = _ShapeVert(P, Np, Ep)
            graph_stats = None
            if plan == "auto":
                # only the auto-planner needs graph statistics: a static
                # resume must not stream two whole relations through the
                # budgeted cache just to discard the counts
                n_live = sum(int((store.read("vid", s) >= 0).sum())
                             for s in range(n_sp))
                n_edges = sum(int((store.read("edge_src", s) >= 0).sum())
                              for s in range(n_sp))
                from repro.planner.cost import GraphStats
                graph_stats = GraphStats(
                    n_vertices=n_live, n_edges=n_edges, n_partitions=P,
                    vertex_capacity=Np, edge_capacity=Ep,
                    value_dims=program.value_dims,
                    msg_dims=program.msg_dims)
        else:
            Np = vert.vid.shape[1]
            shape_vert = vert
            i = 0
            graph_stats = None
        saved_plan = None
        if ck_meta is not None and ck_meta.get("plan"):
            saved_plan = PhysicalPlan(**ck_meta["plan"])
        wanted_auto = plan == "auto"
        if kernel_impl is not None:
            # pin the hot-path kernel dispatch: into the concrete plan
            # directly, or into the auto search space so every candidate
            # (initial choice and mid-run switches) carries it
            if isinstance(plan, PhysicalPlan):
                plan = dataclasses.replace(plan, kernel_impl=kernel_impl)
            else:
                auto_space = dict(_OOC_AUTO_SPACE if auto_space is None
                                  else auto_space)
                auto_space.setdefault("kernel_impls", (kernel_impl,))
        plan, controller = _resolve_plan(
            shape_vert if resume_from is None else None, program, plan,
            adaptive=True, auto_config=auto_config,
            auto_space=_OOC_AUTO_SPACE if auto_space is None
            else auto_space, graph_stats=graph_stats)
        if saved_plan is not None:
            if wanted_auto:
                # restart auto jobs from the plan IN EFFECT at the
                # checkpoint (it produced the restored inbox's layout)
                # rather than re-choosing blind at superstep-0 stats;
                # the controller re-plans from live statistics as usual
                plan = saved_plan
                if kernel_impl is not None:
                    plan = dataclasses.replace(plan,
                                               kernel_impl=kernel_impl)
                if controller is not None:
                    controller.plan = plan
            if (plan.connector == "partitioning_merging"
                    and saved_plan.connector != "partitioning_merging"
                    and not saved_plan.sender_combine):
                # the checkpointed inbox's runs are unsorted but the
                # resumed plan's merging receiver assumes dst order:
                # one-off sort, the resume analogue of the mid-run
                # switch guard below
                for q in range(n_sp):
                    triple = _sort_inbox_runs(tuple(
                        store.get_page((nm, 0, q)) for nm in _INBOX))
                    for nm, a in zip(_INBOX, triple):
                        store.put_page((nm, 0, q), a, immutable=True)
        if controller is not None and ck_meta is not None \
                and ck_meta.get("controller"):
            # restore the hysteresis window/streak/cooldown, so a resume
            # right before a pending switch does not re-pay the patience
            # window
            controller.load_state(ck_meta["controller"])
        caller_ec = ec is not None
        ec = ec or default_engine_config(shape_vert, program, plan)
        if not caller_ec and ck_meta is not None and ck_meta.get("caps"):
            # restore the checkpointed (possibly overflow-regrown)
            # capacities instead of replaying the regrow cascade from
            # the defaults on every restart
            ec = dataclasses.replace(ec, **ck_meta["caps"])
        # resolve frontier_cap=0 (the EngineConfig "Np/2" default) to its
        # concrete value up front: the overflow regrow path doubles it,
        # and 0 * 2 = 0 would re-jit the identical config forever
        ec = dataclasses.replace(ec, ooc_collect=True,
                                 frontier_cap=ec.frontier_cap or
                                 max(Np // 2, 1))
        if explain.enabled():
            # plan-audit ledger: the shadow auditor re-prices the
            # in-effect plan per superstep (static resumes without
            # graph statistics stay decision-log-only)
            from repro.planner.cost import machine_for
            explain.attach(
                program,
                vert=shape_vert if resume_from is None else None,
                g=(controller.g if controller is not None
                   else graph_stats),
                plan=plan,
                machine=(controller.machine if controller is not None
                         else machine_for()),
                space_kw=(_OOC_AUTO_SPACE if auto_space is None
                          else auto_space))
        if memwatch.enabled():
            memwatch.configure(
                ec=ec, Np=Np, Ep=shape_vert.edge_src.shape[1],
                value_dims=program.value_dims,
                msg_dims=program.msg_dims,
                budget_bytes=memory_budget_bytes)
        step = jit_superstep(program, plan, ec, donate_vertex=True)
        seen_widths = set()   # inbox widths this `step` has already traced

        # kernel-path gather layouts, one per super-partition q. edge_src
        # is immutable for the whole run (mutations rewrite edge_dst /
        # edge_val only; commit never writes edge_src), so the cache is
        # valid across regrows AND plan switches; plan_layout_fixed pads
        # every q's layout to the SAME shape (load_graph sorts each
        # partition's edges by source), so the shared jitted step
        # traces once and takes each q's layout as a plain traced argument
        gather_layouts = {}

        def gather_layout(q):
            if not kbackend.wants_edge_layout(plan):
                return None
            lay = gather_layouts.get(q)
            if lay is None:
                lay = jax.device_put(kbackend.plan_edge_layout(
                    store.read("edge_src", q), Np))
                gather_layouts[q] = lay
            return lay

        D = program.msg_dims
        if resume_from is None:
            # host-resident state through the buffer cache (DRAM pages
            # backed by the disk tier when configured)
            for k in _RELS:
                store.register(k, np.asarray(getattr(vert, k)))
            gs = init_gs(program.agg_dims)
            # init values on device per super-partition (streams once)
            from repro.core.driver import init_vertex_values
            for s in range(n_sp):
                vpart = VertexRel(**{k: jnp.asarray(store.read(k, s))
                                     for k in _RELS})
                vpart = init_vertex_values(vpart, program, gs)
                store.write("value", s, np.asarray(vpart.value))
            # run-structured empty inbox: one invalid slot per (dst, src)
            # run, chunked per destination super-partition
            C_in = 1
            for q in range(n_sp):
                store.put_page(("inbox_dst", 0, q),
                               np.full((sp, P, 1), -1, np.int32),
                               immutable=True)
                store.put_page(("inbox_pay", 0, q),
                               np.zeros((sp, P, 1, D), np.float32),
                               immutable=True)
                store.put_page(("inbox_val", 0, q),
                               np.zeros((sp, P, 1), bool),
                               immutable=True)
        n_live = (controller.g.n_vertices if controller is not None
                  else sum(int((store.read("vid", s) >= 0).sum())
                           for s in range(n_sp)))
        coll = StatsCollector(n_partitions=P, vertex_capacity=Np,
                              msg_dims=D, n_vertices=n_live,
                              metrics=metrics)
        m_prepare = metrics.histogram("ooc.prepare_s")
        m_regrows = metrics.counter("ooc.regrows")
        m_switches = metrics.counter("ooc.plan_switches")
        stats = []
        delta_bytes = full_bytes = 0
        recompiled = True  # first superstep includes the jit compile
        window = max(int(prefetch_depth), 1) if stream else 1
        store.take_interval()    # reset per-superstep pager counters
        # ---- rolling-frontier state (reassigned at every fold; the
        # closures below read the CURRENT binding at call time) ---------
        prepared = set(range(n_sp))   # gen-0 chunks exist (init / resume)
        cur_has_mut = False           # no mutation pages precede gen 0
        sort_on_build = False         # one-off run sort on a merging switch
        todo = deque()
        committed = {}
        t_io = {"dispatch": 0.0, "wait": 0.0, "commit": 0.0}
        acc = {"distinct": 0, "proposals": 0, "applied": False}
        stall_cell = [None]
        t_ready0 = time.time()

        def prepare(q):
            """Per-destination readiness work for generation ``gen``:
            restack destination q's inbox chunk from the runs all n_sp
            sources landed for it (the host-side emulated exchange —
            source-major stack, destination-major transpose, trim every
            run to the fold's C_in; valid entries are a bucket PREFIX,
            so the trim drops only invalid tail slots), then apply q's
            mutation-inbox columns. Under barrier_free this runs
            interleaved with dispatches — the device computes earlier
            destinations while the host prepares later ones; the barrier
            path calls it for every q at the fold."""
            if q in prepared:
                return
            tp = time.time()
            d_q = np.concatenate([store.get_page(("out_dst", gen, s, q))
                                  for s in range(n_sp)], axis=0)
            p_q = np.concatenate([store.get_page(("out_pay", gen, s, q))
                                  for s in range(n_sp)], axis=0)
            v_q = np.concatenate([store.get_page(("out_val", gen, s, q))
                                  for s in range(n_sp)], axis=0)
            triple = (np.ascontiguousarray(
                          d_q.transpose(1, 0, 2)[:, :, :C_in]),
                      np.ascontiguousarray(
                          p_q.transpose(1, 0, 2, 3)[:, :, :C_in]),
                      np.ascontiguousarray(
                          v_q.transpose(1, 0, 2)[:, :, :C_in]))
            if sort_on_build:
                # a plan switch onto the merging receiver landed at the
                # fold before this chunk was built: give it dst-sorted
                # runs at build time (the rolling analogue of the
                # post-switch inbox sort)
                triple = _sort_inbox_runs(triple)
            for nm, a in zip(_INBOX, triple):
                store.put_page((nm, gen, q), a, immutable=True)
            for s in range(n_sp):
                for nm in _OUT:
                    store.delete_page((nm, gen, s, q))
            if gen > 0:
                for nm in _INBOX:
                    store.delete_page((nm, gen - 1, q))
            if cur_has_mut:
                _apply_mutation_chunk(store, program, plan, P, sp, n_sp,
                                      gen, q)
                for s in range(n_sp):
                    for nm in _MUT:
                        store.delete_page((nm, gen, s, q))
            m_prepare.observe(time.time() - tp)
            trace.complete("prepare", "prepare", tp, time.time(), q=q)
            prepared.add(q)

        def dispatch(q):
            """Non-blocking disk->DRAM->HBM prefetch + step enqueue
            for one super-partition: pages fault in from the spill
            tier if evicted, upload with ``jax.device_put``, and the
            device starts (or queues) the work while the host moves
            on to prepare or collect another one. The value page stays
            PINNED until commit (the delta compare needs the
            pre-step values resident)."""
            td = time.time()
            if store.engine is not None:
                # announce the NEXT destination's pages to the I/O
                # engine so its faults happen off the critical path.
                # When this superstep's queue has drained, warm the
                # NEXT superstep's first destination instead — its
                # relation pages are the coldest (touched first after
                # the fold) and would otherwise fault inside the
                # readiness stall.
                if todo:
                    qn = todo[0]
                    keys = [(nm, qn) for nm in _RELS]
                    if qn in prepared:
                        keys += [(nm, gen, qn) for nm in _INBOX]
                    else:
                        keys += [(nm, gen, s2, qn)
                                 for s2 in range(n_sp) for nm in _OUT]
                        if cur_has_mut:
                            keys += [(nm, gen, s2, qn)
                                     for s2 in range(n_sp)
                                     for nm in _MUT]
                else:
                    keys = [(nm, 0) for nm in _RELS]
                    keys += [(nm, gen + 1, s2, 0)
                             for s2 in range(n_sp) for nm in _OUT]
                store.readahead(keys)
            store.pin("value", q)
            vpart = VertexRel(**{k: jax.device_put(store.read(k, q))
                                 for k in _RELS})
            # incoming chunk: the run-structured inbox page for this
            # destination super-partition, runs flattened — already
            # the receiver's layout
            d_in = store.get_page(("inbox_dst", gen, q))
            p_in = store.get_page(("inbox_pay", gen, q))
            v_in = store.get_page(("inbox_val", gen, q))
            msg = MsgRel(
                dst=jax.device_put(d_in.reshape(sp, P * C_in)),
                payload=jax.device_put(
                    p_in.reshape(sp, P * C_in, D)),
                valid=jax.device_put(v_in.reshape(sp, P * C_in)))
            # part0 = this block's first GLOBAL partition index, so
            # resurrect mints correct vids past super-partition 0
            with trace.annotate("step_enqueue", "compute"):
                v2, buckets, g2, cnts, mut = step(
                    vpart, msg, gs, jnp.asarray(q * sp, jnp.int32),
                    gather_layout(q))
            now = time.time()
            t_io["dispatch"] += now - td
            trace.complete("dispatch", "dispatch", td, now, q=q)
            if stall_cell[0] is None:
                # device-idle gap: from the previous superstep's last
                # collect to this superstep's first step enqueue — the
                # readiness stall the barrier-free pipeline minimizes
                stall_cell[0] = now - t_ready0
                trace.complete("readiness_stall", "dispatch",
                               t_ready0, now)
            return _InFlight(q, v2, buckets, g2, cnts, mut)

        def commit(e):
            """Drain one clean super-partition D2H and commit its
            host state (delta vs full write-back policy; both byte
            counts are measured every superstep to feed the cost
            model's storage dimension). Blocking on the value pull
            is the pipeline's compute-wait; everything after is
            host-side commit time. Dirty pages write back to disk
            lazily (on eviction, background drain or checkpoint),
            overlapped by the pipeline like every other page move.
            The fold-time signals — combinability, mutation proposal
            count, will-any-insert-land — are measured HERE, on the
            full-width collected blocks, so the rolling fold never
            waits for the inbox rebuild to learn them."""
            tw = time.time()
            new_value = np.asarray(e.v2.value)   # blocks on e's step
            tc = time.time()
            t_io["wait"] += tc - tw
            trace.complete("collect_wait", "collect", tw, tc, q=e.s)
            old_value = store.read("value", e.s)
            changed = np.any(new_value != old_value, axis=-1)
            d_b = int(changed.sum()) * new_value.shape[-1] * 4
            f_b = new_value.size * 4
            if plan.storage == "delta":
                store.write_rows("value", e.s, changed,
                                 new_value[changed])
            else:
                store.write("value", e.s, new_value)
            new_halt = np.asarray(e.v2.halt)
            new_vid = np.asarray(e.v2.vid)
            store.write("halt", e.s, new_halt)
            store.write("vid", e.s, new_vid)
            store.write("edge_dst", e.s, np.asarray(e.v2.edge_dst))
            store.write("edge_val", e.s, np.asarray(e.v2.edge_val))
            store.unpin("value", e.s)
            # collected sender buckets -> per-destination out pages of
            # the NEXT generation (chunking here is what keeps the
            # prepare's inbox rebuild at inbox/n_sp peak DRAM). Once
            # every source has landed its runs for destination q, q is
            # dispatchable — per-destination readiness.
            b_dst = np.asarray(e.buckets.dst)
            b_pay = np.asarray(e.buckets.payload)
            b_val = np.asarray(e.buckets.valid)
            counts = np.asarray(e.counts)
            if controller is not None:
                # only the adaptive controller consumes the signal, so
                # fixed-plan runs skip the O(M log C) pass; trim the
                # sort to the block's occupancy (valid entries are a
                # bucket prefix) — bucket_cap carries slack the sort
                # must not pay for
                w = max(int(counts.max(initial=0)), 1)
                acc["distinct"] += _distinct_run_dsts(
                    b_dst[:, :, :w], b_val[:, :, :w])
            for q in range(n_sp):
                qsl = slice(q * sp, (q + 1) * sp)
                store.put_page(("out_dst", gen + 1, e.s, q),
                               b_dst[:, qsl])
                store.put_page(("out_pay", gen + 1, e.s, q),
                               b_pay[:, qsl])
                store.put_page(("out_val", gen + 1, e.s, q),
                               b_val[:, qsl])
            has_mut = e.mut is not None
            if has_mut:
                # chunked per destination like the out blocks, so the
                # prepare's apply pass runs at mut-inbox / n_sp peak
                # DRAM and never re-faults full-width pages. The
                # vote-to-halt input ("will any proposal land?") is
                # decided here from the same slot math the apply uses.
                m_dst = np.asarray(e.mut[0])
                m_pay = np.asarray(e.mut[1])
                m_ok = np.asarray(e.mut[2])
                acc["proposals"] += int(m_ok.sum())
                if not acc["applied"]:
                    lands = _host_slot_of(m_dst, m_ok, Np, P,
                                          plan.partition) < Np
                    if bool((m_ok & lands).any()):
                        acc["applied"] = True
                for q in range(n_sp):
                    qsl = slice(q * sp, (q + 1) * sp)
                    store.put_page(("mut_dst", gen + 1, e.s, q),
                                   m_dst[:, qsl])
                    store.put_page(("mut_pay", gen + 1, e.s, q),
                                   m_pay[:, qsl])
                    store.put_page(("mut_val", gen + 1, e.s, q),
                                   m_ok[:, qsl])
            done = _Done(
                counts=counts,
                halt_ok=bool(np.all(new_halt | (new_vid < 0))),
                active=int(e.g2.active_count),
                agg=np.asarray(e.g2.aggregate),
                delta_bytes=d_b, full_bytes=f_b, has_mut=has_mut)
            now = time.time()
            t_io["commit"] += now - tc
            trace.complete("commit", "commit", tc, now, q=e.s)
            return done

        while i < max_supersteps and not bool(gs.halt):
            chaos.superstep_tick(i, "ooc")
            ts = time.time()
            this_recompiled = recompiled
            recompiled = False
            if C_in not in seen_widths:
                # a new message width retraces inside jit: this
                # superstep's wall time includes a compile
                seen_widths.add(C_in)
                this_recompiled = True
            ovf0 = np.asarray(gs.overflow)
            t_io = {"dispatch": 0.0, "wait": 0.0, "commit": 0.0}
            acc = {"distinct": 0, "proposals": 0, "applied": False}
            stall_cell = [None]
            committed = {}                # s -> _Done
            todo = deque(range(n_sp))     # dispatch queue (redo re-enters)
            pending = []                  # _InFlight, dispatch order

            while todo or pending:
                # fill the pipeline window, preparing each destination
                # (chunk rebuild + mutation apply) just before its
                # dispatch — under barrier_free this is where the old
                # barrier's serial work overlaps the device
                while todo and len(pending) < window:
                    q = todo.popleft()
                    prepare(q)
                    pending.append(dispatch(q))
                # collect a completed super-partition — out of dispatch
                # order when a later one is already done — else block on
                # the oldest
                j = 0
                if len(pending) > 1:
                    j = next((k for k, e in enumerate(pending)
                              if e.g2.overflow.is_ready()), 0)
                e = pending.pop(j)
                delta = np.asarray(e.g2.overflow) - ovf0   # blocks on e
                if (delta > 0).any():
                    # DEFERRED OVERFLOW: a bucket / frontier / mutation /
                    # edge capacity overflowed mid-pipeline. Unwind the
                    # in-flight prefetch: drain every pending result,
                    # committing the ones that finished clean and marking
                    # overflowed ones for redo; then double ONLY the
                    # overflowed capacities, re-jit, end-pad the
                    # committed blocks and redo from retained host state
                    # (nothing from a dirty step was committed). This is
                    # one of the three events the barrier-free frontier
                    # synchronizes on.
                    t_rg = time.time()
                    redo = {e.s}
                    store.unpin("value", e.s)
                    for other in pending:
                        od = np.asarray(other.g2.overflow) - ovf0
                        if (od > 0).any():
                            delta = delta + od
                            redo.add(other.s)
                            store.unpin("value", other.s)
                        else:
                            committed[other.s] = commit(other)
                    pending = []
                    ec = grow_overflowed(ec, delta)
                    step = jit_superstep(program, plan, ec,
                                         donate_vertex=True)
                    seen_widths = {C_in}
                    for s2, done in committed.items():
                        for q in range(n_sp):
                            old = tuple(
                                store.get_page((nm, gen + 1, s2, q))
                                for nm in _OUT)
                            new = _pad_run_width(old, ec.bucket_cap)
                            if new[0] is not old[0]:
                                for nm, a in zip(_OUT, new):
                                    store.put_page((nm, gen + 1, s2, q),
                                                   a)
                        if done.has_mut:
                            for q in range(n_sp):
                                old = tuple(
                                    store.get_page((nm, gen + 1, s2, q))
                                    for nm in _MUT)
                                new = _pad_run_width(old,
                                                     ec.mutation_cap)
                                if new[0] is not old[0]:
                                    for nm, a in zip(_MUT, new):
                                        store.put_page(
                                            (nm, gen + 1, s2, q), a)
                    todo = deque(sorted(redo | set(todo)))
                    stats.append(coll.event(
                        i, "regrow", bucket_cap=ec.bucket_cap,
                        frontier_cap=ec.frontier_cap,
                        mutation_cap=ec.mutation_cap,
                        sources=np.flatnonzero(delta > 0).tolist(),
                        redo=sorted(redo)).as_dict())
                    m_regrows.inc()
                    trace.complete("overflow_regrow", "replan",
                                   t_rg, time.time())
                    this_recompiled = True
                    if controller is not None:
                        controller.note_shape_change()
                    continue
                committed[e.s] = commit(e)
            t_ready0 = time.time()

            # ROLLING FOLD: every input was measured at collect time, so
            # this is scalar work — the per-super-partition results fold
            # in super-partition order (float aggregate order must not
            # depend on pipeline completion order — bit-for-bit vs the
            # synchronous loop), and the next superstep's first
            # destination dispatches right after, without waiting for
            # any inbox rebuild or mutation apply.
            t_fold = time.time()
            ordered = [committed[s] for s in range(n_sp)]
            halt_all = all(d.halt_ok for d in ordered)
            active = sum(d.active for d in ordered)
            agg = np.zeros((program.agg_dims,), np.float32)
            for d in ordered:
                agg += d.agg
            step_delta = sum(d.delta_bytes for d in ordered)
            step_full = sum(d.full_bytes for d in ordered)
            delta_bytes += step_delta
            full_bytes += step_full
            msg_count = int(sum(int(d.counts.sum()) for d in ordered))
            C_eff = _round_run_width(
                int(max((int(d.counts.max(initial=0)) for d in ordered),
                        default=0)), ec.bucket_cap)
            combinability = (msg_count / acc["distinct"]
                             if acc["distinct"] else 1.0)
            # host mutation inbox vote: an insert that WILL land (decided
            # at commit time from the collected blocks) clears halt on
            # its slot, exactly as the in-device path would have; the
            # apply itself happens per destination in prepare()
            mutation_rate = 0.0
            if any(d.has_mut for d in ordered):
                mutation_rate = acc["proposals"] / max(n_live, 1)
                if acc["applied"]:
                    halt_all = False
            gen += 1
            C_in = C_eff
            prepared = set()
            cur_has_mut = any(d.has_mut for d in ordered)
            sort_on_build = False
            i += 1
            gs = GlobalState(halt=jnp.asarray(halt_all and msg_count == 0),
                             aggregate=jnp.asarray(agg),
                             superstep=jnp.asarray(i, jnp.int32),
                             overflow=gs.overflow,
                             active_count=jnp.asarray(active, jnp.int32),
                             msg_count=jnp.asarray(msg_count, jnp.int32))
            trace.complete("fold", "commit", t_fold, time.time(), i=i)
            if not barrier_free:
                # the PR-4 barrier: rebuild the whole generation and
                # apply every destination's mutations before anything
                # else dispatches
                for q in range(n_sp):
                    prepare(q)
            if store.engine is not None:
                # close the I/O pacing loop: fit the readahead depth to
                # how many observed-latency page faults the superstep's
                # compute window (the collect-wait) can hide
                store.engine.autopace(t_io["wait"])
            interval = store.take_interval()
            pool_now = store.stats()
            faults = interval["misses"]
            looks = faults + interval["hits"]
            spill_rd = interval["spill_read_bytes"]
            spill_wr = interval["spill_write_bytes"]
            rec = coll.record(
                i, active=active, messages=msg_count,
                wall_s=time.time() - ts, recompiled=this_recompiled,
                delta_bytes=delta_bytes, full_bytes=full_bytes,
                change_density=step_delta / max(step_full, 1),
                storage=plan.storage, ooc=True, streaming=stream,
                barrier_free=barrier_free,
                super_partitions=n_sp,
                readiness_stall_s=stall_cell[0] or 0.0,
                dispatch_s=t_io["dispatch"], collect_wait_s=t_io["wait"],
                commit_s=t_io["commit"],
                combinability=combinability,
                mutation_rate=mutation_rate,
                # MEASURED paging, not configuration: a disk_dir whose
                # budget never forces an eviction must not make the cost
                # model price phantom disk traffic. All pager counters
                # are PER-SUPERSTEP (interval counters, reset each
                # record), so the planner sees current behavior.
                spill=bool(spill_rd or spill_wr),
                cache_hit_rate=(1.0 - faults / looks) if looks else 1.0,
                spill_read_bytes=spill_rd,
                spill_write_bytes=spill_wr,
                io_queue_depth=interval.get("io_queue_depth_peak", 0),
                io_queue_depth_mean=interval.get("io_queue_depth_mean",
                                                 0.0),
                # queue-depth DISTRIBUTION (metrics histogram), not just
                # the mean: a spiky engine with a calm average still
                # stalls evictions at its p90
                io_queue_depth_p50=interval.get("io_queue_depth_p50",
                                                0.0),
                io_queue_depth_p90=interval.get("io_queue_depth_p90",
                                                0.0),
                io_queue_depth_max=interval.get("io_queue_depth_max",
                                                0.0),
                readahead_depth=interval.get("readahead_depth",
                                             readahead_pages),
                pager_resident_bytes=pool_now["resident_bytes"],
                pager_peak_bytes=pool_now["peak_resident_bytes"])
            stats.append(rec.as_dict())
            if explain.enabled():
                # audit the plan that EXECUTED this superstep (a switch
                # below only takes effect on the next one)
                explain.superstep(rec, plan=plan,
                                  bucket_cap=ec.bucket_cap)
            if memwatch.enabled():
                # tier snapshot at the superstep boundary: only `sp`
                # partitions are device-resident under the OOC stream
                memwatch.sample(i, store=store, resident_parts=sp)
            if trace.enabled():
                trace.counter("active", active)
                trace.counter("messages", msg_count)
                trace.counter("io_queue_depth",
                              interval.get("io_queue_depth_peak", 0))
            if on_superstep is not None:
                on_superstep(i, stats[-1])
            switched = False
            if controller is not None and not bool(gs.halt):
                with trace.span("replan", "replan"):
                    new_plan = controller.observe(rec,
                                                  bucket_cap=ec.bucket_cap)
                if new_plan is not None:
                    if (new_plan.connector == "partitioning_merging"
                            and plan.connector != "partitioning_merging"
                            and not plan.sender_combine):
                        # the old plan left runs unsorted; give the
                        # merging receiver its dst-sorted runs. Chunks
                        # already built get a one-off host-side sort;
                        # chunks the rolling frontier has not built yet
                        # are sorted at build time (sort_on_build) — the
                        # plan switch is a synchronization event only
                        # for the re-jit, never a full-inbox stall.
                        for q in sorted(prepared):
                            triple = _sort_inbox_runs(tuple(
                                store.get_page((nm, gen, q))
                                for nm in _INBOX))
                            for nm, a in zip(_INBOX, triple):
                                store.put_page((nm, gen, q), a,
                                               immutable=True)
                        sort_on_build = True
                    plan = new_plan
                    if plan.join == "left_outer":
                        # refit the frontier to the live set — safe now
                        # that an outgrown refit regrows instead of
                        # aborting
                        act = active // max(P, 1) + 1
                        ec = dataclasses.replace(
                            ec, frontier_cap=min(
                                max(FRONTIER_FLOOR, act * 4), Np + 8))
                    # dropping the sender combine needs room for
                    # uncombined sends: grow the buckets now instead of
                    # paying an overflow-redo on the next superstep
                    need = default_engine_config(shape_vert, program, plan)
                    if need.bucket_cap > ec.bucket_cap:
                        ec = dataclasses.replace(
                            ec, bucket_cap=need.bucket_cap)
                    step = jit_superstep(program, plan, ec,
                                         donate_vertex=True)
                    seen_widths = set()
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
            # adaptive frontier refit (left-outer plan), mirroring
            # run_host: when the live set collapses, shrink the frontier
            # capacity so each super-partition only pays O(|frontier|)
            if plan.join == "left_outer" and not switched \
                    and not bool(gs.halt):
                act = active // max(P, 1) + 1
                if act * 4 < ec.frontier_cap and ec.frontier_cap > \
                        FRONTIER_FLOOR:
                    ec = dataclasses.replace(
                        ec, frontier_cap=max(FRONTIER_FLOOR, act * 2))
                    step = jit_superstep(program, plan, ec,
                                         donate_vertex=True)
                    seen_widths = set()
                    stats.append(coll.event(
                        i, "frontier-refit",
                        frontier_cap=ec.frontier_cap).as_dict())
                    recompiled = True
                    if controller is not None:
                        controller.note_shape_change()
            if controller is not None and not bool(gs.halt):
                # periodic cost-model re-calibration: after a regrow /
                # refit / switch changed the lowered shapes, refit the
                # analytic constants against the HLO analyzer — at most
                # once per recalibrate_every supersteps (amortizes the
                # probe compiles)
                recal = controller.maybe_recalibrate(program, i)
                if recal is not None:
                    stats.append(coll.event(
                        i, "recalibrate", **recal).as_dict())
            if checkpoint_every and checkpoint_dir \
                    and i % checkpoint_every == 0:
                # checkpoints synchronize the rolling frontier: the
                # saved inbox generation must be complete and every
                # pending mutation applied before the pages export
                t_ck = time.time()
                for q in range(n_sp):
                    prepare(q)
                if store.engine is not None:
                    store.engine.drain()
                save_ooc_checkpoint(
                    checkpoint_dir, i, store, gs, inbox_gen=gen,
                    inbox_width=C_in, sp=sp, plan=plan, ec=ec,
                    controller_state=(controller.state_dict()
                                      if controller is not None else None))
                trace.complete("checkpoint_sync", "checkpoint",
                               t_ck, time.time(), superstep=i)
            if bool(gs.halt):
                break
        # the rolling frontier defers mutation application to each
        # destination's prepare; a run that stops here (max_supersteps,
        # or a halt vote — where the pending applies are no-ops by
        # construction, else the vote would have failed) must land them
        # before the final gather, exactly like run_host's in-step apply
        if cur_has_mut:
            for q in range(n_sp):
                if q not in prepared:
                    _apply_mutation_chunk(store, program, plan, P, sp,
                                          n_sp, gen, q)
        final = VertexRel(**{k: jnp.asarray(store.gather(k))
                             for k in _RELS})
        return RunResult(vertex=final, gs=gs, supersteps=i, stats=stats,
                         wall_s=time.time() - t0, plan=plan)
    finally:
        if store is not None:
            store.close()
