"""Analytical per-superstep cost model over the physical plan space.

The engine executes STATIC shapes, so cost scales with the capacities a
plan implies, not with live tuple counts: a full-outer join always touches
every vertex slot; a left-outer join touches the (adaptively refitted)
frontier capacity, which tracks observed frontier density. The model
mirrors the capacity policies in ``core/driver.py`` (``default_engine_config``
bucket caps, the frontier-refit rule) and the operator structure of
``core/superstep.py``, then converts flops / HBM bytes / exchange bytes to
seconds with the dry-run machine model (``launch/dryrun.py`` roofline
constants). ``hlo_calibrate`` cross-checks the capacity terms against the
trip-count-aware HLO analyzer (``launch/hlo_cost.py``) on a lowered
superstep.

Out-of-core runs add a STORAGE dimension: each streamed super-partition
writes its vertex updates back over the device<->host link, and the
``storage_writeback`` term prices the ``inplace`` (full-block stream) vs
``delta`` (changed-records scatter-merge) policies from the measured
change density (``Observation.change_density`` = delta_bytes/full_bytes
from the OOC statistics stream).

Only RANKING between plans matters for the optimizer; absolute seconds are
the single-chip roofline bound, a lower bound on real wall time.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field

from repro.core.plan import FRONTIER_FLOOR, PhysicalPlan, bucket_capacity

WORD = 4          # bytes per int32/float32 element

# ---- analytic constants (units in comments; hand-tuned against
# ``hlo_calibrate``, which lowers a real superstep and measures it with the
# trip-count-aware HLO analyzer). These are the DEFAULTS: ``MachineModel``
# carries a per-instance copy, and ``calibrate_machine`` refits them per
# backend from lowered probe supersteps at startup when a driver opts in
# (``AdaptiveConfig.calibrate``); the periodic re-calibration loop is
# still a ROADMAP item.

# K_COMPUTE [flops/element]: arithmetic intensity of one fused elementwise
# UDF stage (compute/send/combine bodies lower to a handful of fused ops
# per element; 8 flops/element matches the HLO flop counts of the built-in
# algorithm library within ~2x, which is enough for ranking).
K_COMPUTE = 8.0
# K_SCATTER [dimensionless bytes multiplier]: random gather/scatter
# amplification — each randomly-addressed access moves a cache line /
# memory transaction, not one element, so scattered traffic is charged
# K_SCATTER times the payload bytes (sequential/streamed traffic is
# charged 1x).
K_SCATTER = 4.0
# SORT_PASS_FRAC [dimensionless]: sorts are memory-bound; one argsort +
# permute over n rows is modeled as SORT_PASS_FRAC * log2(n) full
# read+write passes over the keyed payload (cache-resident merge passes
# cost well under a full memory round-trip each, hence the fraction < 1).
SORT_PASS_FRAC = 0.25
FRONTIER_SLACK = 2.0   # refit keeps 2x headroom over the live frontier
MIN_FRONTIER = FRONTIER_FLOOR   # the driver's refit floor
# INTERPRET_PENALTY [dimensionless]: Pallas interpret mode executes the
# kernel's tile program through the host backend — every block move is a
# real HBM/DRAM round trip and the MXU matmul degenerates to scalar code.
# Charged on the kernel path's streamed bytes when the resolved impl is
# "pallas" (interpret) so plan="auto" never picks the emulator over the
# jnp reference off-TPU.
INTERPRET_PENALTY = 8.0


@dataclass(frozen=True)
class MachineModel:
    """Roofline constants of one device (entries of ``MACHINES``) plus the
    analytic cost constants, so ``calibrate_machine`` can refit the
    latter per backend without touching module globals."""
    peak_flops: float            # bf16 flops/s per chip
    hbm_bw: float                # bytes/s per chip
    link_bw: float               # bytes/s per ICI link
    hbm_bytes: float             # device memory per chip
    host_bw: float = 32e9        # bytes/s device<->host (PCIe-class); the
                                 # OOC streaming traffic and storage
                                 # write-back cross this link
    disk_bw: float = 3e9         # bytes/s host DRAM<->local SSD (NVMe,
                                 # sequential); the spill tier's page
                                 # faults and dirty write-backs cross it
                                 # when the buffer cache overflows its
                                 # memory_budget_bytes
    host_mem_bw: float = 100e9   # bytes/s host DRAM (DDR-class); the
                                 # serial inter-superstep inbox restack
                                 # is a host-memory pass, not a PCIe or
                                 # HBM one, and must be priced at host
                                 # memory speed
    net_bw: float = 25e9         # bytes/s BISECTION bandwidth per worker
                                 # for the sharded all_to_all exchange
                                 # (network axis; ethernet/DCN-class
                                 # default — the ICI link_bw stays the
                                 # on-device exchange price)
    net_latency_s: float = 10e-6  # per-exchange dispatch latency: one
                                  # all_to_all STAGE pays it once per
                                  # superstep regardless of plan, but it
                                  # keeps the modeled exchange seconds in
                                  # the measured span's regime when the
                                  # payload is latency-dominated
    k_compute: float = K_COMPUTE
    k_scatter: float = K_SCATTER
    sort_pass_frac: float = SORT_PASS_FRAC
    # does this machine have a matrix unit the Pallas kernels compile to?
    # `estimate` resolves plan.kernel_impl="auto"/"pallas" against THIS
    # flag (not the host process's backend): the planner prices plans for
    # the machine model it is told about, which is what lets one process
    # rank TPU and emulated plans side by side.
    mxu: bool = True


# One entry per ``jax.Device.device_kind`` the system runs on.
# "TPU v5 lite" (TPU v5e): Google Cloud TPU documentation, "TPU v5e" —
# 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of inter-chip
# interconnect per chip (4 links of 50 GB/s).
# "cpu": the host emulator. The "exchange" is a transpose through memory
# and the "host link" is a memcpy, not an ICI/PCIe hop (the
# delta-vs-inplace distinction survives: scatter amplification vs
# streaming is a memory-system property). The DISK is a real disk either
# way, so disk_bw keeps its default. The fake host devices' all_to_all is
# a memcpy, but each exchange STAGE pays a real dispatch latency
# (ms-class on the CPU client) — this is what keeps the modeled exchange
# within the clamp of the measured-span calibration
# (Observation.net_scale). No Pallas kernel compiles there (mxu=False).
_V5E = MachineModel(peak_flops=197e12, hbm_bw=819e9, link_bw=50e9,
                    hbm_bytes=16e9)
MACHINES = {
    "TPU v5 lite": _V5E,
    "cpu": MachineModel(peak_flops=_V5E.peak_flops, hbm_bw=_V5E.hbm_bw,
                        link_bw=_V5E.hbm_bw, hbm_bytes=_V5E.hbm_bytes,
                        host_bw=_V5E.hbm_bw, host_mem_bw=_V5E.hbm_bw,
                        net_bw=_V5E.hbm_bw, net_latency_s=1e-3, mxu=False),
}
DEFAULT_MACHINE = MACHINES["TPU v5 lite"]
EMULATED_MACHINE = MACHINES["cpu"]


def machine_for(device_kind: str | None = None) -> MachineModel:
    """The ``MACHINES`` entry of ``device_kind`` (default: the first
    device JAX sees). A device without an entry is an error, not a
    default."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    try:
        return MACHINES[device_kind]
    except KeyError:
        raise ValueError(f"no machine model for device_kind "
                         f"{device_kind!r}; known: {sorted(MACHINES)}") \
            from None


@dataclass(frozen=True)
class GraphStats:
    """Static per-job facts the cost model needs (paper Table 1 shapes)."""
    n_vertices: int
    n_edges: int
    n_partitions: int
    vertex_capacity: int   # Np: slots per partition
    edge_capacity: int     # Ep: edge slots per partition
    value_dims: int = 1
    msg_dims: int = 1

    @classmethod
    def from_vertex(cls, vert, program) -> "GraphStats":
        import numpy as np
        P, Np = vert.vid.shape
        n_v = int(np.asarray(vert.vid >= 0).sum())
        n_e = int(np.asarray(vert.edge_src >= 0).sum())
        return cls(n_vertices=n_v, n_edges=n_e, n_partitions=P,
                   vertex_capacity=Np,
                   edge_capacity=vert.edge_src.shape[1],
                   value_dims=program.value_dims,
                   msg_dims=program.msg_dims)


@dataclass(frozen=True)
class Observation:
    """Runtime statistics the model conditions on (from planner.stats)."""
    frontier_density: float = 1.0   # active fraction of LIVE vertices
    messages: int = 0               # live messages last superstep (total)
    superstep: int = 0
    # live per-(src,dst) bucket capacity (0 = unknown/initial): running
    # drivers only GROW buckets, so a candidate plan cannot realize a
    # smaller message capacity than the engine already carries
    bucket_cap: int = 0
    # fraction of vertex-value bytes that changed last superstep — the OOC
    # driver measures it as delta_bytes / full_bytes per superstep; drives
    # the storage (write-back) dimension. 1.0 = everything changed.
    change_density: float = 1.0
    # True when the job streams super-partitions through the device (OOC):
    # only then does the storage write-back cross the host link and enter
    # the cost; in-memory drivers keep the Vertex relation resident.
    ooc: bool = False
    # True when the OOC executor PIPELINES the super-partition stream
    # (core/ooc.py stream=True): host-link transfers then overlap device
    # compute, so the model prices the superstep as max(step, transfer)
    # instead of step + transfer (PlanCost.overlap_host).
    streaming: bool = False
    # True when the executor runs the BARRIER-FREE superstep pipeline
    # (core/ooc.py barrier_free=True): the inter-superstep inbox rebuild
    # and mutation apply run per destination, overlapped with the next
    # superstep's compute, so only 1/super_partitions of that work stays
    # on the serial critical path (the first destination's prepare) —
    # the barrier executor pays all of it serially.
    barrier_free: bool = False
    # super-partitions the OOC stream cycles through (P / budget): sets
    # the serial share of the rebuild under barrier-free execution.
    super_partitions: int = 1
    # observed device-idle gap between supersteps (seconds) and the I/O
    # engine's queue depth — surfaced for diagnostics/benchmarks; the
    # model prices the rebuild analytically (plan-dependent), not from
    # the raw observed stall, which mixes in compile and fold noise.
    readiness_stall_s: float = 0.0
    io_queue_depth: float = 0.0
    # measurement loop closure (ROADMAP "Measurement-driven planning"):
    # the controller EWMAs the measured readiness stall across steady
    # (non-recompile) supersteps and divides it by the analytic serial
    # leg of the CURRENT plan to get `serial_scale` — a plan-independent
    # calibration multiplier applied to every candidate's serial leg, so
    # ranking stays plan-relative but the serial-vs-overlapped tradeoff
    # is priced at the stall the hardware actually delivers.
    # `stall_ewma_s` rides along for diagnostics; < 0 = no measurement.
    stall_ewma_s: float = -1.0
    serial_scale: float = 1.0
    # messages per DISTINCT destination, measured from the run-structured
    # host inbox (>= 1). High combinability means a sender combine
    # collapses the inbox that crosses the host link; ~1 means the
    # sort+fold buys nothing — this is what makes the sender_combine
    # dimension replannable from observed statistics.
    combinability: float = 1.0
    # insert proposals per live vertex last superstep: the host mutation
    # inbox's device->host + scatter-merge traffic.
    mutation_rate: float = 0.0
    # ---- network axis (sharded driver) -------------------------------
    # True when the run executes on a multi-device mesh with the
    # all_to_all exchange stage (core/sharded.py): the exchange then
    # crosses the NETWORK (machine.net_bw), not device memory, and the
    # model prices it per worker over the bisection.
    sharded: bool = False
    n_workers: int = 1
    # measured per-superstep exchange wire bytes / stage stall (seconds),
    # lifted from the driver's ``exchange`` span — diagnostics plus the
    # raw inputs of the net calibration below.
    exchange_bytes: float = 0.0
    exchange_stall_s: float = 0.0
    # measurement loop closure for the network axis, mirroring
    # serial_scale: the controller EWMAs the measured exchange stall and
    # divides it by the CURRENT plan's analytic net leg; every
    # candidate's net price shifts by the clamped ratio, so connector
    # choice trades against OBSERVED interconnect pressure.
    # exchange_ewma_s < 0 = no measurement yet.
    exchange_ewma_s: float = -1.0
    net_scale: float = 1.0
    # True when the OOC store runs the DISK TIER (a memory_budget_bytes
    # smaller than the working set, spilling through storage/pager): page
    # faults and dirty write-backs then cross the disk axis.
    spilling: bool = False
    # pager hit rate (fraction of page lookups served from DRAM) from the
    # statistics stream; 1 - hit_rate of the streamed bytes fault from
    # disk.
    hit_rate: float = 1.0


@dataclass
class PlanCost:
    flops: float = 0.0
    bytes: float = 0.0            # HBM traffic per partition
    exchange_bytes: float = 0.0   # cross-partition link bytes
    host_bytes: float = 0.0       # device<->host link bytes (OOC only)
    disk_bytes: float = 0.0       # DRAM<->disk spill-tier bytes (OOC
                                  # under a memory budget only)
    net_bytes: float = 0.0        # all_to_all wire bytes per worker
                                  # (sharded runs only)
    # seconds of the all_to_all exchange STAGE: the sharded driver runs
    # it as its own blocking dispatch between supersteps, so it is
    # ADDITIVE on the critical path (never hidden by the overlap max),
    # like the serial leg but priced at net_bw + a per-stage latency.
    net_seconds: float = 0.0
    terms: dict = field(default_factory=dict)   # per-operator seconds
    # pipelined OOC streaming: the host link and the disk both run
    # concurrently with the device, so total seconds =
    # max(device, host, disk) instead of their sum
    overlap_host: bool = False
    # SERIAL leg of the critical path: inter-superstep work no pipeline
    # overlaps (the barrier executor's whole inbox rebuild; barrier-free
    # keeps only the first destination's share). Added on top of the
    # overlap max — this is what turns the streamed ``max(device, host,
    # disk)`` formula into a critical-path estimate.
    serial_seconds: float = 0.0
    # per-term raw components (flops / bytes per axis) — what the
    # roofline benchmark plots against the machine ceilings; `terms`
    # above only keeps the converted seconds
    detail: dict = field(default_factory=dict)

    def _detail(self, term: str) -> dict:
        return self.detail.setdefault(term, {
            "flops": 0.0, "hbm_bytes": 0.0, "exchange_bytes": 0.0,
            "host_bytes": 0.0, "disk_bytes": 0.0, "serial_bytes": 0.0,
            "net_bytes": 0.0})

    def add(self, term: str, machine: MachineModel, *, flops: float = 0.0,
            bytes: float = 0.0, exchange_bytes: float = 0.0,
            host_bytes: float = 0.0, disk_bytes: float = 0.0):
        self.flops += flops
        self.bytes += bytes
        self.exchange_bytes += exchange_bytes
        self.host_bytes += host_bytes
        self.disk_bytes += disk_bytes
        self.terms[term] = self.terms.get(term, 0.0) + (
            flops / machine.peak_flops + bytes / machine.hbm_bw +
            exchange_bytes / machine.link_bw +
            host_bytes / machine.host_bw +
            disk_bytes / machine.disk_bw)
        d = self._detail(term)
        d["flops"] += flops
        d["hbm_bytes"] += bytes
        d["exchange_bytes"] += exchange_bytes
        d["host_bytes"] += host_bytes
        d["disk_bytes"] += disk_bytes

    def add_serial(self, term: str, machine: MachineModel, *,
                   bytes: float = 0.0):
        """Host-memory traffic on the SERIAL inter-superstep path (the
        readiness leg): charged at host DRAM bandwidth
        (``machine.host_mem_bw`` — not device HBM, which would
        underprice the leg ~8x on the default machine) and excluded
        from the overlap max — the device is idle while it runs."""
        s = bytes / machine.host_mem_bw
        self.serial_seconds += s
        self.terms[term] = self.terms.get(term, 0.0) + s
        self._detail(term)["serial_bytes"] += bytes

    def scale_serial(self, factor: float, term: str = "inbox_rebuild"):
        """Apply a measured calibration multiplier to the serial leg
        (the Observation.serial_scale closure): scales both the total
        and the named term so reports stay consistent."""
        self.serial_seconds *= factor
        if term in self.terms:
            self.terms[term] *= factor

    def add_net(self, term: str, machine: MachineModel, *,
                net_bytes: float = 0.0, latency_s: float = 0.0):
        """All_to_all wire traffic of the sharded exchange stage: priced
        at the machine's bisection bandwidth plus a per-stage dispatch
        latency, and kept out of the overlap max — the stage blocks
        between the superstep dispatch and the next prepare."""
        s = net_bytes / machine.net_bw + latency_s
        self.net_bytes += net_bytes
        self.net_seconds += s
        self.terms[term] = self.terms.get(term, 0.0) + s
        self._detail(term)["net_bytes"] += net_bytes

    def scale_net(self, factor: float, term: str = "exchange_net"):
        """Measured calibration multiplier for the network leg (the
        Observation.net_scale closure), mirroring ``scale_serial``."""
        self.net_seconds *= factor
        if term in self.terms:
            self.terms[term] *= factor

    def device_seconds(self, machine: MachineModel = DEFAULT_MACHINE) \
            -> float:
        return (self.flops / machine.peak_flops +
                self.bytes / machine.hbm_bw +
                self.exchange_bytes / machine.link_bw)

    def host_seconds(self, machine: MachineModel = DEFAULT_MACHINE) \
            -> float:
        return self.host_bytes / machine.host_bw

    def disk_seconds(self, machine: MachineModel = DEFAULT_MACHINE) \
            -> float:
        return self.disk_bytes / machine.disk_bw

    def seconds(self, machine: MachineModel = DEFAULT_MACHINE) -> float:
        dev = self.device_seconds(machine)
        hst = self.host_seconds(machine)
        dsk = self.disk_seconds(machine)
        if self.overlap_host:
            # CRITICAL-PATH estimate: the streaming executor hides the
            # slower legs behind the slowest — steady state settles at
            # max(device, host_link, disk) — plus the serial readiness
            # leg nothing overlaps (the inter-superstep rebuild share).
            # The small residual breaks ties among transfer-bound plans
            # toward the one doing less total work (overlap is never
            # quite perfect, and less hidden work frees the pipeline
            # sooner).
            return (max(dev, hst, dsk) + self.serial_seconds
                    + self.net_seconds + 1e-3 * (dev + hst + dsk))
        return dev + hst + dsk + self.serial_seconds + self.net_seconds


def bucket_cap(plan: PhysicalPlan, g: GraphStats, slack: float = 1.5) -> int:
    """The drivers' per-bucket capacity policy (core.plan.bucket_capacity)
    at this graph's shapes."""
    return bucket_capacity(plan, g.edge_capacity, g.vertex_capacity,
                           g.n_partitions, slack=slack)


def refit_frontier_cap(g: GraphStats, density: float) -> int:
    """Frontier capacity the driver's adaptive refit converges to.
    `density` is the active fraction of LIVE vertices."""
    live_pp = density * g.n_vertices / max(g.n_partitions, 1)
    return int(min(g.vertex_capacity,
                   max(MIN_FRONTIER, FRONTIER_SLACK * live_pp)))


def _sort_bytes(n: float, width: float, frac: float) -> float:
    """Memory traffic of one argsort+permute over n keyed rows of `width`
    bytes (log-pass model; `frac` = the machine's sort_pass_frac)."""
    n = max(n, 2.0)
    return frac * math.log2(n) * n * width


def estimate(plan: PhysicalPlan, g: GraphStats, obs: Observation,
             machine: MachineModel = DEFAULT_MACHINE) -> PlanCost:
    """Per-superstep, per-partition cost of running `plan` at the observed
    statistics. Follows superstep.py's operator order D1..D3."""
    P, Np, Ep = g.n_partitions, g.vertex_capacity, g.edge_capacity
    D, V = g.msg_dims, g.value_dims
    kc, ks = machine.k_compute, machine.k_scatter
    sort_b = lambda n, w: _sort_bytes(n, w, machine.sort_pass_frac)
    f = min(max(obs.frontier_density, 1.0 / max(Np, 1)), 1.0)
    c = PlanCost()
    cap = max(bucket_cap(plan, g), obs.bucket_cap)
    M = P * cap                       # received message capacity
    msg_w = (1 + D) * WORD + 1        # dst + payload + valid per slot

    # hot-path kernel dispatch, resolved against the MACHINE MODEL (not
    # the host backend): "auto" prices as pallas_tpu when the machine has
    # an MXU, as the jnp reference otherwise — which is exactly how the
    # engine will resolve it there, so plan="auto" picks the kernel path
    # per backend. Interpret mode ("pallas" off-MXU) is an emulator and
    # carries INTERPRET_PENALTY on its streamed bytes.
    from repro.kernels import backend as _kbackend
    kern = _kbackend.resolve(plan.kernel_impl, tpu=machine.mxu)
    pen = INTERPRET_PENALTY if kern == "pallas" else 1.0
    kern_gather = kern != "ref" and plan.join == "full_outer"
    # (the engine only folds named monoids through the kernel; the model
    # can't see combine_op here, so custom-combine programs are mildly
    # mispriced on the kernel path — acceptable: ranking is plan-relative
    # and every candidate shares the same kernel_impl by default)
    kern_combine = kern != "ref" and plan.sender_combine

    # D1: receiver group-by over the full message capacity
    if plan.connector == "partitioning_merging":
        # presorted runs: one segmented scan, then a scatter of the <=1
        # surviving partial per (run, dst) — run_combine_dense
        c.add("recv_groupby", machine, flops=kc * M * D,
              bytes=(1 + ks) * M * msg_w)
    elif plan.groupby == "sort":
        c.add("recv_groupby", machine, flops=kc * M * D,
              bytes=sort_b(M, msg_w) + M * msg_w)
    else:  # scatter (hash)
        c.add("recv_groupby", machine, flops=kc * M * D,
              bytes=ks * M * msg_w)

    # D1/D2: join + compute + write-back
    if plan.join == "full_outer":
        c.add("join_compute", machine, flops=kc * Np * (V + D),
              bytes=Np * (2 * V + D + 1) * WORD)
        e_work = Ep
    else:
        F = refit_frontier_cap(g, f)
        # mask scan + cumsum over all slots, edge-gate prepass over all
        # edges, then gather/compute/scatter-back only F rows
        c.add("join_compute", machine,
              flops=kc * F * (V + D),
              bytes=(Np + Ep) * WORD +
              ks * F * (2 * V + D + 1) * WORD)
        # gen_messages compacts the edge stream to EF = min(8F, Ep); when
        # the live frontier's edges (~f*Ep) outgrow that, the driver's
        # overflow-regrow doubles the capacity until they fit, so the
        # effective edge work is bounded below by the live edge count
        e_work = min(max(8 * F, MIN_FRONTIER, f * Ep), Ep)

    # D3: edge-parallel payload generation
    if kern_gather:
        # csr_spmv kernel: the value gather becomes row-blocked one-hot
        # MXU matmuls ((BM x BR) @ (BR x 2V) per tile — 2V: the value
        # channel plus the non-finite class channel), so the random HBM
        # gather's scatter amplification disappears: the value block and
        # edge stream are READ ONCE, sequentially, and the matmul flops
        # buy the addressing. Off-MXU interpret mode streams the same
        # bytes through the emulator at INTERPRET_PENALTY.
        from repro.kernels.backend import GATHER_BLOCK_R
        c.add("send", machine,
              flops=kc * e_work * D +
              2.0 * e_work * GATHER_BLOCK_R * 2 * V,
              bytes=pen * e_work * (V + D + 2) * WORD)
    else:
        c.add("send", machine, flops=kc * e_work * D,
              bytes=ks * e_work * (V + D + 2) * WORD)

    # D3/D7: sender combine = sort + segmented fold over the edge stream
    if plan.sender_combine:
        if kern == "pallas_tpu":
            # segment_combine kernel: the fold runs VMEM-resident inside
            # ONE streamed pass over the sorted run (the jnp fold's
            # multi-pass scan through HBM disappears); the dst argsort
            # remains either way
            c.add("sender_combine", machine, flops=kc * e_work * D,
                  bytes=sort_b(e_work, msg_w) + 0.5 * e_work * msg_w)
        else:
            c.add("sender_combine", machine, flops=kc * e_work * D,
                  bytes=sort_b(e_work, msg_w) + pen * e_work * msg_w)

    # connector bucket build (bucket_by_owner): the merging connector
    # with hash partitioning sorts twice (by dst, then stably by owner);
    # range partitioning needs one dst sort — or none when the sender
    # combine already left the stream dst-ascending (owners contiguous);
    # the plain hash connector sorts once by owner
    if plan.partition == "range":
        n_sorts = 0 if plan.sender_combine else 1
    elif plan.connector == "partitioning_merging":
        n_sorts = 2
    else:
        n_sorts = 1
    # with the kernel fold in play the scatter->combine->pack leg is fused:
    # combined survivors are compacted to the bucket capacity (M) BEFORE
    # routing, so the connector never sees more than M rows and the
    # intermediate (P, Ep, C) payload relation is never materialized
    e_pack = min(e_work, float(M)) if kern_combine else e_work
    c.add("connector", machine, flops=kc * e_pack,
          bytes=n_sorts * sort_b(e_pack, msg_w) +
          ks * e_pack * msg_w)

    # exchange: fixed-capacity buckets cross the links whole. On a
    # sharded mesh the cross-WORKER share crosses the network instead
    # (all_to_all over the bisection, plus one per-stage dispatch
    # latency — plan-independent, so it shifts every candidate equally
    # and only matters for matching the measured span's magnitude);
    # the intra-worker share stays a link/memory move. net_scale is the
    # controller's measured-exchange calibration multiplier.
    if obs.sharded and obs.n_workers > 1:
        W = obs.n_workers
        P_l = max(P // W, 1)
        c.add("exchange", machine,
              exchange_bytes=M * msg_w * (P_l - 1) / max(P, 1))
        c.add_net("exchange_net", machine,
                  net_bytes=M * msg_w * (P - P_l) / max(P, 1),
                  latency_s=machine.net_latency_s)
        if obs.net_scale != 1.0:
            c.scale_net(obs.net_scale)
    else:
        c.add("exchange", machine,
              exchange_bytes=M * msg_w * (P - 1) / max(P, 1))

    if obs.ooc:
        # super-partition streaming I/O: every superstep the vertex block
        # (vid/halt/value/edges) and its inbox runs go H2D, and the
        # vid/halt/edge updates plus collected sender buckets come back
        # D2H (the value write-back is priced separately below, by
        # storage policy). The inbox that goes UP is run-trimmed to its
        # occupancy, so it is priced from live messages — and a sender
        # combine divides it by the measured COMBINABILITY (messages per
        # distinct destination): that is the term that lets observed
        # combinability drive the sender_combine replan dimension. The
        # collected buckets coming DOWN are capacity-sized (M).
        if obs.messages > 0:
            mpp = obs.messages / max(P, 1)
            if plan.sender_combine:
                mpp = mpp / max(obs.combinability, 1.0)
            inbox_up = min(float(M), mpp + P) * msg_w
        else:
            inbox_up = M * msg_w    # superstep 0: no measurement yet
        up = Np * ((1 + V) * WORD + 1) + 3 * Ep * WORD + inbox_up
        down = Np * (WORD + 1) + 2 * Ep * WORD + M * msg_w
        c.add("stream_io", machine, host_bytes=up + down)
        # storage write-back: a streamed super-partition must push its
        # vertex VALUE updates back over the device<->host link and into
        # the host store every superstep. `change_density` is the
        # measured delta_bytes/full_bytes ratio from the OOC statistics
        # stream.
        vblock = Np * V * WORD
        cd = min(max(obs.change_density, 0.0), 1.0)
        if plan.storage == "delta":
            # changed (slot, value) records cross the link; the compare
            # streams the store once and the merge scatters the survivors
            c.add("storage_writeback", machine,
                  host_bytes=cd * Np * (1 + V) * WORD,
                  bytes=vblock + ks * cd * vblock)
        else:
            # the full value block streams across the link and the store
            c.add("storage_writeback", machine,
                  host_bytes=vblock, bytes=vblock)
        # host mutation inbox: insert proposals cross the link D2H and
        # scatter-merge into the host store at the barrier
        if obs.mutation_rate > 0.0:
            mut = obs.mutation_rate * Np
            c.add("mutation_io", machine,
                  host_bytes=mut * ((1 + V) * WORD + 1),
                  bytes=ks * mut * (1 + V) * WORD)
        # DISK TIER: when the buffer cache spills (memory budget smaller
        # than the working set), the missed fraction of every streamed
        # page faults in from disk and the dirty write-back goes out to
        # it. Reads miss at (1 - hit_rate); writes are storage-policy
        # shaped — `inplace` rewrites the value pages every superstep,
        # `delta` only dirties pages with changed rows (≈ change
        # density), and the inbox generation is rewritten either way.
        if obs.spilling:
            miss = min(max(1.0 - obs.hit_rate, 0.0), 1.0)
            rel_pages = Np * ((1 + V) * WORD + 1) + 3 * Ep * WORD
            reads = miss * (rel_pages + inbox_up)
            writes = inbox_up + (cd * vblock if plan.storage == "delta"
                                 else vblock)
            c.add("disk_io", machine, disk_bytes=reads + writes)
        # INTER-SUPERSTEP READINESS LEG: the run-structured inbox
        # restack (source-major stack -> destination-major transpose ->
        # trim) streams the inbox through host memory twice. Under the
        # barrier executor it all runs serially between supersteps (the
        # device idles); barrier-free keeps only the FIRST destination's
        # share on the critical path — the rest overlaps the next
        # superstep's compute. Plan-dependent through the inbox
        # occupancy (a sender combine shrinks what must be restacked),
        # which is what lets the optimizer trade rebuild time against
        # combine cost under either schedule.
        rebuild = 2.0 * inbox_up
        if obs.barrier_free:
            rebuild /= max(obs.super_partitions, 1)
        c.add_serial("inbox_rebuild", machine, bytes=rebuild)
        if obs.serial_scale != 1.0:
            c.scale_serial(obs.serial_scale)
        # the pipelined executor overlaps the host link and the disk
        # with compute: rank plans by max(device, host, disk) (plus the
        # serial readiness leg) instead of their sum
        c.overlap_host = bool(obs.streaming)
    return c


def hlo_calibrate(program, plan: PhysicalPlan, g: GraphStats,
                  obs: Observation = Observation()) -> "object":
    """Lower one emulated superstep at the capacities `estimate` assumes
    and measure it with the trip-count-aware HLO analyzer — the ground
    truth the analytic constants are calibrated against. Returns a
    ``launch.hlo_cost.Cost``. Compile-time heavy; used by benchmarks and
    calibration tests, not by the per-superstep optimizer loop."""
    import jax
    import jax.numpy as jnp

    from repro.core.relations import (N_OVERFLOW, GlobalState, MsgRel,
                                      VertexRel)
    from repro.core.superstep import EngineConfig, make_superstep
    from repro.launch import hlo_cost

    cap = bucket_cap(plan, g)
    ec = EngineConfig(n_parts=g.n_partitions, bucket_cap=cap,
                      frontier_cap=refit_frontier_cap(
                          g, obs.frontier_density))
    step = make_superstep(program, plan, ec)
    P, Np, Ep = g.n_partitions, g.vertex_capacity, g.edge_capacity
    sds = jax.ShapeDtypeStruct
    vert = VertexRel(vid=sds((P, Np), jnp.int32),
                     halt=sds((P, Np), jnp.bool_),
                     value=sds((P, Np, g.value_dims), jnp.float32),
                     edge_src=sds((P, Ep), jnp.int32),
                     edge_dst=sds((P, Ep), jnp.int32),
                     edge_val=sds((P, Ep), jnp.float32))
    msg = MsgRel(dst=sds((P, P * cap), jnp.int32),
                 payload=sds((P, P * cap, g.msg_dims), jnp.float32),
                 valid=sds((P, P * cap), jnp.bool_))
    gs = GlobalState(halt=sds((), jnp.bool_),
                     aggregate=sds((program.agg_dims,), jnp.float32),
                     superstep=sds((), jnp.int32),
                     overflow=sds((N_OVERFLOW,), jnp.int32),
                     active_count=sds((), jnp.int32),
                     msg_count=sds((), jnp.int32))
    compiled = jax.jit(step).lower(vert, msg, gs).compile()
    return hlo_cost.analyze(compiled.as_text())


# (backend name, combine_op) -> fitted (k_compute, k_scatter,
# sort_pass_frac); the one-shot startup calibration
# (AdaptiveConfig.calibrate) fills this once per process — the constants
# are compiler/backend properties, but the probe plans legal for a custom
# combine UDF differ from the monoid ones, so the fit is cached per
# combine class too. The periodic refresh loop stays future work.
_CALIBRATED: dict = {}


def _fit_constants(program, g: GraphStats, machine: MachineModel):
    """Refit (k_compute, k_scatter, sort_pass_frac) against the HLO
    analyzer. Two probe plans (a scatter-heavy and a sort-heavy group-by;
    sort-only for custom combine UDFs) are lowered at the capacities
    ``estimate`` assumes and measured with ``hlo_calibrate``. The model's
    flops are linear in k_compute and its bytes are affine in
    (k_scatter, sort_pass_frac), so unit-coefficient estimates turn the
    fit into one ratio and one 2x2 least-squares solve. Fitted values are
    clamped to sane ranges; a degenerate system keeps the defaults."""
    import numpy as np
    obs = Observation(frontier_density=1.0)
    # probes pin kernel_impl="ref": hlo_calibrate lowers on the host CPU
    # where the reference path runs, so the fit must price the same path
    # it measures (the kernel path's constants ride along unfitted)
    if program.combine_op == "custom":
        probes = [PhysicalPlan(join="full_outer", groupby="sort",
                               connector="partitioning",
                               sender_combine=False, kernel_impl="ref"),
                  PhysicalPlan(join="full_outer", groupby="sort",
                               connector="partitioning",
                               sender_combine=True, kernel_impl="ref")]
    else:
        probes = [PhysicalPlan(join="full_outer", groupby="scatter",
                               connector="partitioning",
                               sender_combine=False, kernel_impl="ref"),
                  PhysicalPlan(join="full_outer", groupby="sort",
                               connector="partitioning",
                               sender_combine=False, kernel_impl="ref")]
    P = max(g.n_partitions, 1)   # hlo measures all partitions; the model
    unit = lambda kc, ks, sp: dataclasses.replace(   # is per-partition
        machine, k_compute=kc, k_scatter=ks, sort_pass_frac=sp)
    kcs, rows, rhs = [], [], []
    for p in probes:
        meas = hlo_calibrate(program, p, g, obs)
        f_unit = estimate(p, g, obs, unit(1.0, 0.0, 0.0)).flops
        if f_unit > 0 and meas.flops > 0:
            kcs.append(meas.flops / P / f_unit)
        base = estimate(p, g, obs, unit(0.0, 0.0, 0.0)).bytes
        scat = estimate(p, g, obs, unit(0.0, 1.0, 0.0)).bytes - base
        srt = estimate(p, g, obs, unit(0.0, 0.0, 1.0)).bytes - base
        rows.append([scat, srt])
        rhs.append(meas.bytes / P - base)
    kc = (float(np.clip(np.mean(kcs), 0.5, 128.0)) if kcs
          else machine.k_compute)
    ks, sp = machine.k_scatter, machine.sort_pass_frac
    try:
        sol, *_ = np.linalg.lstsq(np.asarray(rows, float),
                                  np.asarray(rhs, float), rcond=None)
        if np.isfinite(sol).all():
            ks = float(np.clip(sol[0], 1.0, 64.0))
            sp = float(np.clip(sol[1], 0.02, 4.0))
    except np.linalg.LinAlgError:
        pass
    return kc, ks, sp


def calibrate_machine(program, g: GraphStats,
                      machine: MachineModel = DEFAULT_MACHINE,
                      *, refresh: bool = False) -> MachineModel:
    """Startup calibration (opt-in via ``AdaptiveConfig.calibrate``):
    lower probe supersteps on the CURRENT backend, measure them with the
    trip-count-aware HLO analyzer and return a MachineModel whose
    analytic constants are refit to what this backend's compiler
    actually emits, instead of the hand-tuned K_COMPUTE / K_SCATTER /
    SORT_PASS_FRAC. Compile-time heavy, so the fit is cached per backend
    for the life of the process; ``refresh=True`` bypasses the cache and
    refits in place — the periodic re-calibration path
    (``AdaptiveConfig.recalibrate_every``) uses it after a regrow /
    refit / plan switch changes the lowered shapes."""
    import jax
    key = (jax.default_backend(), program.combine_op)
    if refresh or key not in _CALIBRATED:
        _CALIBRATED[key] = _fit_constants(program, g, machine)
    kc, ks, sp = _CALIBRATED[key]
    return dataclasses.replace(machine, k_compute=kc, k_scatter=ks,
                               sort_pass_frac=sp)
