"""The adaptive cost-based plan optimizer: cost-model rankings, plan-space
pruning, message-layout migration, and end-to-end adaptive runs."""
import numpy as np
import pytest

from repro.core import (PhysicalPlan, VertexProgram, gather_values,
                        load_graph, run_host, run_jit)
from repro.graph import SSSP, PageRank
from repro.graph.generators import grid_graph
from repro.planner import (AdaptiveConfig, GraphStats, Observation,
                           StatsCollector, choose, estimate, migrate_msgs,
                           plan_space, rank)

WEB = GraphStats(n_vertices=100_000, n_edges=800_000, n_partitions=8,
                 vertex_capacity=16_250, edge_capacity=100_000,
                 value_dims=1, msg_dims=1)


def _join_cost(join, density):
    plan = PhysicalPlan(join=join)
    return estimate(plan, WEB, Observation(frontier_density=density)) \
        .seconds()


def test_cost_ranks_left_outer_below_full_once_sparse():
    """The paper's Figure 14 regime: full-outer wins message-dense,
    left-outer wins once the frontier collapses."""
    assert _join_cost("full_outer", 1.0) <= _join_cost("left_outer", 1.0)
    assert _join_cost("left_outer", 0.01) < _join_cost("full_outer", 0.01)
    # full-outer's cost is density-independent (it always scans all slots);
    # left-outer's falls with the frontier
    assert _join_cost("left_outer", 0.01) < _join_cost("left_outer", 1.0)


def test_choose_switches_join_with_density():
    sssp = SSSP(source=0)
    dense, _ = choose(sssp, WEB, Observation(frontier_density=1.0))
    sparse, _ = choose(sssp, WEB, Observation(frontier_density=0.01))
    assert dense.join == "full_outer"
    assert sparse.join == "left_outer"


class _CustomCombine(VertexProgram):
    combine_op = "custom"

    def combine(self, a, b):
        return a + b


def test_optimizer_rejects_invalid_combos():
    """scatter group-by + custom combine is pruned from the space."""
    prog = _CustomCombine()
    plans = list(plan_space(prog))
    assert plans and all(p.groupby == "sort" for p in plans)
    plan, _ = choose(prog, WEB, Observation())
    plan.validate(prog.combine_op)  # must not raise
    # restricting the space to the invalid combo is an error, not a pick
    with pytest.raises(ValueError):
        choose(prog, WEB, Observation(), groupbys=("scatter",))


def test_rank_is_sorted_and_covers_space():
    pr = PageRank(100_000)
    ranked = rank(pr, WEB, Observation(frontier_density=1.0))
    assert len(ranked) == 16   # 2 joins x 2 group-bys x 2 conns x 2 sc
    secs = [c.seconds() for _, c in ranked]
    assert secs == sorted(secs)


def test_storage_dimension_defaults_inherited_and_ooc_doubles_space():
    """In-memory spaces inherit the base storage (write-back never paid,
    so varying it only makes ties); storages=STORAGES doubles the space."""
    from repro.core import STORAGES
    pr = PageRank(100_000)
    assert len(list(plan_space(pr))) == 16
    both = list(plan_space(pr, storages=STORAGES))
    assert len(both) == 32
    assert {p.storage for p in both} == {"inplace", "delta"}


def test_storage_cost_follows_measured_change_density():
    """The storage_writeback term prices delta by the measured
    delta/full byte ratio: sparse updates favor delta, dense inplace —
    and without ooc the policies tie (no write-back crosses the link)."""
    inplace = PhysicalPlan(storage="inplace")
    delta = PhysicalPlan(storage="delta")
    sparse = Observation(ooc=True, change_density=0.01)
    dense = Observation(ooc=True, change_density=1.0)
    assert estimate(delta, WEB, sparse).seconds() < \
        estimate(inplace, WEB, sparse).seconds()
    assert estimate(inplace, WEB, dense).seconds() < \
        estimate(delta, WEB, dense).seconds()
    in_mem = Observation(change_density=0.01)
    assert estimate(delta, WEB, in_mem).seconds() == \
        estimate(inplace, WEB, in_mem).seconds()
    # the write-back term lives on the device<->host link
    assert estimate(inplace, WEB, sparse).host_bytes > 0
    assert estimate(inplace, WEB, in_mem).host_bytes == 0


def test_streaming_observation_prices_with_overlap():
    """Under the pipelined OOC executor the host link overlaps compute:
    the model prices the superstep as a CRITICAL PATH — max(device,
    host) plus the serial inter-superstep readiness leg (the inbox
    rebuild nothing overlaps) — instead of the plain sum, so streaming
    cost is never above synchronous cost and is strictly below it
    whenever both sides are non-trivial."""
    plan = PhysicalPlan()
    sync = estimate(plan, WEB, Observation(ooc=True))
    strm = estimate(plan, WEB, Observation(ooc=True, streaming=True))
    assert not sync.overlap_host and strm.overlap_host
    # identical traffic, different composition rule
    assert strm.host_bytes == sync.host_bytes
    assert strm.bytes == sync.bytes
    assert strm.serial_seconds == sync.serial_seconds > 0
    assert strm.seconds() < sync.seconds()
    dev, hst = strm.device_seconds(), strm.host_seconds()
    assert strm.seconds() == pytest.approx(
        max(dev, hst) + strm.serial_seconds, rel=0.01)
    # in-memory observations are untouched by the streaming flag
    mem = estimate(plan, WEB, Observation(streaming=True))
    assert not mem.overlap_host and mem.host_bytes == 0
    assert mem.serial_seconds == 0


def test_barrier_free_shrinks_the_serial_readiness_leg():
    """barrier_free keeps only the first destination's share of the
    inbox rebuild on the serial path (1/super_partitions); the barrier
    executor pays all of it — so the model prefers the barrier-free
    schedule and scales its advantage with the super-partition count."""
    plan = PhysicalPlan()
    bar = estimate(plan, WEB, Observation(ooc=True, streaming=True,
                                          super_partitions=4))
    bf4 = estimate(plan, WEB, Observation(ooc=True, streaming=True,
                                          barrier_free=True,
                                          super_partitions=4))
    bf8 = estimate(plan, WEB, Observation(ooc=True, streaming=True,
                                          barrier_free=True,
                                          super_partitions=8))
    assert bf4.serial_seconds == pytest.approx(bar.serial_seconds / 4)
    assert bf8.serial_seconds < bf4.serial_seconds < bar.serial_seconds
    assert bf4.seconds() < bar.seconds()
    assert "inbox_rebuild" in bar.terms


def test_ooc_stream_io_prices_the_super_partition_traffic():
    """OOC observations charge the host link for the vertex/edge block
    and message-bucket round trip, not just the value write-back."""
    plan = PhysicalPlan()
    ooc = estimate(plan, WEB, Observation(ooc=True))
    assert "stream_io" in ooc.terms and ooc.terms["stream_io"] > 0
    assert ooc.host_bytes > estimate(
        plan, WEB, Observation()).host_bytes == 0


def test_calibrate_machine_refits_constants_from_hlo():
    """One-shot startup calibration: the fitted constants come back
    finite, inside their clamp ranges, cached per backend, and the
    calibrated machine still ranks plans (sanity: left-outer wins sparse
    frontiers)."""
    from repro.planner import (DEFAULT_MACHINE, calibrate_machine, choose)
    from repro.planner.cost import _CALIBRATED
    small = GraphStats(n_vertices=192, n_edges=960, n_partitions=4,
                       vertex_capacity=64, edge_capacity=256,
                       value_dims=1, msg_dims=1)
    prog = SSSP(source=0)
    _CALIBRATED.clear()
    m = calibrate_machine(prog, small, DEFAULT_MACHINE)
    assert 0.5 <= m.k_compute <= 128.0
    assert 1.0 <= m.k_scatter <= 64.0
    assert 0.02 <= m.sort_pass_frac <= 4.0
    # cached: a second call must not refit (and must agree)
    m2 = calibrate_machine(prog, small, DEFAULT_MACHINE)
    assert (m2.k_compute, m2.k_scatter, m2.sort_pass_frac) == \
        (m.k_compute, m.k_scatter, m.sort_pass_frac)
    assert len(_CALIBRATED) == 1
    sparse, _ = choose(prog, WEB, Observation(frontier_density=0.01),
                       machine=m)
    assert sparse.join == "left_outer"


def test_run_host_auto_with_calibration_matches_static():
    """AdaptiveConfig(calibrate=True) wires the one-shot calibration into
    _resolve_plan; the run must still be exact."""
    side = 12
    edges = grid_graph(side)
    n = side * side
    prog = SSSP(source=0)
    static = run_host(load_graph(edges, n, P=4, value_dims=1), prog,
                      prog.suggested_plan, max_supersteps=60)
    auto = run_host(load_graph(edges, n, P=4, value_dims=1), prog, "auto",
                    max_supersteps=60,
                    auto_config=AdaptiveConfig(calibrate=True))
    assert np.array_equal(gather_values(auto.vertex, n),
                          gather_values(static.vertex, n))


def test_choose_switches_storage_with_change_density():
    from repro.core import STORAGES
    sssp = SSSP(source=0)
    sparse, _ = choose(sssp, WEB,
                       Observation(ooc=True, change_density=0.01,
                                   frontier_density=0.05),
                       storages=STORAGES)
    dense, _ = choose(PageRank(100_000), WEB,
                      Observation(ooc=True, change_density=1.0,
                                  frontier_density=1.0),
                      storages=STORAGES)
    assert sparse.storage == "delta"
    assert dense.storage == "inplace"


def test_controller_reads_change_density_from_stats_extra():
    """The OOC driver annotates records with ooc/change_density; the
    controller must surface them into the Observation it plans with.
    Planned on the EMULATED machine (host link = memcpy), like the real
    emulated-transport OOC driver: on a PCIe-class host link the
    stream_io term correctly makes synchronous OOC transfer-bound, which
    mutes per-plan differences below the switch margin."""
    from repro.core import STORAGES
    from repro.planner import EMULATED_MACHINE, AdaptiveController
    sssp = SSSP(source=0)
    plan, _ = choose(sssp, WEB, Observation(frontier_density=1.0, ooc=True),
                     machine=EMULATED_MACHINE, storages=STORAGES)
    ctl = AdaptiveController(sssp, WEB, plan,
                             AdaptiveConfig(patience=1, cooldown=0),
                             machine=EMULATED_MACHINE,
                             space_kw={"storages": STORAGES})
    coll = StatsCollector(n_partitions=WEB.n_partitions,
                          vertex_capacity=WEB.vertex_capacity,
                          msg_dims=WEB.msg_dims)
    total = WEB.n_partitions * WEB.vertex_capacity
    rec = coll.record(2, active=total // 100, messages=10, wall_s=0.0,
                      ooc=True, change_density=0.01)
    switched = ctl.observe(rec)
    assert switched is not None
    assert switched.storage == "delta"


def test_migrate_msgs_sorts_runs_for_merging_receiver():
    import jax.numpy as jnp

    from repro.core.relations import MsgRel
    rng = np.random.default_rng(0)
    P, n_parts, C, D = 2, 4, 8, 1
    dst = rng.integers(0, 100, (P, n_parts * C)).astype(np.int32)
    valid = rng.random((P, n_parts * C)) > 0.3
    pay = dst[..., None].astype(np.float32)   # payload tracks its dst
    msg = MsgRel(dst=jnp.asarray(np.where(valid, dst, -1)),
                 payload=jnp.asarray(np.where(valid[..., None], pay, 0.0)),
                 valid=jnp.asarray(valid))
    old = PhysicalPlan(connector="partitioning", sender_combine=False)
    new = PhysicalPlan(connector="partitioning_merging")
    out = migrate_msgs(msg, old, new, n_parts)
    od = np.asarray(out.dst).reshape(P, n_parts, C)
    ov = np.asarray(out.valid).reshape(P, n_parts, C)
    op = np.asarray(out.payload).reshape(P, n_parts, C, D)
    for p in range(P):
        for r in range(n_parts):
            d, v = od[p, r], ov[p, r]
            assert (np.diff(d[v]) >= 0).all()        # runs dst-ascending
            assert (op[p, r][v, 0] == d[v]).all()    # payload follows dst
    # same multiset of live messages
    assert sorted(np.asarray(msg.dst)[np.asarray(msg.valid)]) == \
        sorted(od[ov])
    # no-op when the stream is already dst-sorted (sender combine on)
    sorted_old = PhysicalPlan(connector="partitioning", sender_combine=True)
    same = migrate_msgs(msg, sorted_old, new, n_parts)
    assert same is msg


def test_stats_collector_record_and_events():
    coll = StatsCollector(n_partitions=4, vertex_capacity=100, msg_dims=2)
    rec = coll.record(1, active=40, messages=10, wall_s=0.5)
    assert rec.frontier_density == pytest.approx(0.1)
    assert rec.bytes_exchanged == 10 * (4 + 8 + 1)
    coll.event(1, "plan-switch", join="left_outer")
    assert len(coll.supersteps()) == 1 and len(coll.records) == 2
    d = coll.records[-1].as_dict()
    assert d == {"superstep": 1, "event": "plan-switch",
                 "join": "left_outer"}


def test_adaptive_sssp_matches_static_and_switches():
    """Acceptance: plan="auto" SSSP equals the best static plan
    vertex-for-vertex and performs >=1 mid-run plan adaptation."""
    side = 40
    edges = grid_graph(side)
    n = side * side
    prog = SSSP(source=0)
    static = run_host(load_graph(edges, n, P=4, value_dims=1), prog,
                      prog.suggested_plan, max_supersteps=100)
    auto = run_host(load_graph(edges, n, P=4, value_dims=1), prog,
                    "auto", max_supersteps=100)
    d_static = gather_values(static.vertex, n)[:, 0]
    d_auto = gather_values(auto.vertex, n)[:, 0]
    assert np.array_equal(d_static, d_auto)
    switches = [s for s in auto.stats if s.get("event") == "plan-switch"]
    assert len(switches) >= 1
    # the high-diameter lattice collapses to a sparse frontier: the
    # adaptation must land on the paper's Figure 9 SSSP hint
    assert auto.plan.join == "left_outer"
    assert auto.supersteps == static.supersteps


def test_run_jit_auto_resolves_statically():
    side = 16
    edges = grid_graph(side)
    n = side * side
    prog = SSSP(source=0)
    auto = run_jit(load_graph(edges, n, P=4, value_dims=1), prog, "auto",
                   max_supersteps=40)
    static = run_host(load_graph(edges, n, P=4, value_dims=1), prog,
                      prog.suggested_plan, max_supersteps=40)
    assert np.array_equal(gather_values(auto.vertex, n),
                          gather_values(static.vertex, n))
    assert auto.plan is not None   # resolved to a concrete plan


def test_run_host_rejects_unknown_plan_string():
    side = 8
    edges = grid_graph(side)
    vert = load_graph(edges, side * side, P=2, value_dims=1)
    with pytest.raises(ValueError):
        run_host(vert, SSSP(source=0), "fastest")


def test_adaptive_controller_hysteresis():
    """No thrash: a one-superstep density dip must not trigger a switch
    with patience=2; a sustained dip must."""
    from repro.planner import AdaptiveController
    sssp = SSSP(source=0)
    plan, _ = choose(sssp, WEB, Observation(frontier_density=1.0))
    ctl = AdaptiveController(sssp, WEB, plan,
                             AdaptiveConfig(patience=2, cooldown=1))
    coll = StatsCollector(n_partitions=WEB.n_partitions,
                          vertex_capacity=WEB.vertex_capacity,
                          msg_dims=WEB.msg_dims)
    total = WEB.n_partitions * WEB.vertex_capacity
    blip = coll.record(1, active=total // 100, messages=10, wall_s=0.0)
    assert ctl.observe(blip) is None           # first sparse sighting
    dense = coll.record(2, active=total, messages=total, wall_s=0.0)
    assert ctl.observe(dense) is None          # streak reset
    s3 = coll.record(3, active=total // 100, messages=10, wall_s=0.0)
    assert ctl.observe(s3) is None
    s4 = coll.record(4, active=total // 100, messages=10, wall_s=0.0)
    switched = ctl.observe(s4)                 # sustained -> switch
    assert switched is not None and switched.join == "left_outer"
    assert ctl.switches and ctl.plan == switched


def test_machine_for_reads_the_device_kind_table():
    """Every driver prices the device JAX runs on from one table keyed by
    device_kind; a device without an entry is an error, not a default."""
    import jax
    from repro.planner import MACHINES, machine_for
    assert machine_for() is MACHINES[jax.devices()[0].device_kind]
    v5e = machine_for("TPU v5 lite")
    assert (v5e.peak_flops, v5e.hbm_bw, v5e.hbm_bytes) == (197e12, 819e9,
                                                           16e9)
    assert v5e.mxu and not machine_for("cpu").mxu
    with pytest.raises(ValueError, match="no machine model"):
        machine_for("TPU v99")
