"""Multi-device sharded parity + network-axis planner suite.

The tentpole claim: ``run_sharded`` — supersteps under ``shard_map`` on a
real device mesh with the bucket exchange as a ``jax.lax.all_to_all`` —
is BIT-FOR-BIT equal to the emulated-transport ``run_host`` for
PageRank / SSSP / CC across both connectors, including the per-worker
out-of-core mode (each worker's own TieredStore + spill dir) and a
mid-run capacity regrow that spans the exchange.

The device-dependent tests need a multi-device backend: they run in the
dedicated CI ``sharded`` job under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the tier-1 run,
which initializes jax with one device, skips them). Setting the flag at
module import only works when this file runs standalone — before any
other test has touched jax — hence the skipif, not an xfail.

The cost-model / readiness-protocol unit tests at the bottom are device
count independent and run everywhere.
"""
import dataclasses
import os
import pathlib
import tempfile

if "XLA_FLAGS" not in os.environ:   # effective only when run standalone
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import numpy as np
import pytest

from repro.core import (EngineConfig, PhysicalPlan, gather_values,
                        load_graph, run_host)
from repro.core.sharded import (ExchangeReadiness, _exchange_wire_bytes,
                                run_sharded)
from repro.graph import SSSP, ConnectedComponents, PageRank, rmat_graph
from repro.kernels import backend as kbackend

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >=2 devices (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=8 before jax init)")

N = 220
EDGES = rmat_graph(N, 1200, seed=7)
ALGOS = {
    "pagerank": (lambda: PageRank(N, iterations=6), 2),
    "sssp": (lambda: SSSP(source=3), 1),
    "cc": (lambda: ConnectedComponents(), 1),
}
_HOST_REF = {}   # (algo, connector, P) -> gathered values of run_host


def _host_ref(algo: str, connector: str, P: int = 8) -> np.ndarray:
    if (algo, connector, P) not in _HOST_REF:
        mk, vd = ALGOS[algo]
        prog = mk()
        plan = dataclasses.replace(prog.suggested_plan,
                                   connector=connector)
        vert = load_graph(EDGES, N, P=P, value_dims=vd)
        res = run_host(vert, prog, plan, max_supersteps=30)
        _HOST_REF[(algo, connector, P)] = gather_values(res.vertex, N)
    return _HOST_REF[(algo, connector, P)]


# ---------------------------------------------------------------------
# bit-for-bit parity: sharded all_to_all vs emulated transport
# ---------------------------------------------------------------------

@multi_device
@pytest.mark.parametrize("algo", list(ALGOS))
@pytest.mark.parametrize("connector", ["sort_merge", "scatter_gather"])
def test_sharded_matches_host(algo, connector):
    """P=8 partitions over 2 devices: the tiled all_to_all plus the
    dst-major reorder must reproduce the emulated exchange exactly —
    even float accumulation order agrees."""
    mk, vd = ALGOS[algo]
    prog = mk()
    plan = dataclasses.replace(prog.suggested_plan, connector=connector)
    vert = load_graph(EDGES, N, P=8, value_dims=vd)
    res = run_sharded(vert, prog, plan, devices=2, max_supersteps=30)
    assert np.array_equal(gather_values(res.vertex, N),
                          _host_ref(algo, connector))
    assert res.supersteps > 1
    recs = [s for s in res.stats if "exchange_stall_s" in s]
    assert len(recs) == res.supersteps
    assert all(s["n_workers"] == 2 and s["sharded"] for s in recs)
    assert all(s["exchange_bytes"] > 0 for s in recs)
    assert all(s["metrics"]["exchange.stall_s"] >= 0 for s in recs)


@multi_device
def test_sharded_more_workers():
    """Worker count is a pure execution knob: 4 devices, same bits."""
    prog = SSSP(source=3)
    vert = load_graph(EDGES, N, P=8, value_dims=1)
    res = run_sharded(vert, prog, prog.suggested_plan, devices=4,
                      max_supersteps=30)
    assert np.array_equal(gather_values(res.vertex, N),
                          _host_ref("sssp", "partitioning"))


@multi_device
@pytest.mark.parametrize("algo", list(ALGOS))
@pytest.mark.parametrize("connector", ["sort_merge", "scatter_gather"])
def test_sharded_ooc_matches_host(algo, connector, tmp_path):
    """Per-worker tiered stores with disk spill dirs: 2 workers x 4
    partitions each, 2 resident at a time, 16 KiB DRAM budget per store
    (forces paging). Still bit-for-bit."""
    mk, vd = ALGOS[algo]
    prog = mk()
    plan = dataclasses.replace(prog.suggested_plan, connector=connector)
    vert = load_graph(EDGES, N, P=8, value_dims=vd)
    res = run_sharded(vert, prog, plan, devices=2, budget_partitions=2,
                      disk_dir=str(tmp_path),
                      memory_budget_bytes=16 * 1024, max_supersteps=30)
    assert np.array_equal(gather_values(res.vertex, N),
                          _host_ref(algo, connector))
    # each worker spilled into ITS OWN tier directory
    for w in range(2):
        assert pathlib.Path(tmp_path, f"worker{w}").is_dir()
    recs = [s for s in res.stats if "exchange_stall_s" in s]
    assert recs and all(s["spill"] for s in recs)
    assert all(s["n_workers"] == 2 for s in recs)


@multi_device
def test_sharded_ooc_traced_observability(tmp_path):
    """Observability under the sharded disk-tier driver: a traced run
    must show (a) spans from the main loop AND the per-worker tiered
    stores' I/O engine threads, (b) the separately-timed all_to_all as
    ``exchange``-category spans (one per superstep), and (c) the
    exchange counters landing in ``SuperstepStats.extra["metrics"]``."""
    from repro.obs import chrome_trace, trace, validate_chrome_trace
    prog = PageRank(N, iterations=6)
    vert = load_graph(EDGES, N, P=8, value_dims=2)
    trace.start()
    try:
        res = run_sharded(vert, prog, prog.suggested_plan, devices=2,
                          budget_partitions=2, disk_dir=str(tmp_path),
                          memory_budget_bytes=16 * 1024, io_threads=2,
                          max_supersteps=30)
    finally:
        tracer = trace.stop()
    obj = chrome_trace(tracer)
    # (a) per-worker spans: main thread + the stores' io engines
    summary = validate_chrome_trace(obj, min_threads=3)
    assert any(t.startswith("pregelix-io-")
               for t in summary["thread_names"])
    # (b) the exchange stage is its own span category — the OOC driver
    # times one all_to_all per destination round (4 partitions/worker at
    # budget 2 -> 2 rounds per superstep)
    ex_spans = [e for e in obj["traceEvents"]
                if e.get("ph") == "X" and e.get("cat") == "exchange"]
    assert "exchange" in summary["categories"]
    assert len(ex_spans) == 2 * res.supersteps
    assert all(e["dur"] >= 0 for e in ex_spans)
    # (c) exchange counters in the per-superstep metrics snapshots
    recs = [s for s in res.stats if "exchange_stall_s" in s]
    assert recs and len(recs) == res.supersteps
    for s in recs:
        m = s["metrics"]
        assert m["exchange.bytes"] > 0
        assert m["exchange.stall_s"] >= 0


@multi_device
def test_sharded_host_legs_land_on_the_profiler_clock(tmp_path):
    """In-memory ``run_sharded`` under ``jax.profiler``: each superstep's
    host legs, the exchange included, are ``pregel.*`` spans with their
    ``superstep`` stat, as under ``run_host``, and the two programs run
    as ``superstep`` and ``exchange``."""
    import glob
    from jax.profiler import ProfileData
    prog = PageRank(N, iterations=6)
    vert = load_graph(EDGES, N, P=8, value_dims=2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        run_sharded(vert, prog, prog.suggested_plan, devices=2,
                    max_supersteps=3, on_superstep=lambda *_: None)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
    legs = ("pregel.dispatch", "pregel.wait", "pregel.readback",
            "pregel.exchange", "pregel.callback")
    got, names = {}, set()
    for line in host.lines:
        for e in line.events:
            names.add(e.name)
            if e.name in legs:
                got.setdefault(e.name, set()).add(dict(e.stats)["superstep"])
    assert got == {leg: {1, 2, 3} for leg in legs}
    # compiled ahead of the first superstep, the programs dispatch as
    # their jitted functions' compiled forms
    assert {"PjitFunction(jit(superstep))",
            "PjitFunction(jit(exchange))"} <= names


@multi_device
def test_sharded_regrow_spans_exchange():
    """bucket_cap=2 overflows on superstep 0 in BOTH modes; the sharded
    OOC redo must end-pad the already-landed inbox pages to the grown
    run width and still match the host run bit-for-bit."""
    prog = SSSP(source=3)
    ref = _host_ref("sssp", "partitioning")
    # in-memory sharded
    vert = load_graph(EDGES, N, P=8, value_dims=1)
    ec = EngineConfig(n_parts=8, bucket_cap=2,
                      frontier_cap=vert.capacity + 8)
    res = run_sharded(vert, prog, prog.suggested_plan, devices=2, ec=ec,
                      max_supersteps=30)
    assert [s for s in res.stats if s.get("event") == "regrow"]
    assert np.array_equal(gather_values(res.vertex, N), ref)
    # OOC sharded: the regrow lands MID-EXCHANGE (later rounds overflow
    # after earlier rounds already landed runs into gen+1 pages)
    with tempfile.TemporaryDirectory() as td:
        vert = load_graph(EDGES, N, P=8, value_dims=1)
        ec = EngineConfig(n_parts=8, bucket_cap=2,
                          frontier_cap=vert.capacity + 8)
        res = run_sharded(vert, prog, prog.suggested_plan, devices=2,
                          ec=ec, budget_partitions=2, disk_dir=td,
                          memory_budget_bytes=16 * 1024,
                          max_supersteps=30)
    assert [s for s in res.stats if s.get("event") == "regrow"]
    assert np.array_equal(gather_values(res.vertex, N), ref)


@multi_device
def test_sharded_auto_plan():
    """plan="auto" on the mesh: the planner sees sharded=True/n_workers
    and the run still matches; exchange EWMA feeds net_scale without
    destabilizing the choice on a small graph."""
    prog = PageRank(N, iterations=6)
    vert = load_graph(EDGES, N, P=8, value_dims=2)
    res = run_sharded(vert, prog, "auto", devices=2, max_supersteps=30)
    # no pin under shard_map: "auto" stays, and resolves to the jnp
    # reference off a TPU
    assert res.plan.kernel_impl == "auto"
    assert kbackend.resolve(res.plan.kernel_impl) == "ref"
    # parity against a host run of the SAME resolved plan (the auto
    # choice may differ from the suggested plan, and groupby/join change
    # float accumulation order)
    assert not [s for s in res.stats if s.get("event") == "plan-switch"]
    vert2 = load_graph(EDGES, N, P=8, value_dims=2)
    ref = run_host(vert2, prog, res.plan, max_supersteps=30)
    assert np.array_equal(gather_values(res.vertex, N),
                          gather_values(ref.vertex, N))


@multi_device
def test_make_host_mesh_device_count():
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(devices=2)
    assert int(mesh.devices.size) == 2
    assert mesh.axis_names == ("data",)
    with pytest.raises(RuntimeError, match="host_platform_device_count"):
        make_host_mesh(devices=len(jax.devices()) + 1)


@multi_device
def test_sharded_rejects_indivisible():
    vert = load_graph(EDGES, N, P=6, value_dims=1)
    with pytest.raises(ValueError, match="divide"):
        run_sharded(vert, SSSP(source=3), devices=4)


# ---------------------------------------------------------------------
# device-count-independent units: readiness protocol, network cost axis
# ---------------------------------------------------------------------

def test_exchange_readiness_protocol():
    """A destination round is dispatchable only when every remote
    (src_worker, src_round) pair has landed its runs."""
    rd = ExchangeReadiness(n_workers=2, n_rounds=2)
    assert not rd.ready(0, 0)
    rd.land(0, 0, src_round=0)      # all workers' round-0 runs land
    assert not rd.ready(0, 0)       # round-1 sources still missing
    assert rd.missing(0, 0) == [(0, 1), (1, 1)]
    rd.land(0, 0, src_round=1)
    assert rd.ready(0, 0)
    assert not rd.ready_round(0)    # worker 1's page not landed
    rd.land(1, 0, src_round=0)
    rd.land(1, 0, src_round=1)
    assert rd.ready_round(0)
    assert not rd.ready_round(1)


def test_exchange_wire_bytes():
    # (P=8 rows) x (8 buckets) x (C=4 slots) x (dst 4B + 2x4B payload
    # + 4B valid, sent as int32), half of it remote on 2 workers
    total = 8 * 8 * 4 * 16
    assert _exchange_wire_bytes(8, 8, 4, 2, 2) == total // 2
    assert _exchange_wire_bytes(8, 8, 4, 2, 1) == 0   # single worker


def test_cost_model_network_axis():
    """The sharded observation routes (P - P_local)/P of the exchange
    through net_bw + per-stage latency; more workers -> more net
    seconds; net_scale calibrates it."""
    from repro.planner import EMULATED_MACHINE
    from repro.planner.cost import GraphStats, Observation, estimate

    g = GraphStats(n_vertices=N, n_edges=1200, n_partitions=8,
                   vertex_capacity=64, edge_capacity=256,
                   value_dims=2, msg_dims=2)
    plan = PhysicalPlan()
    local = estimate(plan, g, Observation(frontier_density=1.0),
                     EMULATED_MACHINE)
    assert local.net_seconds == 0.0
    obs2 = Observation(frontier_density=1.0, sharded=True, n_workers=2)
    obs4 = Observation(frontier_density=1.0, sharded=True, n_workers=4)
    c2 = estimate(plan, g, obs2, EMULATED_MACHINE)
    c4 = estimate(plan, g, obs4, EMULATED_MACHINE)
    assert c2.net_seconds > 0.0
    assert c4.net_bytes > c2.net_bytes     # more remote traffic
    assert "exchange_net" in c2.terms
    # the latency term keeps CPU-mesh predictions in the measurable
    # regime: one stage >= net_latency_s
    assert c2.net_seconds >= EMULATED_MACHINE.net_latency_s
    # net_scale closes the measurement loop multiplicatively
    scaled = estimate(plan, g,
                      dataclasses.replace(obs2, net_scale=2.0),
                      EMULATED_MACHINE)
    assert scaled.net_seconds == pytest.approx(2 * c2.net_seconds)
    # net seconds enter the total
    assert c2.seconds() > local.seconds() - 1e-12


def test_adaptive_exchange_ewma_calibrates_net_scale():
    """The controller EWMAs measured exchange stalls and divides by the
    analytic net leg of the current plan -> Observation.net_scale."""
    from repro.planner import AdaptiveConfig, EMULATED_MACHINE
    from repro.planner.adaptive import AdaptiveController
    from repro.planner.cost import GraphStats, estimate
    from repro.planner.stats import StatsCollector

    g = GraphStats(n_vertices=N, n_edges=1200, n_partitions=8,
                   vertex_capacity=64, edge_capacity=256,
                   value_dims=2, msg_dims=2)
    plan = PhysicalPlan()
    prog = PageRank(N, iterations=6)
    ctrl = AdaptiveController(prog, g, plan, config=AdaptiveConfig(),
                              machine=EMULATED_MACHINE)
    coll = StatsCollector(n_partitions=8, vertex_capacity=64,
                          msg_dims=2, n_vertices=N)
    stall = 4e-3
    for i in range(1, 5):
        rec = coll.record(i, active=N, messages=1200, wall_s=0.01,
                          recompiled=(i == 1), sharded=True, n_workers=2,
                          exchange_bytes=1e5, exchange_stall_s=stall)
        ctrl.observe(rec, bucket_cap=0)
    assert ctrl._exchange_ewma == pytest.approx(stall)
    obs = ctrl._make_observation(rec, bucket_cap=0)
    assert obs.sharded and obs.n_workers == 2
    analytic = estimate(plan, g, dataclasses.replace(obs, net_scale=1.0),
                        EMULATED_MACHINE).net_seconds
    assert obs.net_scale == pytest.approx(
        min(max(stall / analytic, 0.125), 8.0))
    # state round-trips through checkpoints
    state = ctrl.state_dict()
    ctrl2 = AdaptiveController(prog, g, plan, config=AdaptiveConfig(),
                               machine=EMULATED_MACHINE)
    ctrl2.load_state(state)
    assert ctrl2._exchange_ewma == pytest.approx(stall)


def test_sharded_ooc_rejects_mutations():
    from repro.graph.algorithms import PathMerge
    vert = load_graph(EDGES, N, P=8, value_dims=2)
    with pytest.raises(NotImplementedError, match="mutat"):
        run_sharded(vert, PathMerge(), devices=1, budget_partitions=2)
