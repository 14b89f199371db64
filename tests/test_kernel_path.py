"""Kernel-path dispatch + bit-for-bit parity suite.

The tentpole invariant: routing the superstep hot path through the
Pallas kernels (``kernel_impl="pallas"`` — interpret mode on CPU, the
bit-for-bit-testable emulator) produces EXACTLY the results of the jnp
reference path (``kernel_impl="ref"``), across algorithms x joins x
connectors x drivers (host loop / whole-loop jit / out-of-core,
including a disk-tier run). Not allclose — ``np.array_equal``: both
paths execute the same blocked reduction order for the sender fold, and
the gather's one-hot matmul is exact for finite floats (non-finites ride
a class channel).

Plus the dispatch layer itself (``kernels/backend.resolve`` matrix and
the ``REPRO_KERNEL_IMPL`` env override), the planner's pricing of the
kernel path, and the fused combine->pack leg's HLO evidence: the
lowered fused leg moves strictly fewer bytes because the intermediate
edge-payload relation is never re-materialized.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (PhysicalPlan, gather_values, load_graph, run_host,
                        run_jit)
from repro.core.ooc import run_out_of_core
from repro.graph import SSSP, ConnectedComponents, PageRank, rmat_graph
from repro.kernels import backend as kbackend

N = 220
EDGES = rmat_graph(N, 1200, seed=7)
ALGOS = {
    "pagerank": (lambda: PageRank(N, iterations=6), 2),
    "sssp": (lambda: SSSP(source=3), 1),
    "cc": (lambda: ConnectedComponents(), 1),
}
JOINS = ("full_outer", "left_outer")
CONNECTORS = ("partitioning", "partitioning_merging")

_REF = {}   # (algo, join, connector) -> gathered values, kernel_impl="ref"


def _plan(algo, join, connector, impl):
    mk, _ = ALGOS[algo]
    return dataclasses.replace(mk().suggested_plan, join=join,
                               connector=connector, kernel_impl=impl)


def _run_host(algo, join, connector, impl):
    mk, vd = ALGOS[algo]
    vert = load_graph(EDGES, N, P=4, value_dims=vd)
    res = run_host(vert, mk(), _plan(algo, join, connector, impl),
                   max_supersteps=30)
    return gather_values(res.vertex, N)


def _ref(algo, join, connector):
    key = (algo, join, connector)
    if key not in _REF:
        _REF[key] = _run_host(algo, join, connector, "ref")
    return _REF[key]


# ---------------------------------------------------------------- drivers

@pytest.mark.parametrize("algo", list(ALGOS))
@pytest.mark.parametrize("join", JOINS)
@pytest.mark.parametrize("connector", CONNECTORS)
def test_host_parity_bit_for_bit(algo, join, connector):
    """run_host: pallas (interpret) == ref exactly, every algorithm x
    join x connector."""
    got = _run_host(algo, join, connector, "pallas")
    assert np.array_equal(got, _ref(algo, join, connector))


@pytest.mark.parametrize("algo", list(ALGOS))
def test_jit_parity_bit_for_bit(algo):
    """run_jit (whole-loop jit, kernels traced inside the while_loop):
    pallas == ref exactly."""
    mk, vd = ALGOS[algo]
    runs = {}
    for impl in ("ref", "pallas"):
        vert = load_graph(EDGES, N, P=4, value_dims=vd)
        res = run_jit(vert, mk(), mk().suggested_plan, max_supersteps=30,
                      kernel_impl=impl)
        runs[impl] = gather_values(res.vertex, N)
    assert np.array_equal(runs["pallas"], runs["ref"])


@pytest.mark.parametrize("algo", ["pagerank", "sssp"])
@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_ooc_parity_bit_for_bit(algo, impl):
    """run_out_of_core under either kernel impl == the in-memory ref
    (per-super-partition gather layouts through one shared jitted step)."""
    mk, vd = ALGOS[algo]
    plan = _plan(algo, "full_outer", "partitioning", impl)
    vert = load_graph(EDGES, N, P=4, value_dims=vd)
    res = run_out_of_core(vert, mk(), plan, budget_partitions=2,
                          max_supersteps=30)
    assert np.array_equal(gather_values(res.vertex, N),
                          _ref(algo, "full_outer", "partitioning"))


def test_ooc_disk_tier_parity_bit_for_bit(tmp_path):
    """The kernel path composes with the full storage hierarchy: an OOC
    run under a DRAM budget spilling pages to disk, kernels on."""
    mk, vd = ALGOS["sssp"]
    plan = _plan("sssp", "full_outer", "partitioning", "pallas")
    vert = load_graph(EDGES, N, P=4, value_dims=vd)
    res = run_out_of_core(vert, mk(), plan, budget_partitions=2,
                          max_supersteps=30,
                          memory_budget_bytes=1 << 14,
                          disk_dir=str(tmp_path / "spill"))
    assert np.array_equal(gather_values(res.vertex, N),
                          _ref("sssp", "full_outer", "partitioning"))
    spilled = [s for s in res.stats
               if s.get("spill_read_bytes", 0) + s.get("spill_write_bytes",
                                                       0) > 0]
    assert spilled, "budget was meant to force the disk tier"


def test_driver_kernel_impl_overrides_plan():
    """run_host(kernel_impl=...) pins the dispatch over whatever the plan
    says, and the result still matches the ref exactly."""
    mk, vd = ALGOS["cc"]
    vert = load_graph(EDGES, N, P=4, value_dims=vd)
    plan = _plan("cc", "full_outer", "partitioning", "ref")
    res = run_host(vert, mk(), plan, max_supersteps=30,
                   kernel_impl="pallas")
    assert np.array_equal(gather_values(res.vertex, N),
                          _ref("cc", "full_outer", "partitioning"))


# ------------------------------------------------------- backend.resolve

def test_resolve_matrix(monkeypatch):
    monkeypatch.delenv(kbackend.ENV_VAR, raising=False)
    assert kbackend.resolve("auto", tpu=False) == "ref"
    assert kbackend.resolve("auto", tpu=True) == "pallas_tpu"
    assert kbackend.resolve("pallas", tpu=False) == "pallas"
    assert kbackend.resolve("pallas", tpu=True) == "pallas_tpu"
    assert kbackend.resolve("ref", tpu=False) == "ref"
    assert kbackend.resolve("ref", tpu=True) == "ref"
    assert kbackend.resolve("pallas_tpu", tpu=False) == "pallas_tpu"
    assert kbackend.resolve("pallas_tpu", tpu=True) == "pallas_tpu"
    with pytest.raises(ValueError):
        kbackend.resolve("bogus", tpu=False)


def test_resolve_env_override(monkeypatch):
    """$REPRO_KERNEL_IMPL overrides the knob itself — including "auto" —
    so CI can force a path without touching code or configs."""
    monkeypatch.setenv(kbackend.ENV_VAR, "pallas")
    assert kbackend.resolve("ref", tpu=False) == "pallas"
    assert kbackend.resolve("auto", tpu=False) == "pallas"
    assert kbackend.resolve("auto", tpu=True) == "pallas_tpu"
    monkeypatch.setenv(kbackend.ENV_VAR, "ref")
    assert kbackend.resolve("pallas", tpu=True) == "ref"
    monkeypatch.setenv(kbackend.ENV_VAR, "bogus")
    with pytest.raises(ValueError):
        kbackend.resolve("auto", tpu=False)


def test_env_override_end_to_end(monkeypatch):
    """A plain kernel_impl="auto" run under REPRO_KERNEL_IMPL=pallas
    takes the kernel path and still matches the ref bit-for-bit."""
    monkeypatch.setenv(kbackend.ENV_VAR, "pallas")
    mk, vd = ALGOS["sssp"]
    vert = load_graph(EDGES, N, P=4, value_dims=vd)
    res = run_host(vert, mk(), mk().suggested_plan, max_supersteps=30)
    monkeypatch.delenv(kbackend.ENV_VAR)
    assert np.array_equal(gather_values(res.vertex, N),
                          _ref("sssp", "full_outer", "partitioning"))


def test_plan_validates_kernel_impl():
    with pytest.raises(ValueError):
        PhysicalPlan(kernel_impl="vector").validate("sum")


# ----------------------------------------------------- planner pricing

def _web_stats():
    from repro.planner import GraphStats
    return GraphStats(n_vertices=130_000, n_edges=800_000, n_partitions=8,
                      vertex_capacity=16_250, edge_capacity=100_000)


def test_planner_prices_kernel_path_per_machine():
    """The cost model makes plan="auto" pick the kernels exactly where
    they win: cheaper than the jnp path on the MXU machine, dearer (the
    interpreter penalty) on the emulated one."""
    from repro.planner import (DEFAULT_MACHINE, EMULATED_MACHINE,
                               Observation, estimate)
    g = _web_stats()
    obs = Observation(frontier_density=1.0)
    base = PhysicalPlan(join="full_outer", groupby="sort",
                        connector="partitioning", sender_combine=True)
    ref = dataclasses.replace(base, kernel_impl="ref")
    pal = dataclasses.replace(base, kernel_impl="pallas")
    s = lambda p, m: estimate(p, g, obs, m).seconds(m)
    assert s(pal, DEFAULT_MACHINE) < s(ref, DEFAULT_MACHINE)
    assert s(ref, EMULATED_MACHINE) < s(pal, EMULATED_MACHINE)


def test_plan_space_kernel_dimension():
    """Default space stays the paper's 16 plans (kernel_impl inherited);
    pinning competing impls doubles it."""
    from repro.planner import plan_space
    prog = PageRank(N, iterations=6)
    assert len(list(plan_space(prog))) == 16
    both = list(plan_space(prog, kernel_impls=("ref", "pallas")))
    assert len(both) == 32
    assert {p.kernel_impl for p in both} == {"ref", "pallas"}


def test_choose_picks_kernels_only_on_mxu():
    from repro.planner import (DEFAULT_MACHINE, EMULATED_MACHINE,
                               Observation, choose)
    prog = PageRank(N, iterations=6)
    g, obs = _web_stats(), Observation(frontier_density=1.0)
    kw = dict(joins=("full_outer",), sender_combines=(True,),
              kernel_impls=("ref", "pallas"))
    plan_mxu, _ = choose(prog, g, obs, machine=DEFAULT_MACHINE, **kw)
    plan_emu, _ = choose(prog, g, obs, machine=EMULATED_MACHINE, **kw)
    assert plan_mxu.kernel_impl == "pallas"
    assert plan_emu.kernel_impl == "ref"


def test_cost_detail_ledger_populated():
    """PlanCost.detail carries the per-leg raw flops/bytes the roofline
    benchmark plots; components reconcile with the rolled-up totals."""
    from repro.planner import DEFAULT_MACHINE, Observation, estimate
    c = estimate(PhysicalPlan(kernel_impl="pallas"), _web_stats(),
                 Observation(frontier_density=1.0), DEFAULT_MACHINE)
    for leg in ("send", "sender_combine", "connector", "exchange"):
        assert leg in c.detail
    assert sum(d["flops"] for d in c.detail.values()) == pytest.approx(
        c.flops)
    assert sum(d["hbm_bytes"] for d in c.detail.values()) == pytest.approx(
        c.bytes)


# ------------------------------------------------- fused-pack HLO proof

def test_fused_pack_lowers_to_fewer_bytes_and_same_buckets():
    """The fused combine->exchange-pack leg: compacting combined
    survivors to the bucket capacity BEFORE the bucket build means the
    lowered HLO never re-materializes (or re-sorts) the full edge-payload
    relation — measured via the trip-count-aware HLO byte count, and
    the bucket outputs are bit-identical."""
    from repro.core.connector import bucket_by_owner
    from repro.core.superstep import compact_combined
    from repro.launch import hlo_cost

    P, M, D, n_parts, cap = 2, 4096, 2, 2, 32
    capc = n_parts * cap
    rng = np.random.default_rng(11)
    # post-combine shape: few survivors (one per distinct dst), dst
    # ascending per partition, everything else invalid — M >> capc
    dst = np.full((P, M), -1, np.int32)
    pay = np.zeros((P, M, D), np.float32)
    valid = np.zeros((P, M), bool)
    for p in range(P):
        rows = np.sort(rng.choice(M, 40, replace=False))
        dst[p, rows] = np.sort(rng.choice(1000, 40, replace=False))
        pay[p, rows] = rng.normal(size=(40, D)).astype(np.float32)
        valid[p, rows] = True

    def leg(d, pl, v, *, fused):
        if fused:
            d, pl, v, ovf_pack = compact_combined(d, pl, v, capc)
        else:
            ovf_pack = jnp.zeros((), jnp.int32)
        f = lambda dd, pp, vv: bucket_by_owner(dd, pp, vv, n_parts, cap,
                                               sort_by_dst=False)
        b_dst, b_pay, b_val, ovf = jax.vmap(f)(d, pl, v)
        return b_dst, b_pay, b_val, jnp.sum(ovf) + ovf_pack

    args = (jnp.asarray(dst), jnp.asarray(pay), jnp.asarray(valid))
    outs, bts = {}, {}
    for fused in (False, True):
        fn = jax.jit(functools.partial(leg, fused=fused))
        compiled = fn.lower(*args).compile()
        bts[fused] = hlo_cost.analyze(compiled.as_text()).bytes
        outs[fused] = jax.tree.map(np.asarray, fn(*args))
    for a, b in zip(outs[False], outs[True]):
        assert np.array_equal(a, b)
    assert bts[True] < bts[False], \
        f"fused leg must move fewer bytes: {bts[True]} vs {bts[False]}"


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_edge_capacity_pads_partitions_and_keeps_results(impl):
    """``load_graph(edge_capacity=...)`` gives every partition that many
    edge slots, its edges first and empty slots (-1, -1, 0.0) after, and
    PageRank on the padded relation reads exactly what it reads on the
    unpadded one, on both gather paths."""
    vd = ALGOS["pagerank"][1]
    tight = load_graph(EDGES, N, P=4, value_dims=vd)
    Ep = tight.edge_src.shape[1]
    padded = load_graph(EDGES, N, P=4, value_dims=vd, edge_capacity=Ep + 300)
    assert padded.edge_src.shape == (4, Ep + 300)
    for f, empty in (("edge_src", -1), ("edge_dst", -1), ("edge_val", 0.0)):
        a, b = np.asarray(getattr(tight, f)), np.asarray(getattr(padded, f))
        assert np.array_equal(b[:, :Ep], a) and (b[:, Ep:] == empty).all()
    assert load_graph(EDGES, N, P=4, value_dims=vd,
                      edge_capacity=Ep - 1).edge_src.shape == (4, Ep)
    plan = _plan("pagerank", "full_outer", "partitioning", impl)
    got = [gather_values(run_host(v, PageRank(N, iterations=6), plan,
                                  max_supersteps=30).vertex, N)
           for v in (tight, padded)]
    assert np.array_equal(got[0], got[1])
