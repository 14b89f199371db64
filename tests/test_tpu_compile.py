"""Compile the kernels and whole supersteps for a described TPU v5e.

No chip is needed: the TPU compiler that ships with jaxlib compiles for a
described v5e topology and refuses what the chip's compiler would refuse
(an unsupported primitive in a kernel, a program that does not fit HBM).
The topology is described inside a fixture, so collecting this file never
loads the TPU library; where it cannot be described the tests skip.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as PSpec

from repro.core.plan import PhysicalPlan, bucket_capacity
from repro.core.relations import (N_OVERFLOW, GlobalState, MsgRel,
                                  VertexRel)
from repro.core.sharded import sharded_superstep
from repro.core.superstep import EngineConfig, make_superstep
from repro.graph import SSSP, PageRank
from repro.kernels import backend as kbackend
from repro.kernels.csr_spmv.csr_spmv import edge_gather_pallas
from repro.kernels.csr_spmv.ops import item_capacity
from repro.kernels.segment_combine.segment_combine import fold_lane_dense

EDGE_SLOTS = 1 << 22
KERNEL = 'custom_call_target="tpu_custom_call"'
TEMP_PER_SLOT = 64   # bytes of temporaries per edge slot a chip holds


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.array(topo.devices[:4]), ("workers",))


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_csr_spmv_compiles_for_v5e(one_chip):
    n_rows, block_r = 1 << 20, kbackend.GATHER_BLOCK_R
    items = item_capacity(EDGE_SLOTS, n_rows,
                          block_m=kbackend.GATHER_BLOCK_M, block_r=block_r)
    f = jax.jit(lambda t, s, w: edge_gather_pallas(
        t, s, w, 3, block_m=kbackend.GATHER_BLOCK_M, block_r=block_r,
        interpret=False))
    compiled = f.lower(_sds((8, n_rows), jnp.float32, one_chip),
                       _sds((EDGE_SLOTS,), jnp.int32, one_chip),
                       _sds((items,), jnp.int32, one_chip)).compile()
    assert KERNEL in compiled.as_text()


@pytest.mark.parametrize("D", [1, 2])
@pytest.mark.parametrize("op", ["sum", "min"])
def test_segment_combine_compiles_for_v5e(one_chip, D, op):
    bm = kbackend.combine_block(EDGE_SLOTS)
    f = jax.jit(lambda k, p: fold_lane_dense(k, p, op, block_m=bm,
                                             interpret=False))
    compiled = f.lower(_sds((1, EDGE_SLOTS), jnp.int32, one_chip),
                       _sds((1, D, EDGE_SLOTS), jnp.float32,
                            one_chip)).compile()
    assert KERNEL in compiled.as_text()


def _superstep_args(program, plan, scale: int, sharding, P: int = 1,
                    gs_sharding=None):
    """Abstract relations of a Graph500 graph (edgefactor 16, both
    directions) in P hash partitions of equal size, as ``load_graph`` and
    ``default_engine_config`` would shape them. ``sharding(ndim)`` places
    an array of that rank; ``gs_sharding``, if given, the global state."""
    n = 1 << scale
    Np, Ep = int(math.ceil(n / P) * 1.3) + 1, 32 * n // P
    ec = EngineConfig(n_parts=P,
                      bucket_cap=bucket_capacity(plan, Ep, Np, P),
                      frontier_cap=Np + 8)
    V, D, M = program.value_dims, program.msg_dims, P * ec.bucket_cap
    s = lambda shape, dt: _sds(shape, dt, sharding(len(shape)))
    vert = VertexRel(vid=s((P, Np), jnp.int32), halt=s((P, Np), jnp.bool_),
                     value=s((P, Np, V), jnp.float32),
                     edge_src=s((P, Ep), jnp.int32),
                     edge_dst=s((P, Ep), jnp.int32),
                     edge_val=s((P, Ep), jnp.float32))
    msg = MsgRel(dst=s((P, M), jnp.int32), payload=s((P, M, D), jnp.float32),
                 valid=s((P, M), jnp.bool_))
    g = lambda shape, dt: _sds(shape, dt, gs_sharding or
                                  sharding(len(shape)))
    gs = GlobalState(halt=g((), jnp.bool_), aggregate=g((1,), jnp.float32),
                     superstep=g((), jnp.int32),
                     overflow=g((N_OVERFLOW,), jnp.int32),
                     active_count=g((), jnp.int32),
                     msg_count=g((), jnp.int32))
    items = kbackend.edge_layout_shapes(P, Ep, Np)
    layout = s(items.shape, items.dtype)
    return ec, (vert, msg, gs, None, layout), P * Ep


@pytest.mark.parametrize("algo", ["pagerank", "sssp"])
def test_superstep_compiles_for_v5e_and_fits(one_chip, algo):
    """One whole superstep on the kernel path that ``auto`` takes on a
    TPU: both kernels compile in, the temporaries stay a few dozen
    bytes per edge slot (a (P, Ep, V) gather index once padded each slot
    to a tile, ~1 KB per slot for PageRank), and the arguments stay near
    the edge relation's 12 bytes per slot: the gather layout has no
    per-edge array (one would add 4 bytes per slot)."""
    scale = 14
    program = PageRank(1 << scale) if algo == "pagerank" else SSSP(0)
    plan = PhysicalPlan(join="full_outer", kernel_impl="pallas_tpu")
    ec, args, slots = _superstep_args(program, plan, scale,
                                      lambda _: one_chip)
    compiled = jax.jit(make_superstep(program, plan, ec)).lower(
        *args).compile()
    assert compiled.as_text().count(KERNEL) == 2
    mem = compiled.memory_analysis()
    temp_per_slot = mem.temp_size_in_bytes / slots
    assert temp_per_slot < TEMP_PER_SLOT, \
        f"{temp_per_slot:.0f} B per edge slot"
    arg_per_slot = mem.argument_size_in_bytes / slots
    assert arg_per_slot < 14, f"{arg_per_slot:.2f} B per edge slot"


def test_sharded_superstep_compiles_for_v5e_2x2(four_chips):
    """``run_sharded``'s PageRank superstep, ``shard_map``'d over all four
    chips of a v5e:2x2 with one partition and one gather layout per chip
    (the stacked (4, cap) layouts, each chip handed its own row): both
    kernels compile in, and each chip's temporaries per edge slot it
    holds stay under the one-chip bound. At scale 20 (8.4M slots a chip)
    the compiler reports temporaries (28.5 B a slot); at 18 and below it
    reports none, which no bound could check."""
    scale, P = 20, 4
    program = PageRank(1 << scale)
    plan = PhysicalPlan(join="full_outer", kernel_impl="pallas_tpu")
    lead = lambda ndim: NamedSharding(four_chips,
                                      PSpec("workers", *[None] * (ndim - 1)))
    ec, (vert, msg, gs, _, _), slots = _superstep_args(
        program, plan, scale, lead, P,
        gs_sharding=NamedSharding(four_chips, PSpec()))
    items = kbackend.edge_layout_shapes(1, vert.edge_src.shape[1],
                                        vert.capacity)
    layouts = _sds((P,) + items.shape, items.dtype, lead(2))
    ec = dataclasses.replace(ec, axis_name=("workers",),
                             exchange_apart=True)
    step, _ = sharded_superstep(program, plan, ec, four_chips, layout=True)
    compiled = step.lower(vert, msg, gs, layouts).compile()
    assert compiled.as_text().count(KERNEL) == 2
    temp_per_slot = compiled.memory_analysis().temp_size_in_bytes / (
        slots / P)
    assert 0 < temp_per_slot < TEMP_PER_SLOT, \
        f"{temp_per_slot:.0f} B per edge slot"
