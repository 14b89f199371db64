"""The Pregel state as relations (paper Table 1), adapted to dense sharded
arrays.

Vertex(vid, halt, value, edges) / Msg(vid, payload) / GS(halt, aggregate,
superstep) — stored struct-of-arrays with a leading partition axis P.
Hash partitioning by vid (the paper's default): owner(vid) = vid % P,
local slot = vid // P, so the dense slot array IS the vid index (the
B-tree analogue: O(1) probe = array indexing).

Edges are owned by their source partition as flat (edge_slot -> src slot,
dst vid, value) arrays — the CSR adaptation for edge-parallel sends.
"""
from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

# with sources sorted, up to this many partitions are picked by one mask
# each (a pass over the edges apiece) in place of a sort by owner
_MASKED_PARTITIONS = 8


@jax.tree_util.register_dataclass
@dataclass
class VertexRel:
    vid: jax.Array        # (P, Np) int32, -1 = empty slot
    halt: jax.Array       # (P, Np) bool
    value: jax.Array      # (P, Np, V) float32
    edge_src: jax.Array   # (P, Ep) int32 local src slot, -1 = pad
    edge_dst: jax.Array   # (P, Ep) int32 global dst vid
    edge_val: jax.Array   # (P, Ep) float32

    @property
    def num_partitions(self) -> int:
        return self.vid.shape[0]

    @property
    def capacity(self) -> int:
        return self.vid.shape[1]


@jax.tree_util.register_dataclass
@dataclass
class MsgRel:
    dst: jax.Array        # (P, M) int32 global dst vid, -1 = invalid
    payload: jax.Array    # (P, M, D) float32
    valid: jax.Array      # (P, M) bool

    @property
    def capacity(self) -> int:
        return self.dst.shape[1]


# GlobalState.overflow attributes every capacity overflow to its source,
# so a regrow can double ONLY the capacity that actually overflowed — a
# frontier overflow no longer drags the bucket tensors (the device-memory
# hot spot on the budgeted OOC path) along with it.
OVF_BUCKET = 0     # message bucket capacity (EngineConfig.bucket_cap)
OVF_FRONTIER = 1   # left-outer frontier compaction (frontier_cap)
OVF_MUTATION = 2   # insert-proposal buckets (mutation_cap)
OVF_EDGE = 3       # frontier edge-stream compaction (scales with
                   # frontier_cap: EF = frontier_cap * 8)
N_OVERFLOW = 4


@jax.tree_util.register_dataclass
@dataclass
class GlobalState:
    halt: jax.Array         # () bool
    aggregate: jax.Array    # (A,) float32 user aggregate
    superstep: jax.Array    # () int32
    overflow: jax.Array     # (N_OVERFLOW,) int32 dropped tuples per source
                            # (bucket / frontier / mutation / edge)
    active_count: jax.Array  # () int32 (statistics collector)
    msg_count: jax.Array     # () int32


def empty_msgs(P: int, M: int, D: int) -> MsgRel:
    return MsgRel(dst=jnp.full((P, M), -1, jnp.int32),
                  payload=jnp.zeros((P, M, D), jnp.float32),
                  valid=jnp.zeros((P, M), bool))


def init_gs(agg_dims: int) -> GlobalState:
    return GlobalState(halt=jnp.array(False),
                       aggregate=jnp.zeros((agg_dims,), jnp.float32),
                       superstep=jnp.array(0, jnp.int32),
                       overflow=jnp.zeros((N_OVERFLOW,), jnp.int32),
                       active_count=jnp.array(0, jnp.int32),
                       msg_count=jnp.array(0, jnp.int32))


def load_graph(edges: np.ndarray, num_vertices: int, P: int, *,
               value_dims: int, edge_values: np.ndarray | None = None,
               capacity_factor: float = 1.3,
               partition: str = "hash", mesh=None,
               edge_capacity: int = 0) -> VertexRel:
    """Partition an edge list (E, 2) into a VertexRel (the paper's bulk
    load: scan, partition by vid, sort, bulk-load per-partition indexes).

    partition="hash" (paper default): vid lives at (vid % P, vid // P).
    partition="range": vid lives at (vid // cap, vid % cap) — owners are
    contiguous in vid order (see PhysicalPlan.partition); capacity_factor
    is forced to 1.0 (no insert headroom).

    Each partition keeps its edges sorted by source, ties in input order,
    in ``Ep`` slots: the largest partition's edge count, or
    ``edge_capacity`` where that is more. A caller that loads graphs of
    one size but different draws fixes ``edge_capacity`` above their
    largest partitions, so every load has one shape and the programs
    compiled for one serve the next (``vertex_rel_shapes`` gives that
    shape before the load). Without ``mesh`` the relation lands on the
    default device. With a ``mesh`` of N devices (P divisible by N), the
    partitions shard over it in contiguous blocks, the layout
    ``run_sharded`` runs: each device's block [w P/N, (w+1) P/N) is built
    on the host and placed on that device alone, under a ``pregel.place``
    span with its ``bytes``, so no device ever holds another's block.
    """
    edges = np.asarray(edges)
    if edges.dtype not in (np.int32, np.int64):
        edges = edges.astype(np.int64)
    Np = _rows_per_partition(num_vertices, P, capacity_factor, partition)

    def owner_of(v):
        if partition == "range":
            return np.minimum(v // Np, P - 1)
        return v % P

    def slot_of(v):
        if partition == "range":
            return v - owner_of(v) * Np
        return v // P

    vid = np.full((P, Np), -1, np.int32)
    all_v = np.arange(num_vertices, dtype=np.int64)
    vid[owner_of(all_v), slot_of(all_v)] = all_v.astype(np.int32)

    src, dst = edges[:, 0], edges[:, 1]
    ev = (np.asarray(edge_values, np.float32) if edge_values is not None
          else None)
    with ThreadPoolExecutor(min(P, 8)) as pool:
        picks, counts = _partition_picks(
            src, P, num_vertices, owner_of, pool)
        Ep = max(max(counts), edge_capacity, 1)

        def block(parts: range) -> dict:
            """Host arrays of partitions ``parts``."""
            e_src = np.full((len(parts), Ep), -1, np.int32)
            e_dst = np.full((len(parts), Ep), -1, np.int32)
            e_val = np.zeros((len(parts), Ep), np.float32)
            for k, p in enumerate(parts):
                e_src[k, :counts[p]] = slot_of(src[picks[p]])
                e_dst[k, :counts[p]] = dst[picks[p]]
                e_val[k, :counts[p]] = 1.0 if ev is None else ev[picks[p]]
            return dict(vid=vid[parts.start:parts.stop],
                        halt=np.zeros((len(parts), Np), bool),
                        value=np.zeros((len(parts), Np, value_dims),
                                       np.float32),
                        edge_src=e_src, edge_dst=e_dst, edge_val=e_val)

        if mesh is None:
            return VertexRel(**{f: jnp.asarray(a)
                                for f, a in block(range(P)).items()})
        return _place_blocks(mesh, P, block, pool)


def _rows_per_partition(num_vertices: int, P: int, capacity_factor: float,
                        partition: str) -> int:
    """``Np``: the vertex rows of each partition ``load_graph`` builds."""
    if partition == "range":
        capacity_factor = 1.0
    return int(np.ceil(num_vertices / P) * capacity_factor) + 1


def vertex_rel_shapes(num_vertices: int, P: int, *, value_dims: int,
                      edge_slots: int, capacity_factor: float = 1.3,
                      partition: str = "hash", mesh=None) -> VertexRel:
    """The relation ``load_graph(..., edge_capacity=edge_slots)`` returns
    for a graph whose partitions hold at most ``edge_slots`` edges each,
    as ``jax.ShapeDtypeStruct`` leaves, sharded over ``mesh`` by their
    leading axis as ``load_graph`` places them there."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as PSpec

    Np = _rows_per_partition(num_vertices, P, capacity_factor, partition)

    def leaf(shape, dtype):
        sharding = None if mesh is None else NamedSharding(
            mesh, PSpec(tuple(mesh.axis_names), *[None] * (len(shape) - 1)))
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    rows, edges = (P, Np), (P, edge_slots)
    return VertexRel(vid=leaf(rows, jnp.int32), halt=leaf(rows, jnp.bool_),
                     value=leaf(rows + (value_dims,), jnp.float32),
                     edge_src=leaf(edges, jnp.int32),
                     edge_dst=leaf(edges, jnp.int32),
                     edge_val=leaf(edges, jnp.float32))


def _partition_picks(src, P: int, num_vertices: int, owner_of, pool):
    """Per partition, the index (a slice, a mask or positions) that picks
    its edges out of the input in the order it keeps them, sorted by
    source with ties in input order, and their counts."""
    presorted = len(src) > 0 and bool(np.all(src[1:] >= src[:-1]))
    if presorted and P == 1:
        return [slice(None)], [len(src)]
    owner = owner_of(src)
    if presorted and P <= _MASKED_PARTITIONS:
        # a partition's edges are the ones its mask picks, in input order
        # (NumPy releases the GIL, so the masks fill side by side)
        picks = list(pool.map(lambda p: owner == p, range(P)))
        return picks, [int(np.count_nonzero(m)) for m in picks]
    order = np.argsort(owner.astype(np.int64) * (num_vertices + 1) + src,
                       kind="stable")
    counts = np.bincount(owner, minlength=P)
    start = np.concatenate([[0], np.cumsum(counts)])
    return ([order[start[p]:start[p + 1]] for p in range(P)],
            counts.tolist())


def _place_blocks(mesh, P: int, block, pool) -> VertexRel:
    """A VertexRel sharded over ``mesh`` by its leading (partition) axis,
    each device's block made by ``block(range of its partitions)`` and put
    on that device alone, the devices' blocks side by side on ``pool``."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as PSpec

    from repro.obs import trace
    axes = tuple(mesh.axis_names)
    N = int(mesh.devices.size)
    if P % N:
        raise ValueError(f"{P} partitions do not divide over {N} devices")
    rows = NamedSharding(mesh, PSpec(axes)).devices_indices_map((P,))
    devices = list(mesh.devices.flat)

    def place(w: int) -> dict:
        sl = rows[devices[w]][0]
        blk = block(range(sl.start or 0, P if sl.stop is None else sl.stop))
        with trace.annotate("pregel.place", "prepare", worker=w,
                            bytes=sum(a.nbytes for a in blk.values())):
            return jax.block_until_ready(
                {f: jax.device_put(a, devices[w]) for f, a in blk.items()})

    shards = list(pool.map(place, range(N)))
    return VertexRel(**{
        f: jax.make_array_from_single_device_arrays(
            (P,) + a.shape[1:],
            NamedSharding(mesh, PSpec(axes, *[None] * (a.ndim - 1))),
            [s[f] for s in shards])
        for f, a in shards[0].items()})


def out_degrees(vert: VertexRel) -> jax.Array:
    """(P, Np) out-degree per vertex slot."""
    P, Np = vert.vid.shape
    valid = vert.edge_src >= 0

    def per_part(src, val):
        return jnp.zeros((Np,), jnp.float32).at[
            jnp.where(val, src, Np)].add(val.astype(jnp.float32),
                                         mode="drop")

    return jax.vmap(per_part)(vert.edge_src, valid)


def gather_values(vert: VertexRel, num_vertices: int) -> np.ndarray:
    """Dump the Vertex relation back out (HDFS write analogue):
    -> (num_vertices, V) in vid order."""
    P, Np, V = vert.value.shape
    vid = np.asarray(vert.vid).reshape(-1)
    val = np.asarray(vert.value).reshape(-1, V)
    out = np.zeros((num_vertices, V), np.float32)
    ok = vid >= 0
    out[vid[ok]] = val[ok]
    return out
