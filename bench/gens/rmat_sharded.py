"""Graph500 Kronecker (R-MAT) edge list for a graph no single chip holds,
each chip drawing its own share.

The draw of ``rmat.py`` (Graph500 specification v3: each edge by `scale`
recursive quadrant choices with initiator probabilities A, B, C and
D = 1 - A - B - C; self-loops and duplicates kept; one random
permutation of the vertex labels; every edge stored in both directions),
split over the first ``chips`` devices: chip k draws edgefactor *
2**scale / chips edges from its own stream of the seed and relabels them
with the one permutation, which the host draws from the seed's stream 0.
The chips draw in one program that sorts nothing: a TPU compile of a
sort takes over half a minute whatever its length, a radix sort of the
same slots on the host's cores a few seconds. Every seed yields exactly
2 * edgefactor * 2**scale slots, one shape per chip. A chip's footprint
is its own share, logged per chip after generation; the host sorts each
chip's slots, one direction at a time, by source and merges the runs into
one stream sorted by source, ties in run order.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as PSpec

from bench.chips import log_memory
from bench.common import Graph, seed_key


def generate(cfg: dict, seed: int) -> Graph:
    chips = cfg["chips"]
    devices = jax.devices()[:chips]
    if len(devices) < chips:
        raise RuntimeError(f"{cfg['name']} draws on {chips} chips; JAX "
                           f"sees {len(devices)} device(s)")
    m = cfg["edgefactor"] << cfg["scale"]
    if m % chips:
        raise ValueError(f"{m} edges do not split over {chips} chips")
    mesh = jax.make_mesh((chips,), ("chips",), devices=devices)
    n = 1 << cfg["scale"]
    # chip k's edge stream is stream 1 + k of the seed; stream 0 permutes
    k_edge = np.stack([np.asarray(jax.random.key_data(seed_key(seed, 1 + k)))
                       for k in range(chips)])
    perm = np.random.default_rng(np.random.SeedSequence(
        seed % (1 << 64), spawn_key=(0,))).permutation(n).astype(np.int32)
    draw = _draw(mesh, scale=cfg["scale"], m=m // chips, a=cfg["a"],
                 b=cfg["b"], c=cfg["c"])
    src, dst = draw(
        jax.device_put(k_edge, NamedSharding(mesh, PSpec("chips"))),
        jax.device_put(perm, NamedSharding(mesh, PSpec())))
    jax.block_until_ready((src, dst))
    log_memory("after generation", devices)
    shares = jax.device_get(list(zip(_rows(src), _rows(dst))))
    del src, dst
    # each edge in both directions, a run a direction and chip
    runs = [run for s, d in shares for run in ((s, d), (d, s))]
    with ThreadPoolExecutor(len(runs)) as pool:
        runs = list(pool.map(lambda r: _by_source(*r, cfg["scale"]), runs))
        src, dst = _merge(runs, n, pool)
    return Graph(n=n, src=src, dst=dst, partitions=cfg["partitions"])


def _rows(arr) -> list:
    """The per-chip pieces of an array sharded by chip, in chip order."""
    shards = sorted(arr.addressable_shards,
                    key=lambda s: s.index[0].start or 0)
    return [s.data for s in shards]


def _by_source(src, dst, bits: int) -> tuple:
    """(src, dst) sorted by src < 2**bits, ties in input order: a least
    significant digit radix sort, 16 bits a pass (NumPy's stable sort of
    16-bit keys is a radix sort, and it releases the GIL)."""
    order = None
    for shift in range(0, bits, 16):
        keys = src if order is None else src[order]
        step = np.argsort((keys >> shift).astype(np.uint16), kind="stable")
        order = step if order is None else order[step]
    if order is None:
        return src, dst
    return src[order], dst[order]


def _merge(runs: list, n: int, pool) -> tuple:
    """(src, dst) runs, each sorted by source, as one stream sorted by
    source with ties in run order (the order a stable sort of their
    concatenation gives): run k's slots of source v land after every slot
    of a smaller source and the earlier runs' slots of v."""
    counts = list(pool.map(lambda r: np.bincount(r[0], minlength=n), runs))
    total = np.sum(counts, axis=0)
    slots = int(total.sum())
    ix = np.int32 if slots < 1 << 31 else np.int64
    at = np.zeros(n, np.int64)          # where each source's slots start
    np.cumsum(total[:-1], out=at[1:])
    shift = []                          # run position -> stream position
    for c in counts:
        shift.append((at - (np.cumsum(c) - c)).astype(ix))
        at += c
    dst = np.empty(slots, runs[0][1].dtype)

    def place(k: int) -> None:
        (s, d), c = runs[k], counts[k]
        pos = np.repeat(shift[k], c)
        pos += np.arange(len(s), dtype=ix)
        dst[pos] = d

    list(pool.map(place, range(len(runs))))
    return np.repeat(np.arange(n, dtype=runs[0][0].dtype), total), dst


@lru_cache(maxsize=None)
def _draw(mesh, **kw):
    """The jitted draw of every chip's share: (chips, 2) edge-stream key
    words sharded one row a chip and the label permutation on every chip
    -> (src, dst), each chip's m edges in one direction, unsorted."""

    def body(k_edge, perm):
        return _share(jax.random.wrap_key_data(k_edge[0]), perm, **kw)

    return jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(PSpec("chips"), PSpec()),
                                 out_specs=(PSpec("chips"), PSpec("chips")),
                                 check_vma=False))


def _share(k_edge, perm, *, scale: int, m: int, a: float, b: float,
           c: float):
    """One chip's m edges, relabelled by ``perm``."""

    def level(lvl, sd):
        s, d = sd
        r = jax.random.uniform(jax.random.fold_in(k_edge, lvl), (m,))
        right_src = r > a + b                                   # C or D
        right_dst = ((r > a) & (r <= a + b)) | (r > a + b + c)  # B or D
        return (s | (right_src.astype(jnp.int32) << lvl),
                d | (right_dst.astype(jnp.int32) << lvl))

    zero = jnp.zeros((m,), jnp.int32)
    s, d = jax.lax.fori_loop(0, scale, level, (zero, zero))
    return perm[s], perm[d]
