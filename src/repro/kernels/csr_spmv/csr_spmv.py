"""Pallas TPU kernel: edge gather written in edge order (CSR message
generation).

Tiles are fixed ranges of the edge stream: tile t is edges
[BM t, BM (t + 1)), an (S, 128) block of edge rows. The grid runs over
work items, not tiles: an item is a (tile, row block) pair, one for each
distinct block of BR value rows that the tile's valid edges touch, listed
in tile order (ops.py plans them on the host). One int32 word per item
arrives by scalar prefetch, ``(i - tile) << shift | row block`` for item
i, and selects the edge block, the output block and the value block in
the BlockSpec index_maps. One word and not two because the list lives in
SMEM, 1 MiB on a v5e: two int32 arrays of Graph500 scale 22's 152,372
items would need 1.17 MiB. For a stream sorted by source, i - tile is
at most the number of row blocks, so both fields fit.

A tile's items are consecutive grid steps, so its output block stays in
VMEM across them (the MegaBlocks pattern): the first item zeroes it, and
each item writes the lanes whose source lies in its row block.

Everything is lane-dense: the value table is channel-major (C8, N) with
the channels padded to 8 sublanes, and the output is channel-major (C,
E / 128, 128). For each row of 128 edges the gather is a one-hot matmul
on the MXU, (C8, BR) @ (BR, 128): the transposed orientation puts the
edges on the lanes of the result. HIGHEST precision makes the f32 product
exact, and the lanes are selected, not summed, so a finite value is
reproduced bit for bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def item_shift(n_blocks: int) -> int:
    """Bits of an item word that hold the row block."""
    return max(n_blocks - 1, 1).bit_length()


def _kernel(items_ref, src_ref, tab_ref, out_ref, *, block_r: int,
            n_chan: int, shift: int):
    i = pl.program_id(0)
    j = jnp.maximum(i - 1, 0)
    tile = i - (items_ref[i] >> shift)

    @pl.when((i == 0) | (j - (items_ref[j] >> shift) != tile))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    r0 = (items_ref[i] & ((1 << shift) - 1)) * block_r
    tab = tab_ref[...]                                   # (C8, BR)
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_r, LANES), 0)
    for s in range(src_ref.shape[0]):
        local = src_ref[s:s + 1, :] - r0                 # (1, 128); pads < 0
        onehot = jnp.where(rows == local, 1.0, 0.0)      # (BR, 128)
        res = jax.lax.dot_general(
            tab, onehot, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)          # (C8, 128)
        mine = (local >= 0) & (local < block_r)
        for c in range(n_chan):
            out_ref[c, s:s + 1, :] = jnp.where(mine, res[c:c + 1, :],
                                               out_ref[c, s:s + 1, :])


def edge_gather_pallas(table: jax.Array, edge_src: jax.Array,
                       items: jax.Array, n_chan: int, *,
                       block_m: int = 1024, block_r: int = 256,
                       interpret: bool = True):
    """table: (C8, N) channel-major, C8 a multiple of 8 and N of block_r;
    edge_src: (n_tiles * block_m,) int32 value row per edge, -1 pad;
    items: (n_items,) int32 words, ``(i - tile) << item_shift(N //
    block_r) | row block``, every tile's items consecutive and in tile
    order, each tile with at least one item, and every valid edge's row
    block among its tile's items (an item may repeat the one before it).
    -> (n_chan, n_tiles * block_m) gathered channels in edge order (0.0
    at pads)."""
    n_edges = edge_src.shape[0]
    C8, N = table.shape
    S = block_m // LANES
    shift = item_shift(N // block_r)
    mask = (1 << shift) - 1
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(items.shape[0],),
        in_specs=[pl.BlockSpec((S, LANES),
                               lambda i, w: (i - (w[i] >> shift), 0)),
                  pl.BlockSpec((C8, block_r),
                               lambda i, w: (0, w[i] & mask))],
        out_specs=pl.BlockSpec((n_chan, S, LANES),
                               lambda i, w: (0, i - (w[i] >> shift), 0)),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, block_r=block_r, n_chan=n_chan,
                          shift=shift),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_chan, n_edges // LANES, LANES),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="csr_spmv",
    )(items, edge_src.reshape(-1, LANES), table)
    return out.reshape(n_chan, n_edges)
