"""Pallas TPU kernel: row-blocked edge gather (CSR message generation).

Edges are grouped by the row block of their source; the host (ops.py)
pads each block's edge range to a multiple of the tile, so every tile of
BM edge slots reads exactly one block of BR value rows. The tile -> row
block map arrives via scalar prefetch and selects the value block in the
BlockSpec index_map.

Everything is lane-dense: the edge slots of a tile are an (S, 128) block
of slot rows, the value table is channel-major (C8, N) with the channels
padded to 8 sublanes, and the output is channel-major (C, slots / 128,
128). For each row of 128 slots the gather is a one-hot matmul on the MXU,
(C8, BR) @ (BR, 128): the transposed orientation puts the edge slots on
the lanes of the result. HIGHEST precision makes the f32 product exact,
so a finite value is reproduced bit for bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _kernel(tile_row_ref, src_ref, tab_ref, out_ref, *, block_r: int,
            n_chan: int):
    r0 = tile_row_ref[pl.program_id(0)] * block_r
    tab = tab_ref[...]                                   # (C8, BR)
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_r, LANES), 0)
    for s in range(src_ref.shape[0]):
        local = src_ref[s:s + 1, :] - r0                 # (1, 128); pads < 0
        onehot = jnp.where(rows == local, 1.0, 0.0)      # (BR, 128)
        res = jax.lax.dot_general(
            tab, onehot, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)          # (C8, 128)
        for c in range(n_chan):
            out_ref[c, s:s + 1, :] = res[c:c + 1, :]


def edge_gather_pallas(table: jax.Array, slot_src: jax.Array,
                       tile_row: jax.Array, n_chan: int, *,
                       block_m: int = 1024, block_r: int = 256,
                       interpret: bool = True):
    """table: (C8, N) channel-major, C8 a multiple of 8 and N of block_r;
    slot_src: (n_tiles * block_m,) int32 value row per edge slot, -1 pad,
    tile i only touching rows of block tile_row[i].
    -> (n_chan, n_tiles * block_m) gathered channels (0.0 at pads)."""
    n_slots = slot_src.shape[0]
    C8, N = table.shape
    S = block_m // LANES
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_slots // block_m,),
        in_specs=[pl.BlockSpec((S, LANES), lambda i, tr: (i, 0)),
                  pl.BlockSpec((C8, block_r), lambda i, tr: (0, tr[i]))],
        out_specs=pl.BlockSpec((n_chan, S, LANES),
                               lambda i, tr: (0, i, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, block_r=block_r, n_chan=n_chan),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_chan, n_slots // LANES, LANES),
                                       jnp.float32),
        interpret=interpret,
        name="csr_spmv",
    )(tile_row, slot_src.reshape(-1, LANES), table)
    return out.reshape(n_chan, n_slots)
