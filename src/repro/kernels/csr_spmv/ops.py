"""Jit-compatible wrapper: lays out src-sorted edges into row-block-aligned
tiles (host-side, once per graph) and runs the Pallas gather."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels import backend
from repro.kernels.csr_spmv.csr_spmv import edge_gather_pallas
from repro.kernels.csr_spmv.ref import edge_gather_ref


def plan_layout(edge_src: np.ndarray, n_rows: int, *, block_m: int = 1024,
                block_r: int = 256):
    """Host-side layout plan: group the edges by the row block of their
    source and pad each block's range to a multiple of block_m slots.
    Returns (slot_src (n_slots,) value row per slot, -1 = pad;
    inv (E,) slot of each edge, -1 = invalid edge;
    tile_row (n_slots // block_m,) row block of each tile)."""
    edge_src = np.asarray(edge_src)
    E = len(edge_src)
    n_blocks = (n_rows + block_r - 1) // block_r
    ok = edge_src >= 0
    blk_ids = np.where(ok, edge_src // block_r, n_blocks)
    order = np.argsort(blk_ids, kind="stable")
    counts = np.bincount(blk_ids, minlength=n_blocks + 1)[:n_blocks]
    padded = ((counts + block_m - 1) // block_m) * block_m
    p_starts = np.concatenate([[0], np.cumsum(padded)[:-1]])
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    n_slots = int(np.sum(padded)) or block_m
    blk = blk_ids[order]
    valid = blk < n_blocks
    blk_c = np.minimum(blk, n_blocks - 1)
    pos = np.arange(E) - starts[blk_c] + p_starts[blk_c]
    slot_src = np.full(n_slots, -1, np.int32)
    slot_src[pos[valid]] = edge_src[order[valid]]
    inv = np.full(E, -1, np.int32)
    inv[order[valid]] = pos[valid]
    tile_row = np.repeat(np.arange(n_blocks), padded // block_m) \
        .astype(np.int32)
    if len(tile_row) == 0:
        tile_row = np.zeros(n_slots // block_m, np.int32)
    return slot_src, inv, tile_row


def layout_capacity(n_edge_slots: int, n_rows: int, *, block_m: int = 1024,
                    block_r: int = 256) -> int:
    """Worst-case slot count of ``plan_layout``: each non-empty row block
    wastes < block_m slots, so E rounded up plus one block per row block
    always fits. A function of SHAPES only — no edge data."""
    n_blocks = (n_rows + block_r - 1) // block_r
    cap = ((n_edge_slots + block_m - 1) // block_m + n_blocks) * block_m
    return max(cap, block_m)


def plan_layout_fixed(edge_src: np.ndarray, n_rows: int, *,
                      block_m: int = 1024, block_r: int = 256):
    """``plan_layout`` padded to shapes that depend ONLY on
    (len(edge_src), n_rows, block_m, block_r) — never on where the edges
    actually point. Equal-shape edge blocks therefore produce equal-shape
    layouts, which is what lets a layout be a TRACED argument of one
    shared jitted superstep (the out-of-core driver reuses a single
    compiled step across super-partitions, each with its own layout).
    Pad slots carry slot_src = -1 and tile_row = 0 (they gather 0.0)."""
    slot_src, inv, tile_row = plan_layout(edge_src, n_rows,
                                          block_m=block_m, block_r=block_r)
    cap = layout_capacity(len(edge_src), n_rows, block_m=block_m,
                          block_r=block_r)
    slot_f = np.full(cap, -1, np.int32)
    slot_f[:len(slot_src)] = slot_src
    tile_f = np.zeros(cap // block_m, np.int32)
    tile_f[:len(tile_row)] = tile_row
    return slot_f, inv, tile_f


def gather_channels(table, layout, *, interpret: bool, block_m: int = 1024,
                    block_r: int = 256):
    """table: (C, N) channel-major values; layout from ``plan_layout``.
    -> (C, E): table[:, src[e]] per edge, 0.0 for invalid edges."""
    slot_src, inv, tile_row = layout
    C, N = table.shape
    C8 = -(-C // 8) * 8
    tab = jnp.pad(table.astype(jnp.float32),
                  ((0, C8 - C), (0, (-N) % block_r)))
    out = edge_gather_pallas(tab, jnp.asarray(slot_src),
                             jnp.asarray(tile_row), C, block_m=block_m,
                             block_r=block_r, interpret=interpret)
    inv = jnp.asarray(inv)
    ok, idx = inv >= 0, inv.clip(0)
    return jnp.stack([jnp.where(ok, out[c][idx], 0.0) for c in range(C)])


def edge_gather(values, edge_src, edge_val, *, layout=None,
                impl: str = "auto", block_m: int = 1024,
                block_r: int = 256):
    """values: (N, V); edge_src: (E,); edge_val: (E,) -> (E, V)."""
    impl_r = backend.resolve(impl)
    if impl_r == "ref" or layout is None:
        return edge_gather_ref(values, edge_src, edge_val)
    g = gather_channels(values.T, layout, block_m=block_m, block_r=block_r,
                        interpret=(impl_r != "pallas_tpu"))
    return jnp.where((edge_src >= 0)[:, None], g.T * edge_val[:, None],
                     0.0)
