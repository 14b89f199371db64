"""Jit-compatible wrapper: plans the work items of an edge stream's tiles
(host-side, once per graph) and runs the Pallas gather, whose output is
already in edge order."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels import backend
from repro.kernels.csr_spmv.csr_spmv import edge_gather_pallas, item_shift
from repro.kernels.csr_spmv.ref import edge_gather_ref


def _n_tiles(n_edge_slots: int, block_m: int) -> int:
    return max(-(-n_edge_slots // block_m), 1)


def _n_blocks(n_rows: int, block_r: int) -> int:
    return max(-(-n_rows // block_r), 1)


def _plan_items(edge_src: np.ndarray, n_rows: int, block_m: int,
                block_r: int):
    """(item_tile, item_row) of ``plan_layout``, in tile order then block
    order."""
    edge_src = np.asarray(edge_src)
    n_tiles = _n_tiles(len(edge_src), block_m)
    n_blocks = _n_blocks(n_rows, block_r)
    pos = np.flatnonzero(edge_src >= 0)
    key = (pos // block_m) * n_blocks + edge_src[pos] // block_r
    step = np.diff(key, prepend=-1)
    if np.all(step >= 0):
        key = key[step != 0]
    else:
        key = np.unique(key)
    has = np.zeros(n_tiles, bool)
    has[key // n_blocks] = True
    key = np.sort(np.concatenate([key, np.flatnonzero(~has) * n_blocks]))
    return ((key // n_blocks).astype(np.int32),
            (key % n_blocks).astype(np.int32))


def _plan_items_rows(edge_src: np.ndarray, n_rows: int, block_m: int,
                     block_r: int):
    """``_plan_items`` of the (P*Ep,) stream that a (P, Ep) block flattens
    to, row p over rows [p n_rows, (p+1) n_rows), for a block whose rows
    each hold their valid sources sorted ahead of their pads (as
    ``load_graph`` leaves them): a few int32 passes a row in place of the
    flat stream's int64 keys. None for any other block."""
    P, Ep = edge_src.shape
    n_blocks = _n_blocks(P * n_rows, block_r)
    wide = P * n_rows >= 1 << 31
    keys = []
    for p, row in enumerate(edge_src):
        c = int(np.count_nonzero(row >= 0))
        s = row[:c]
        if (row[c:] >= 0).any() or not np.all(s[1:] >= s[:-1]):
            return None
        if not c:
            continue
        b = ((s.astype(np.int64) if wide else s) + p * n_rows) // block_r
        first = np.empty(c, bool)      # an item starts here
        first[0] = True
        np.not_equal(b[1:], b[:-1], out=first[1:])
        first[(-p * Ep) % block_m::block_m] = True
        at = np.flatnonzero(first)
        keys.append((p * Ep + at) // block_m * n_blocks + b[at])
    key = np.concatenate(keys) if keys else np.zeros(0, np.int64)
    # a row's first item may repeat the previous row's last
    key = key[np.diff(key, prepend=-1) != 0]
    n_tiles = _n_tiles(P * Ep, block_m)
    has = np.zeros(n_tiles, bool)
    has[key // n_blocks] = True
    key = np.sort(np.concatenate([key, np.flatnonzero(~has) * n_blocks]))
    return ((key // n_blocks).astype(np.int32),
            (key % n_blocks).astype(np.int32))


def plan_rows_layout_fixed(edge_src: np.ndarray, n_rows: int, *,
                           block_m: int = 1024, block_r: int = 256):
    """``plan_layout_fixed`` of the stream a (P, Ep) block flattens to,
    row p's sources offset by p * n_rows (invalid: -1), over P * n_rows
    rows."""
    edge_src = np.asarray(edge_src)
    P, Ep = edge_src.shape
    items = _plan_items_rows(edge_src, n_rows, block_m, block_r)
    if items is None:
        off = (np.arange(P, dtype=np.int64) * n_rows)[:, None]
        flat = np.where(edge_src >= 0, edge_src + off, -1).reshape(-1)
        items = _plan_items(flat, P * n_rows, block_m, block_r)
    cap = item_capacity(P * Ep, P * n_rows, block_m=block_m,
                        block_r=block_r)
    return _pad_items(*items, cap, _n_blocks(P * n_rows, block_r))


def plan_layout(edge_src: np.ndarray, n_rows: int, *, block_m: int = 1024,
                block_r: int = 256) -> np.ndarray:
    """Host-side plan of the kernel's work items. Tile t is edges
    [block_m t, block_m (t + 1)) of the stream; its items are the distinct
    row blocks of block_r rows that its valid edges (source >= 0) touch,
    or block 0 alone for a tile without one. Returns the items in tile
    order then block order, as the kernel's int32 words (``pack_items``).

    A stream whose valid sources are non-decreasing (sorted by source,
    pads anywhere) takes one pass with no sort, and has fewer than
    ``item_capacity`` items; any other stream is sorted by key here, and
    its item count grows with the data."""
    return pack_items(*_plan_items(edge_src, n_rows, block_m, block_r),
                      _n_blocks(n_rows, block_r))


def item_capacity(n_edge_slots: int, n_rows: int, *, block_m: int = 1024,
                  block_r: int = 256) -> int:
    """Bound on ``plan_layout``'s item count for a stream with
    non-decreasing valid sources: each tile has one item plus one for
    each row block that starts inside it. A function of SHAPES only."""
    return _n_tiles(n_edge_slots, block_m) + _n_blocks(n_rows, block_r)


def pack_items(item_tile, item_row, n_blocks: int) -> np.ndarray:
    """The kernel's item words, ``(i - tile) << item_shift | row block``
    for item i."""
    shift = item_shift(n_blocks)
    lag = np.arange(len(item_tile), dtype=np.int64) - item_tile
    if lag.max() >= 1 << (31 - shift):
        raise ValueError(f"{len(item_tile)} gather items over {n_blocks} "
                         "row blocks do not fit one int32 word each")
    return ((lag << shift) | item_row).astype(np.int32)


def unpack_items(items, n_blocks: int):
    """(item_tile, item_row) of ``pack_items``' words."""
    shift = item_shift(n_blocks)
    items = np.asarray(items)
    return (np.arange(len(items)) - (items >> shift)).astype(np.int32), \
        items & ((1 << shift) - 1)


def plan_layout_fixed(edge_src: np.ndarray, n_rows: int, *,
                      block_m: int = 1024, block_r: int = 256):
    """``plan_layout`` padded to ``item_capacity`` items by repeating the
    last real item (which rewrites the same values), as the kernel's
    packed words. For streams with non-decreasing valid sources the shape
    then depends ONLY on (len(edge_src), n_rows, block_m, block_r), which
    lets a layout be a TRACED argument of one shared jitted superstep (the
    out-of-core driver reuses a single compiled step across
    super-partitions, each with its own layout). Any other stream may
    need more items, and keeps them."""
    item_tile, item_row = _plan_items(edge_src, n_rows, block_m, block_r)
    cap = item_capacity(len(edge_src), n_rows, block_m=block_m,
                        block_r=block_r)
    return _pad_items(item_tile, item_row, cap, _n_blocks(n_rows, block_r))


def _pad_items(item_tile, item_row, n_items: int, n_blocks: int):
    pad = max(n_items - len(item_tile), 0)
    return pack_items(np.pad(item_tile, (0, pad), mode="edge"),
                      np.pad(item_row, (0, pad), mode="edge"), n_blocks)


def pad_layout(items, n_items: int, n_rows: int, *,
               block_r: int = 256) -> np.ndarray:
    """A layout's words padded to ``n_items`` items by repeating its last
    item, as ``plan_layout_fixed`` pads (a word holds its item's lag
    behind its tile, so the word itself cannot be repeated)."""
    n_blocks = _n_blocks(n_rows, block_r)
    return _pad_items(*unpack_items(items, n_blocks), n_items, n_blocks)


def layout_counts(items, n_rows: int, *, block_r: int = 256) -> tuple:
    """(real items, tiles) of a ``plan_layout_fixed`` layout: real items
    are distinct, and the padding repeats the last of them."""
    item_tile, item_row = unpack_items(items, _n_blocks(n_rows, block_r))
    pads = np.count_nonzero((item_tile[1:] == item_tile[:-1])
                            & (item_row[1:] == item_row[:-1]))
    return len(item_tile) - int(pads), int(item_tile[-1]) + 1


def gather_channels(table, edge_src, layout, *, interpret: bool,
                    block_m: int = 1024, block_r: int = 256):
    """table: (C, N) channel-major values; edge_src: (E,) value row per
    edge, -1 = invalid; layout from ``plan_layout`` or
    ``plan_layout_fixed`` over that stream and N rows. -> (C, E):
    table[:, src[e]] per edge, 0.0 for invalid edges. The stream is
    copied only to pad it to whole tiles."""
    C, N = table.shape
    C8 = -(-C // 8) * 8
    tab = jnp.pad(table.astype(jnp.float32),
                  ((0, C8 - C), (0, (-N) % block_r)))
    E = edge_src.shape[0]
    pad = _n_tiles(E, block_m) * block_m - E
    if pad:
        edge_src = jnp.pad(edge_src, (0, pad), constant_values=-1)
    out = edge_gather_pallas(tab, edge_src, jnp.asarray(layout), C,
                             block_m=block_m, block_r=block_r,
                             interpret=interpret)
    return out[:, :E] if pad else out


def edge_gather(values, edge_src, edge_val, *, layout=None,
                impl: str = "auto", block_m: int = 1024,
                block_r: int = 256):
    """values: (N, V); edge_src: (E,); edge_val: (E,) -> (E, V)."""
    impl_r = backend.resolve(impl)
    if impl_r == "ref" or layout is None:
        return edge_gather_ref(values, edge_src, edge_val)
    g = gather_channels(values.T, edge_src, layout, block_m=block_m,
                        block_r=block_r,
                        interpret=(impl_r != "pallas_tpu"))
    return jnp.where((edge_src >= 0)[:, None], g.T * edge_val[:, None],
                     0.0)
