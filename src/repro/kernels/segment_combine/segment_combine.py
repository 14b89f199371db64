"""Pallas TPU kernel: segmented combine over key-sorted runs.

Layout: the (M,) key stream and each payload channel are laid out
lane-dense as (M / L, L) with L = 128 lanes, and tiled into (R, L) blocks
of R*L consecutive rows. A segment starts wherever a key differs from the
key before it in that row-major order.

Inside a tile the inclusive segmented fold runs in two log-step levels:
a Hillis-Steele scan along the lanes of each row, then the same scan over
the rows' trailing runs along the sublanes. Shifts are ``pltpu.roll``
rotations with the wrapped positions masked out, so every step is a
full-vreg elementwise op. A VMEM scratch carries the previous tile's last
key row and last folded row to the next grid step, which runs after it
(the grid is sequential), and is reset at the first tile of every
partition.

``fold_tile`` is shared with the jnp re-execution in ``ref.py``: both run
the same operations in the same order, so the two paths agree bit for bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
IDENT = {"sum": 0.0, "min": jnp.inf, "max": -jnp.inf}
NO_KEY = -2   # carry key before the first tile: never equal to a real key


def _fn(op):
    return {"sum": lambda a, b: a + b, "min": jnp.minimum,
            "max": jnp.maximum}[op]


def _reduce_lanes(x, op):
    red = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}[op]
    return red(x, axis=1, keepdims=True)


def tile_shape(block_m: int):
    """(rows, lanes) of a tile of ``block_m`` stream rows."""
    lanes = min(LANES, block_m)
    if block_m % lanes:
        raise ValueError(f"block_m={block_m} must be a multiple of "
                         f"{lanes}")
    return block_m // lanes, lanes


def fold_tile(key, vals, carry_key, carry_vals, op, roll):
    """Inclusive segmented fold of one (R, L) tile.

    key: (R, L) int32; vals: tuple of D (R, L) float32 channels;
    carry_key: (1, L) int32, the previous tile's last key row;
    carry_vals: tuple of D (1, L), the previous tile's last folded row;
    roll: ``pltpu.roll`` in the kernel, ``jnp.roll`` in the jnp path
    (same semantics: ``roll(x, s, a)[i] = x[i - s]``).
    Returns the folded channels, a tuple of D (R, L)."""
    fn, ident = _fn(op), IDENT[op]
    R, L = key.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (R, L), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (R, L), 0)
    # key of the previous stream row (row-major), the carry feeding row 0
    prev_row = jnp.where(row == 0, carry_key, roll(key, 1, 0))
    prev = jnp.where(lane == 0, roll(prev_row, 1, 1), roll(key, 1, 1))
    # f: 1.0 where a segment starts at or before this lane of its row
    f = jnp.where(key != prev, 1.0, 0.0)
    v = list(vals)
    sh = 1
    while sh < L:   # level 1: along the lanes of each row
        inside = lane >= sh
        join = inside & (f == 0.0)
        v = [jnp.where(join, fn(roll(x, sh, 1), x), x) for x in v]
        f = jnp.where(inside, jnp.maximum(f, roll(f, sh, 1)), f)
        sh *= 2
    # each row's trailing run: (started inside the row?, its fold)
    last = lane == L - 1
    tf = jnp.broadcast_to(jnp.max(f, axis=1, keepdims=True), (R, L))
    tv = [jnp.broadcast_to(_reduce_lanes(jnp.where(last, x, ident), op),
                           (R, L)) for x in v]
    sh = 1
    while sh < R:   # level 2: the rows' trailing runs along the sublanes
        inside = row >= sh
        join = inside & (tf == 0.0)
        tv = [jnp.where(join, fn(roll(t, sh, 0), t), t) for t in tv]
        tf = jnp.where(inside, jnp.maximum(tf, roll(tf, sh, 0)), tf)
        sh *= 2
    # rows still open at their start take the run ending on the row
    # above, then the run the previous tile left open
    open_ = f == 0.0
    v = [jnp.where(open_ & (row >= 1), fn(roll(t, 1, 0), x), x)
         for t, x in zip(tv, v)]
    tile_open = open_ & (jnp.where(row >= 1, roll(tf, 1, 0), 0.0) == 0.0)
    lane1 = jax.lax.broadcasted_iota(jnp.int32, (1, L), 1)
    cv = [_reduce_lanes(jnp.where(lane1 == L - 1, c, ident), op)
          for c in carry_vals]
    return tuple(jnp.where(tile_open, fn(c, x), x) for c, x in zip(cv, v))


def _kernel(key_ref, pay_ref, out_ref, carry_key, carry_val, *, op: str,
            n_chan: int):
    R = key_ref.shape[0]

    @pl.when(pl.program_id(1) == 0)
    def _reset():   # first tile of a partition
        carry_key[...] = jnp.full(carry_key.shape, NO_KEY, jnp.int32)
        carry_val[...] = jnp.full(carry_val.shape, IDENT[op], jnp.float32)

    vals = tuple(pay_ref[d] for d in range(n_chan))
    cvs = tuple(carry_val[d:d + 1, :] for d in range(n_chan))
    out = fold_tile(key_ref[...], vals, carry_key[...], cvs, op, pltpu.roll)
    for d in range(n_chan):
        out_ref[d] = out[d]
        carry_val[d:d + 1, :] = out_ref[d, R - 1:R, :]
    carry_key[...] = key_ref[R - 1:R, :]


def fold_lane_dense(key, pay, op: str, *, block_m: int,
                    interpret: bool = True):
    """key: (P, M) int32; pay: (P, D, M) float32; M a multiple of
    ``block_m``. Folds each partition's stream independently (the carry
    resets at every partition). -> folded (P, D, M)."""
    P, D, M = pay.shape
    R, L = tile_shape(block_m)
    n_tiles = M // block_m
    return pl.pallas_call(
        functools.partial(_kernel, op=op, n_chan=D),
        grid=(P, n_tiles),
        in_specs=[pl.BlockSpec((None, R, L), lambda p, t: (p, t, 0)),
                  pl.BlockSpec((None, D, R, L), lambda p, t: (p, 0, t, 0))],
        out_specs=pl.BlockSpec((None, D, R, L), lambda p, t: (p, 0, t, 0)),
        out_shape=jax.ShapeDtypeStruct((P, D, M // L, L), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, L), jnp.int32),
                        pltpu.VMEM((D, L), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="segment_combine",
    )(key.reshape(P, M // L, L), pay.reshape(P, D, M // L, L)) \
        .reshape(P, D, M)


def lane_dense_inputs(seg_ids, payload, valid, op: str, block_m: int):
    """(M,) keys + (M, D) payload -> padded (1, Mp) keys and (1, D, Mp)
    payload; invalid and pad rows carry (int32.max, IDENT)."""
    M, D = payload.shape
    big = jnp.iinfo(jnp.int32).max
    pad = (-M) % block_m
    key = jnp.pad(jnp.where(valid, seg_ids, big), (0, pad),
                  constant_values=big)
    pay = jnp.pad(jnp.where(valid[:, None], payload,
                            IDENT[op]).astype(jnp.float32),
                  ((0, pad), (0, 0)), constant_values=IDENT[op])
    return key[None], pay.T[None]


def is_last_row(seg_ids, valid):
    """Last row of each run of equal keys, masked by valid."""
    big = jnp.iinfo(jnp.int32).max
    s = jnp.where(valid, seg_ids, big)
    return jnp.concatenate([s[1:] != s[:-1], jnp.ones((1,), bool)]) & valid


def segment_combine_pallas(seg_ids: jax.Array, payload: jax.Array,
                           valid: jax.Array, op: str = "sum", *,
                           block_m: int = 8 * LANES,
                           interpret: bool = True):
    """seg_ids: (M,) int32 with equal keys in contiguous runs; payload:
    (M, D); -> (folded (M, D), is_last (M,)). Rows with valid=False are
    keyed int32.max and carry the identity."""
    M, D = payload.shape
    key, pay = lane_dense_inputs(seg_ids, payload, valid, op, block_m)
    folded = fold_lane_dense(key, pay, op, block_m=block_m,
                             interpret=interpret)
    return folded[0].T[:M], is_last_row(seg_ids, valid)
