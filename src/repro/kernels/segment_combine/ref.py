"""Pure-jnp oracle for the segmented combine (sorted-run group-by fold).

Given payloads sorted by segment id, computes the inclusive segmented fold
and marks the last row of each segment (the group's aggregate). This is the
receiver-side group-by inner loop of the Pregelix dataflow.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

OPS = {
    "sum": (lambda a, b: a + b, 0.0),
    "min": (jnp.minimum, jnp.inf),
    "max": (jnp.maximum, -jnp.inf),
}


def segment_combine_ref(seg_ids: jax.Array, payload: jax.Array,
                        valid: jax.Array, op: str = "sum"):
    """seg_ids: (M,) int32 sorted; payload: (M, D); valid: (M,).
    -> (folded (M, D), is_last (M,)) where folded[i] is the running
    aggregate of payload over seg_ids == seg_ids[i] up to i."""
    fn, ident = OPS[op]
    M, D = payload.shape
    x = jnp.where(valid[:, None], payload, ident).astype(jnp.float32)
    starts = jnp.concatenate([jnp.ones((1,), bool),
                              seg_ids[1:] != seg_ids[:-1]])

    def comb(a, b):
        fa, va = a
        fb, vb = b
        return fa | fb, jnp.where(fb[:, None], vb, fn(va, vb))

    _, folded = jax.lax.associative_scan(comb, (starts, x))
    is_last = jnp.concatenate([seg_ids[1:] != seg_ids[:-1],
                               jnp.ones((1,), bool)]) & valid
    return folded, is_last


def fold_lane_dense_ref(key, pay, op: str, *, block_m: int):
    """jnp re-execution of the Pallas kernel's exact computation
    (``segment_combine.fold_lane_dense``): the same ``fold_tile`` on the
    same (R, L) tiles, with the tile carry threaded by ``lax.scan`` and
    reset per partition. key: (P, M) int32; pay: (P, D, M); M a multiple
    of ``block_m``. -> folded (P, D, M).

    ``segment_combine_ref`` above is the readable oracle, but its
    ``associative_scan`` brackets float sums differently, so its low bits
    can differ from the kernel's. The engine's ``kernel_impl="ref"``
    sender combine folds through THIS function so that "ref" and "pallas"
    runs stay bit-for-bit identical even for ``op="sum"``.

    The scan keeps the trace O(1) in the number of tiles (an unrolled
    loop would make compile time grow with the graph)."""
    from repro.kernels.segment_combine.segment_combine import (
        IDENT, NO_KEY, fold_tile, tile_shape)
    P, D, M = pay.shape
    R, L = tile_shape(block_m)
    n = M // block_m
    kt = key.reshape(P, n, R, L).transpose(1, 0, 2, 3)
    pt = pay.reshape(P, D, n, R, L).transpose(2, 0, 1, 3, 4)

    def tile(carry, kp):
        ck, cv = carry
        k, p = kp
        out = jax.vmap(lambda k1, p1, ck1, cv1: jnp.stack(fold_tile(
            k1, tuple(p1), ck1, tuple(cv1), op, jnp.roll)))(k, p, ck, cv)
        return (k[:, R - 1:R], out[:, :, R - 1:R]), out

    carry0 = (jnp.full((P, 1, L), NO_KEY, jnp.int32),
              jnp.full((P, D, 1, L), IDENT[op], jnp.float32))
    _, outs = jax.lax.scan(tile, carry0, (kt, pt))
    return outs.transpose(1, 2, 0, 3, 4).reshape(P, D, M)


def segment_combine_blocked(seg_ids: jax.Array, payload: jax.Array,
                            valid: jax.Array, op: str = "sum", *,
                            block_m: int = 1024):
    """``segment_combine_pallas``'s contract, computed by
    ``fold_lane_dense_ref``: bit-for-bit the kernel's output."""
    from repro.kernels.segment_combine.segment_combine import (
        is_last_row, lane_dense_inputs)
    M, D = payload.shape
    key, pay = lane_dense_inputs(seg_ids, payload, valid, op, block_m)
    folded = fold_lane_dense_ref(key, pay, op, block_m=block_m)
    return folded[0].T[:M], is_last_row(seg_ids, valid)
