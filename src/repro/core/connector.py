"""Connectors (paper Section 4): m-to-n partitioning / partitioning-merging
data exchange, with fixed-capacity buckets + validity masks (the static-
shape adaptation of tuple streams; overflow is counted and surfaces in GS
so the driver can grow capacity — the moral equivalent of a spill).

Two transports for the same bucketed exchange:
* emulated   — partitions stacked on a leading axis, exchange = transpose
               (single-host tests/benches);
* shard_map  — ``jax.lax.all_to_all`` over the mesh axis (production; on
               the multi-pod mesh the flattened ("pod","data","model") axis
               makes XLA generate the hierarchical ICI/DCI exchange).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def bucket_by_owner(dst, payload, valid, P: int, bucket_cap: int, *,
                    sort_by_dst: bool, partition: str = "hash",
                    capacity: int = 0, presorted: bool = False):
    """Per partition: route messages into P fixed-capacity buckets.

    dst: (K,) global vid; payload: (K, D). sort_by_dst=True is the
    'partitioning merging' connector (buckets arrive dst-sorted).
    partition="range" with presorted=True (input already dst-sorted, e.g.
    from the sender combine) skips the sort entirely — owners are
    contiguous in dst order.
    Returns (b_dst (P,C), b_payload (P,C,D), b_valid (P,C), overflow ()).

    Layout contract (every code path below): valid entries occupy a
    PREFIX of each bucket — positions are per-owner ranks 0..count-1, so
    b_valid[p] is True on [0, count_p) and False after. The out-of-core
    inbox (core/ooc.py) relies on this to trim and end-pad collected
    buckets without disturbing run structure, and the in-memory regrow
    path (driver._regrow_msgs) relies on it to widen runs in place."""
    K = dst.shape[0]
    D = payload.shape[-1]
    if partition == "range":
        owner = jnp.where(valid, jnp.minimum(dst // capacity, P - 1), P)
    else:
        owner = jnp.where(valid, dst % P, P)
    if partition == "range" and presorted:
        # dst ascending among valid rows => owners contiguous: positions
        # are computable WITHOUT any sort (rank among valid minus the
        # owner's first rank, via an O(P) scatter-min)
        vrank = jnp.cumsum(valid) - 1
        big = jnp.iinfo(jnp.int32).max
        owner_start = jnp.full((P + 1,), big, jnp.int32).at[owner].min(
            jnp.where(valid, vrank, big).astype(jnp.int32))
        so, sd, sp, sv = owner, dst, payload, valid
        pos = (vrank - owner_start[owner.clip(0, P)]).astype(jnp.int32)
    else:
        if sort_by_dst or partition == "range":
            # stable two-pass radix: by dst, then owner (no 64-bit keys);
            # for range partitioning dst order already groups owners
            o1 = jnp.argsort(jnp.where(valid, dst,
                                       jnp.iinfo(jnp.int32).max),
                             stable=True)
            order = o1 if partition == "range" else \
                o1[jnp.argsort(owner[o1], stable=True)]
        else:
            order = jnp.argsort(owner, stable=True)
        so = owner[order]
        sd = dst[order]
        sp = payload[order]
        sv = valid[order]
        # position within owner bucket: arange - first index of this owner
        first = jnp.searchsorted(so, jnp.arange(P + 1), side="left")
        pos = jnp.arange(K) - first[so.clip(0, P)]
    keep = sv & (pos < bucket_cap)
    flat = jnp.where(keep, so * bucket_cap + pos, P * bucket_cap)
    b_dst = jnp.full((P * bucket_cap + 1,), -1, jnp.int32)
    b_dst = b_dst.at[flat].set(sd, mode="drop")
    b_pay = jnp.zeros((P * bucket_cap + 1, D), payload.dtype)
    b_pay = b_pay.at[flat].set(sp, mode="drop")
    b_val = jnp.zeros((P * bucket_cap + 1,), bool)
    b_val = b_val.at[flat].set(keep, mode="drop")
    overflow = jnp.sum(sv & (pos >= bucket_cap))
    return (b_dst[:-1].reshape(P, bucket_cap),
            b_pay[:-1].reshape(P, bucket_cap, D),
            b_val[:-1].reshape(P, bucket_cap),
            overflow)


def exchange_emulated(b_dst, b_pay, b_val):
    """Stacked-global transport: (P_src, P_dst, C, ...) -> transpose.
    Receiver p sees P_src runs of C messages."""
    return (b_dst.transpose(1, 0, 2),
            b_pay.transpose(1, 0, 2, 3),
            b_val.transpose(1, 0, 2))


def exchange_shard_map(b_dst, b_pay, b_val, axis_name, *,
                       dst_major: bool = True):
    """shard_map transport: per-shard buckets (P_local, n_parts, C, ...)
    exchanged with all_to_all over `axis_name` (tuple axes = the flattened
    multi-pod mesh; XLA emits the hierarchical ICI/DCI exchange).

    Worker d owns the CONTIGUOUS global partitions [d*P_local,
    (d+1)*P_local) — exactly the tiled all_to_all chunking of the bucket
    axis, so chunk j of axis 1 is worker j's owned range.

    The raw tiled result is worker-major: on worker d,
    ``y[p, j*P_local + q]`` holds source worker j's local partition p
    destined to local partition q. ``dst_major=True`` (default) reorders
    it to the global layout ``out[q, s]`` = the run from global source
    partition s into local destination q — bit-for-bit the
    ``exchange_emulated`` transpose, which is what the in-memory sharded
    driver and the receiver group-by's run contract assume. The OOC
    sharded driver takes ``dst_major=False``: it lands the worker-major
    runs into per-destination inbox pages itself."""
    def a2a(x):
        # the valid flags travel as int32: XLA's TPU all_to_all of a
        # bool array compiles in time that grows with its length (50 s
        # for 4 x 5.45M rows on a v5e, against 1 s as int32)
        flags = x.dtype == jnp.bool_
        y = jax.lax.all_to_all(x.astype(jnp.int32) if flags else x,
                               axis_name, split_axis=1, concat_axis=1,
                               tiled=True)
        y = y.astype(jnp.bool_) if flags else y
        P_local, n_parts = x.shape[0], x.shape[1]
        if not dst_major or P_local == 1:
            return y     # worker-major requested, or reorder is identity
        N = n_parts // P_local
        rest = y.shape[2:]
        y = y.reshape((P_local, N, P_local) + rest)   # (p, j, q, ...)
        y = jnp.swapaxes(y, 0, 2)                     # (q, j, p, ...)
        return y.reshape((P_local, n_parts) + rest)   # run s = j*P_l + p
    return a2a(b_dst), a2a(b_pay), a2a(b_val)
