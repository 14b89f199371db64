"""Kernel backend selection + the engine-facing kernel entry points.

``resolve`` maps the ``PhysicalPlan.kernel_impl`` knob (auto | ref |
pallas | pallas_tpu) to a concrete implementation, honouring the
``REPRO_KERNEL_IMPL`` env override so CI can force a path without code
changes. The rest of this module is the thin layer the superstep engine
calls: a fixed-shape gather layout planner, the partition-flattened edge
gather, and the blocked segmented fold — each shaped so that
``kernel_impl="ref"`` and ``"pallas"`` are bit-for-bit identical.
"""
from __future__ import annotations

import os
import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

VALID_IMPLS = ("auto", "ref", "pallas", "pallas_tpu")
ENV_VAR = "REPRO_KERNEL_IMPL"

# Engine block sizes, shared with the planner's cost model. The gather
# tile is GATHER_BLOCK_M edge slots (8 sublanes x 128 lanes) over a row
# block of GATHER_BLOCK_R value rows (the one-hot matmul contraction
# width). The combine tile is up to COMBINE_BLOCK_ROWS x 128 stream rows.
GATHER_BLOCK_M = 1024
GATHER_BLOCK_R = 256
COMBINE_BLOCK_ROWS = 256


def combine_block(M: int) -> int:
    """Combine tile size (stream rows) for a stream of M rows: whole
    (8, 128) vreg rows, at most COMBINE_BLOCK_ROWS of them."""
    rows = -(-max(M, 1) // 128)
    return 128 * min(COMBINE_BLOCK_ROWS, -(-rows // 8) * 8)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve(impl: str, *, tpu: Optional[bool] = None) -> str:
    """Map a kernel_impl knob to a concrete impl in {ref, pallas,
    pallas_tpu}.

    - ``auto``: pallas_tpu on TPU, ref elsewhere (interpret mode is an
      emulator, not a fast path — see the cost model's INTERPRET_PENALTY).
    - ``pallas``: compiled on TPU, interpret mode elsewhere.
    - ``pallas_tpu``: forced TPU lowering (fails off-TPU; debugging knob).
    - ``tpu``: overrides backend detection — the planner resolves per
      MACHINE MODEL (``MachineModel.mxu``), not per host process.
    - ``$REPRO_KERNEL_IMPL`` overrides ``impl`` itself, including "auto".
    """
    env = os.environ.get(ENV_VAR)
    if env:
        if env not in VALID_IMPLS:
            raise ValueError(
                f"{ENV_VAR}={env!r}: expected one of {VALID_IMPLS}")
        if env != impl:
            warnings.warn(f"{ENV_VAR}={env} replaces kernel_impl={impl!r}",
                          stacklevel=2)
        impl = env
    if impl not in VALID_IMPLS:
        raise ValueError(
            f"kernel_impl={impl!r}: expected one of {VALID_IMPLS}")
    if tpu is None:
        tpu = on_tpu()
    if impl == "auto":
        return "pallas_tpu" if tpu else "ref"
    if impl == "pallas" and tpu:
        return "pallas_tpu"
    return impl


def wants_edge_layout(plan) -> bool:
    """True when the resolved kernel path consumes a gather layout.
    full_outer only: left_outer compacts the edge stream data-dependently
    each superstep, which the host-planned fixed tiling cannot express —
    there the gather stays on the jnp path (the segmented fold and the
    fused pack still kick in)."""
    return resolve(plan.kernel_impl) != "ref" and plan.join == "full_outer"


def plan_edge_layout(edge_src, n_rows: int) -> np.ndarray:
    """Host-side gather layout for a (P, Ep) edge_src block over (P, n_rows)
    value rows: the csr_spmv kernel's work items, one int32 word each.
    Partitions are flattened into ONE (P*Ep,) edge stream over P*n_rows
    rows (global row = p * n_rows + source) — ``pallas_call`` must not be
    vmapped (the batching rule would regrid the kernel and break its
    sequential-carry assumption), so a single kernel invocation serves
    the whole block. Uses ``plan_layout_fixed``: for edges sorted
    by source within each partition, as ``load_graph`` leaves them, the
    result shape depends only on the block's shape, so every equal-shape
    super-partition yields an equal-shape layout and the OOC driver can
    pass per-super-partition layouts through one shared jitted superstep
    as traced arguments."""
    from repro.kernels.csr_spmv.ops import plan_layout_fixed
    edge_src = np.asarray(edge_src)
    P, Ep = edge_src.shape
    off = (np.arange(P, dtype=np.int64) * n_rows)[:, None]
    flat = np.where(edge_src >= 0, edge_src + off, -1).reshape(-1)
    return plan_layout_fixed(flat, P * n_rows, block_m=GATHER_BLOCK_M,
                             block_r=GATHER_BLOCK_R)


def edge_layout_shapes(P: int, Ep: int, n_rows: int):
    """Shape of ``plan_edge_layout``'s result for a (P, Ep) edge block
    over (P, n_rows) value rows, sorted by source within each partition,
    without planning it."""
    from repro.kernels.csr_spmv.ops import item_capacity
    cap = item_capacity(P * Ep, P * n_rows, block_m=GATHER_BLOCK_M,
                        block_r=GATHER_BLOCK_R)
    return jax.ShapeDtypeStruct((cap,), jnp.int32)


def edge_layout_counts(layout, P: int, n_rows: int) -> Tuple[int, int]:
    """(real work items, tiles) of ``plan_edge_layout``'s layout for a
    (P, Ep) edge block over (P, n_rows) value rows."""
    from repro.kernels.csr_spmv.ops import layout_counts
    return layout_counts(layout, P * n_rows, block_r=GATHER_BLOCK_R)


def edge_gather_values(values, edge_src, layout, *, impl_r: str):
    """Gather ``values[p, edge_src[p, e]]`` per edge via the csr_spmv
    one-hot matmul kernel, which writes in edge order. values: (P, Np, V);
    edge_src: (P, Ep), -1 = invalid; layout from ``plan_edge_layout``.
    Returns (P, Ep, V); invalid lanes read 0.0 (masked downstream by the
    edge gate, exactly like the clip-gather's arbitrary row-0 reads on the
    jnp path). The kernel reads edge_src itself: as it is for one
    partition, with each partition's row offset added for several.

    Bit-for-bit discipline: a finite value survives the one-hot matmul
    exactly (one 1.0*x product plus exact 0.0 additions; -0.0 may
    normalize to +0.0, which still compares equal). Non-finite values
    would be destroyed by the 0*x products (0*inf = nan), so they ride
    one extra "class" channel (2 bits per value channel: 0 finite, 1 +inf,
    2 -inf, 3 nan) and are re-materialized after the gather. The table
    and the result stay channel-major, so no array carries a narrow
    minor dimension."""
    from repro.kernels.csr_spmv.ops import gather_channels
    P, Np, V = values.shape
    Ep = edge_src.shape[1]
    if P > 1:
        off = jnp.arange(P, dtype=jnp.int32)[:, None] * Np
        edge_src = jnp.where(edge_src >= 0, edge_src + off, -1)
    chans = [values[..., c].reshape(-1) for c in range(V)]
    cls = sum(jnp.where(jnp.isfinite(x), 0.0,
                        jnp.where(jnp.isnan(x), 3.0,
                                  jnp.where(x > 0, 1.0, 2.0))) * 4.0 ** c
              for c, x in enumerate(chans))
    table = jnp.stack([jnp.where(jnp.isfinite(x), x, 0.0) for x in chans]
                      + [cls])
    g = gather_channels(table, edge_src.reshape(-1), layout,
                        interpret=(impl_r != "pallas_tpu"),
                        block_m=GATHER_BLOCK_M, block_r=GATHER_BLOCK_R)
    code = g[V].astype(jnp.int32)
    out = []
    for c in range(V):
        k = (code >> (2 * c)) & 3
        out.append(jnp.where(k == 1, jnp.inf,
                             jnp.where(k == 2, -jnp.inf,
                                       jnp.where(k == 3, jnp.nan, g[c])))
                   .reshape(P, Ep))
    return jnp.stack(out, axis=-1)


def sorted_segment_fold(keys, payload, valid, op: str, *, impl_r: str):
    """Inclusive segmented fold over each partition's key-sorted stream —
    the engine's sender-combine reduction. keys: (P, M), ascending per
    partition; payload: (P, D, M) channel-major; valid: (P, M). Returns
    (folded (P, D, M), is_last (P, M) — already masked by valid).

    Both impls execute the SAME tiled reduction order: "ref" through the
    jnp re-execution ``fold_lane_dense_ref``, "pallas" through the Pallas
    kernel (interpret mode off-TPU). M is padded to a tile multiple here
    so the kernel never sees a ragged tile — one code path, bit-for-bit
    parity for float sums included. One call folds every partition: the
    kernel's carry resets at each partition's first tile."""
    from repro.kernels.segment_combine.ref import fold_lane_dense_ref
    from repro.kernels.segment_combine.segment_combine import (
        IDENT, fold_lane_dense)
    P, D, M = payload.shape
    bm = combine_block(M)
    pad = (-M) % bm
    big = jnp.iinfo(jnp.int32).max
    key = jnp.where(valid, keys, big)
    kp = jnp.pad(key, ((0, 0), (0, pad)), constant_values=big)
    pp = jnp.pad(jnp.where(valid[:, None], payload,
                           IDENT[op]).astype(jnp.float32),
                 ((0, 0), (0, 0), (0, pad)), constant_values=IDENT[op])
    if impl_r == "ref":
        folded = fold_lane_dense_ref(kp, pp, op, block_m=bm)
    else:
        folded = fold_lane_dense(kp, pp, op, block_m=bm,
                                 interpret=(impl_r != "pallas_tpu"))
    is_last = jnp.concatenate(
        [key[:, 1:] != key[:, :-1], jnp.ones((P, 1), bool)], axis=1) & valid
    return folded[:, :, :M], is_last
