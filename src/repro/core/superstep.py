"""One Pregel superstep as a single JAX dataflow (paper Figures 3/4/5).

    Msg_i --[receiver group-by + combine]--> combined payloads
    Vertex_i --[join: full-outer dense | left-outer frontier]--> compute in
    compute UDF --> value'/halt'/sends/aggregate/mutations
    sends --[optional sender combine]--[bucket]--[connector]--> Msg_{i+1}
    aggregates --[two-stage reduction]--> GS_{i+1}
    mutations --[bucket + resolve]--> Vertex_{i+1}

The same function runs in two transports: 'emulated' (partitions stacked on
the leading axis, exchange = transpose — single host) and 'shard_map'
(jax.lax.all_to_all over mesh axes — the production multi-pod path).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import connector, groupby
from repro.core.plan import PhysicalPlan
from repro.core.program import ComputeOut, VertexProgram
from repro.core.relations import GlobalState, MsgRel, VertexRel
from repro.kernels import backend as kbackend

# The superstep's stages, each traced under its ``jax.named_scope``: the
# name lands in every HLO instruction's ``op_name``, so a profiler trace
# can time a stage whatever implements it. A fusion takes the scope of
# its root instruction.
STAGES = ("pregel.receive", "pregel.compute", "pregel.edge_gate",
          "pregel.gather", "pregel.sender_combine", "pregel.route",
          "pregel.mutate", "pregel.global")
RECEIVE, COMPUTE, EDGE_GATE, GATHER, SENDER_COMBINE, ROUTE, MUTATE, \
    GLOBAL = STAGES


@dataclass(frozen=True)
class EngineConfig:
    n_parts: int                 # total partitions (= mesh size in prod)
    bucket_cap: int              # per (src,dst)-partition bucket capacity
    mutation_cap: int = 64       # insert-proposal bucket capacity
    frontier_cap: int = 0        # left-outer frontier capacity (0 = Np/2)
    axis_name: Optional[tuple] = None   # shard_map axes, None = emulated
    # out-of-core: return the (P_local, n_parts, C) sender buckets to the
    # host instead of exchanging — the OOC driver performs the exchange as
    # a host-side transpose into its run-structured inbox (core/ooc.py)
    ooc_collect: bool = False
    # sharded driver: keep the MESSAGE leg collected (the superstep's
    # ``new_msg`` carries the pre-exchange (P_local, n_parts, C) buckets)
    # so the driver can run the all_to_all as a SEPARATE jitted stage —
    # timed as an ``exchange`` span that feeds the planner's network
    # axis. Mutations still exchange in-device (core/sharded.py).
    exchange_apart: bool = False


def _combine_fns(program: VertexProgram):
    if program.combine_op == "custom":
        return program.combine, program.combine_identity()
    fn, ident = groupby.MONOIDS[program.combine_op]
    return fn, jnp.full((program.msg_dims,), ident, jnp.float32)


def compact_combined(dst, payload, valid, capc: int):
    """Fused combine -> exchange-pack leg: compact each partition's
    combined survivors (one row per distinct destination, dst still
    ascending) down to the ``capc`` rows the buckets can actually accept,
    so the bucket build never re-materializes (or re-sorts) the full
    (P, Ep, C) edge-payload relation. Order-preserving, so the
    ``presorted`` bucket contract holds on the compacted stream; rows
    beyond capc are counted as bucket overflow (``capc >= n_parts *
    bucket_cap``, so any such row would have overflowed its bucket
    anyway — the drivers' regrow protocol fires identically with or
    without the fusion)."""
    def per_part(d, p, v):
        idx, _, ovf = groupby.compact(v, capc)
        ok = idx >= 0
        take = idx.clip(0)
        return (jnp.where(ok, d[take], -1),
                jnp.where(ok[:, None], p[take], 0.0),
                ok, ovf)
    d2, p2, v2, ovf = jax.vmap(per_part)(dst, payload, valid)
    return d2, p2, v2, jnp.sum(ovf)


def make_superstep(program: VertexProgram, plan: PhysicalPlan,
                   ec: EngineConfig):
    plan.validate(program.combine_op)
    n_parts = ec.n_parts
    comb_fn, comb_ident = _combine_fns(program)

    # ---- hot-path kernel dispatch (kernels/backend.py)
    impl_r = kbackend.resolve(plan.kernel_impl)
    named_comb = program.combine_op != "custom"
    # csr_spmv gather: full_outer only — left_outer compacts the edge
    # stream data-dependently, which the host-planned tiling can't follow
    kernel_gather = impl_r != "ref" and plan.join == "full_outer"
    # fuse combine -> exchange-pack on the kernel path (clean ref/pallas
    # HLO A/B: the ref path keeps the seed's unfused lowering)
    fuse_pack = impl_r != "ref" and plan.sender_combine and named_comb

    # ---- transport-dependent reductions
    if ec.axis_name is None:
        red_sum = lambda x: jnp.sum(x)
        red_all = lambda x: jnp.all(x)
        exchange = connector.exchange_emulated
    else:
        red_sum = lambda x: jax.lax.psum(jnp.sum(x), ec.axis_name)
        red_all = lambda x: jnp.logical_not(
            jax.lax.pmax(jnp.logical_not(jnp.all(x)).astype(jnp.int32),
                         ec.axis_name) > 0)
        exchange = partial(connector.exchange_shard_map,
                           axis_name=ec.axis_name)

    def _slot_of(dst, valid, Np):
        if plan.partition == "range":
            owner = jnp.minimum(dst // Np, n_parts - 1)
            return jnp.where(valid, dst - owner * Np, Np)
        return jnp.where(valid, dst // n_parts, Np)

    def receiver_groupby(msg: MsgRel, Np: int):
        # run-capacity assumption: msg.capacity = n_parts equal-width
        # sender runs. Both the in-memory exchange (fixed C buckets) and
        # the out-of-core inbox (trimmed host runs) deliver this layout.
        slot = _slot_of(msg.dst, msg.valid, Np)

        if plan.connector == "partitioning_merging":
            # buckets arrived dst-sorted per source run: one-pass combine
            C = msg.capacity // n_parts
            f = lambda s, p, v: groupby.run_combine_dense(
                s.reshape(n_parts, C), p.reshape(n_parts, C, -1),
                v.reshape(n_parts, C), Np, program.combine_op
                if program.combine_op != "custom" else "sum")
            if program.combine_op == "custom":
                f = lambda s, p, v: groupby.sort_combine_dense(
                    s, p, v, Np, comb_fn, comb_ident)
        elif plan.groupby == "sort":
            f = lambda s, p, v: groupby.sort_combine_dense(
                s, p, v, Np, comb_fn, comb_ident)
        else:
            f = lambda s, p, v: groupby.scatter_combine_dense(
                s, p, v, Np, program.combine_op)
        return jax.vmap(f)(slot, msg.payload, msg.valid)

    def _part_ids(P_local: int, part0=None):
        if ec.axis_name is None:
            ids = jnp.arange(P_local, dtype=jnp.int32)
            if part0 is not None:
                # out-of-core: the resident block holds GLOBAL partitions
                # part0..part0+P_local-1, not 0..P_local-1
                ids = ids + part0
            return ids[:, None]
        # shard_map: worker w owns the CONTIGUOUS global partitions
        # [w * (n_parts // n_shards), ...) — the tiled all_to_all
        # chunking of the bucket axis (connector.exchange_shard_map).
        # ``part0`` (OOC sharded) offsets into the worker's own block:
        # the resident rows are global partitions w*P_w + part0 + p.
        idx = jnp.zeros((), jnp.int32)
        n_shards = 1
        for a in ec.axis_name:
            sz = jax.lax.axis_size(a)
            idx = idx * sz + jax.lax.axis_index(a)
            n_shards *= sz
        ids = idx * (n_parts // n_shards) + \
            jnp.arange(P_local, dtype=jnp.int32)
        if part0 is not None:
            ids = ids + part0
        return ids[:, None]

    def resurrect(vert: VertexRel, has_msg, part0):
        """Paper Fig. 2 left-outer case: a message to a non-existent vid
        CREATES the vertex (fields NULL). Slot s of partition p holds vid
        s * n_parts + p, so the vid is recoverable from the address."""
        P_local, Np = vert.vid.shape
        make = has_msg & (vert.vid < 0)
        if plan.partition == "range":
            slot_vid = (jnp.arange(Np, dtype=jnp.int32)[None, :] +
                        _part_ids(P_local, part0) * Np)
        else:
            slot_vid = (jnp.arange(Np, dtype=jnp.int32)[None, :] * n_parts +
                        _part_ids(P_local, part0))
        vid = jnp.where(make, slot_vid, vert.vid)
        halt = jnp.where(make, False, vert.halt)
        value = jnp.where(make[..., None], 0.0, vert.value)
        return dataclasses.replace(vert, vid=vid, halt=halt, value=value)

    def run_compute(vert: VertexRel, combined, has_msg, gs):
        P, Np = vert.vid.shape
        active = ((~vert.halt) | has_msg) & (vert.vid >= 0)
        if plan.join == "full_outer":
            out = program.compute(vert.vid, vert.value, combined, has_msg,
                                  active, gs)
            return out, active, None
        # left-outer: compact the frontier and gather (index probe)
        F = ec.frontier_cap or max(Np // 2, 1)
        idx, cnt, ovf = jax.vmap(lambda m: groupby.compact(m, F))(active)
        take = lambda a: jnp.take_along_axis(
            a, idx.clip(0)[..., None] if a.ndim == 3 else idx.clip(0),
            axis=1)
        fvid = jnp.where(idx >= 0, take(vert.vid), -1)
        fval = take(vert.value)
        fcomb = take(combined)
        fhas = take(has_msg) & (idx >= 0)
        factive = idx >= 0
        out = program.compute(fvid, fval, fcomb, fhas, factive, gs)
        return out, active, (idx, factive, ovf)

    def apply_updates(vert: VertexRel, out: ComputeOut, active, frontier):
        P, Np = vert.vid.shape
        if frontier is None:
            upd = active
            value = jnp.where(upd[..., None], out.value, vert.value)
            halt = jnp.where(upd, out.halt, vert.halt | ~active)
            gate = out.send_gate & upd
            agg = (out.aggregate, upd) if out.aggregate is not None else None
            return value, halt, gate, agg
        idx, factive, _ = frontier
        tgt = jnp.where(factive, idx, Np)

        def scat(dst_full, upd_rows, t):
            return dst_full.at[t].set(upd_rows, mode="drop")

        value = jax.vmap(scat)(vert.value, out.value, tgt)
        halt = jax.vmap(scat)(vert.halt, out.halt, tgt)
        gate = jax.vmap(scat)(jnp.zeros_like(vert.halt), out.send_gate, tgt)
        agg = None
        if out.aggregate is not None:
            agg = (out.aggregate, factive)
        return value, halt, gate & active, agg

    def gen_messages(vert: VertexRel, value_new, gate_dense, gs,
                     layout=None):
        """Edge-parallel send (dataflow D3). Under the left-outer plan the
        edge stream is COMPACTED to the frontier's edges first (cheap
        boolean prepass + cumsum), so payload generation, the sender
        combine and the bucket sort all run at O(|frontier edges|) instead
        of O(|E|) — this is where the paper's per-iteration SSSP win
        comes from."""
        P, Np = vert.vid.shape
        Ep = vert.edge_src.shape[1]
        with jax.named_scope(EDGE_GATE):
            esl = vert.edge_src.clip(0)
            egate = jnp.take_along_axis(gate_dense, esl, axis=1) & \
                (vert.edge_src >= 0) & (vert.edge_dst >= 0)
            edge_src, edge_dst, edge_val = (vert.edge_src, vert.edge_dst,
                                            vert.edge_val)
            if plan.join == "left_outer":
                EF = min(max(ec.frontier_cap * 8, 64), Ep)
                eidx, _, ovf_e = jax.vmap(
                    lambda m: groupby.compact(m, EF))(egate)
                take1 = lambda a: jnp.take_along_axis(a, eidx.clip(0),
                                                      axis=1)
                edge_src = jnp.where(eidx >= 0, take1(vert.edge_src), -1)
                edge_dst = jnp.where(eidx >= 0, take1(vert.edge_dst), -1)
                edge_val = take1(vert.edge_val)
                egate = eidx >= 0
                esl = edge_src.clip(0)
                ovf_edges = jnp.sum(ovf_e)
            else:
                ovf_edges = jnp.zeros((), jnp.int32)
        with jax.named_scope(GATHER):
            src_vid = jnp.take_along_axis(vert.vid, esl, axis=1)
            if kernel_gather and layout is not None:
                # row-blocked csr_spmv Pallas kernel: the gather becomes
                # one-hot MXU matmuls over the host-planned tiling.
                # Invalid lanes read 0.0 where the jnp path reads row 0 —
                # both are masked by egate before anything observable.
                src_val = kbackend.edge_gather_values(
                    value_new, edge_src, layout, impl_r=impl_r)
            else:
                # one (P, Ep) gather per value channel: a (P, Ep, V)
                # gather index would pad its narrow minor dimension to a
                # full tile
                src_val = jnp.stack(
                    [jnp.take_along_axis(value_new[..., c], esl, axis=1)
                     for c in range(value_new.shape[-1])], axis=-1)
            payload = program.send(src_vid, src_val, edge_val, edge_dst,
                                   gs)
        return edge_dst, payload, egate, ovf_edges

    def sender_combine(dst, payload, valid):
        if named_comb:
            # segment_combine kernel path: one stable sort of every
            # partition's (key, payload channels) by destination, then one
            # tiled segmented fold over all partitions. BOTH impls run the
            # same tiled reduction order ("ref" = jnp re-execution of the
            # kernel's tile network) so kernel_impl="ref" and ="pallas"
            # are bit-for-bit identical even for float sums.
            big = jnp.iinfo(jnp.int32).max
            key = jnp.where(valid, dst, big)
            ks, *cols = jax.lax.sort(
                (key, *[payload[..., d] for d in range(payload.shape[-1])]),
                dimension=1, num_keys=1, is_stable=True)
            folded, is_last = kbackend.sorted_segment_fold(
                ks, jnp.stack(cols, axis=1), ks != big, program.combine_op,
                impl_r=impl_r)
            return (jnp.where(is_last, ks, -1), jnp.moveaxis(folded, 1, 2),
                    is_last)

        def per_part(d, p, v):
            ks, folded, is_last = groupby.sort_combine(
                jnp.where(v, d, jnp.iinfo(jnp.int32).max), p, v,
                comb_fn, comb_ident)
            return jnp.where(is_last, ks, -1), folded, is_last
        return jax.vmap(per_part)(dst, payload, valid)

    def route(dst, payload, valid, cap, Np, collect=False, presorted=False):
        f = lambda d, p, v: connector.bucket_by_owner(
            d, p, v, n_parts, cap,
            sort_by_dst=(plan.connector == "partitioning_merging"),
            partition=plan.partition, capacity=Np, presorted=presorted)
        b_dst, b_pay, b_val, ovf = jax.vmap(f)(dst, payload, valid)
        if collect:  # out-of-core: hand buckets back to the host
            return b_dst, b_pay, b_val, jnp.sum(ovf)
        r_dst, r_pay, r_val = exchange(b_dst, b_pay, b_val)
        P_local = dst.shape[0]
        flat = lambda a: a.reshape((P_local, -1) + a.shape[3:])
        return flat(r_dst), flat(r_pay), flat(r_val), jnp.sum(ovf)

    def apply_mutations(vert, value, halt, out: ComputeOut, gs):
        """Dataflow D6 (Figure 5): deletions before insertions, conflicts
        via resolve. Out-of-core (``ec.ooc_collect``) the insert
        proposals are BUCKETED BY OWNER over all n_parts partitions and
        handed back to the host instead of being exchanged: the in-device
        exchange only spans the resident super-partition, so a
        cross-super-partition insert must travel through the HOST
        MUTATION INBOX (core/ooc.py applies the buckets — with the same
        scatter/resolve semantics — at the superstep barrier). Deletions
        and own-edge rewrites stay in-device: they are local to the
        owning partition by construction."""
        P, Np = vert.vid.shape
        vid = vert.vid
        if out.delete_self is not None:
            dele = out.delete_self
            vid = jnp.where(dele, -1, vid)
            halt = jnp.where(dele, True, halt)
        ovf = jnp.zeros((), jnp.int32)
        mut_buckets = None
        if out.insert_vid is not None and ec.ooc_collect:
            ins_dst = out.insert_vid.reshape(P, -1)
            ins_val = out.insert_value.reshape(P, Np, -1)
            mb_dst, mb_val, mb_ok, ovf = route(
                ins_dst, ins_val, ins_dst >= 0, ec.mutation_cap, Np,
                collect=True)
            mut_buckets = (mb_dst, mb_val, mb_ok)
        elif out.insert_vid is not None:
            ins_dst = out.insert_vid.reshape(P, -1)
            ins_val = out.insert_value.reshape(P, Np, -1)
            r_dst, r_val, r_valid, ovf = route(
                ins_dst, ins_val, ins_dst >= 0, ec.mutation_cap, Np)

            def per_part(vidp, valp, haltp, d, pv, v):
                slot = _slot_of(d, v, Np)
                summed = jnp.zeros((Np + 1, pv.shape[-1]), jnp.float32) \
                    .at[slot].add(jnp.where(v[:, None], pv, 0.0))
                cnt = jnp.zeros((Np + 1,), jnp.int32).at[slot].add(v)
                newvid = jnp.full((Np + 1,), -1, jnp.int32) \
                    .at[slot].max(jnp.where(v, d, -1))
                resolved = program.resolve(newvid[:Np], summed[:Np],
                                           cnt[:Np])
                take = cnt[:Np] > 0
                vidp = jnp.where(take, newvid[:Np], vidp)
                valp = jnp.where(take[:, None], resolved, valp)
                haltp = jnp.where(take, False, haltp)
                return vidp, valp, haltp

            vid, value, halt = jax.vmap(per_part)(
                vid, value, halt, r_dst, r_val, r_valid)
        edge_dst, edge_val = vert.edge_dst, vert.edge_val
        if out.new_edge_dst is not None:
            edge_dst = jnp.where(out.new_edge_dst >= -1, out.new_edge_dst,
                                 edge_dst)
        if out.new_edge_val is not None:
            edge_val = jnp.where(jnp.isnan(out.new_edge_val), edge_val,
                                 out.new_edge_val)
        return vid, value, halt, edge_dst, edge_val, ovf, mut_buckets

    def superstep(vert: VertexRel, msg: MsgRel, gs: GlobalState,
                  part0=None, layout=None):
        """``part0`` (out-of-core only): global index of the resident
        block's first partition, so resurrect derives correct vids for
        super-partitions past the first. ``layout`` (kernel path only):
        host-planned gather tiling from ``kbackend.plan_edge_layout`` —
        fixed-shape per graph shape, so the OOC driver threads
        per-super-partition layouts through one shared jitted step. Both
        traced — no re-tracing across super-partitions."""
        P, Np = vert.vid.shape
        # 1-2. receiver group-by + join + select (D1)
        with jax.named_scope(RECEIVE):
            combined, has_msg = receiver_groupby(msg, Np)
            if getattr(program, "mutates", False):
                vert = resurrect(vert, has_msg, part0)
        with jax.named_scope(COMPUTE):
            out, active, frontier = run_compute(vert, combined, has_msg, gs)
            # 3. vertex updates (D2)
            value, halt, gate, agg = apply_updates(vert, out, active,
                                                   frontier)
        # 4. message generation + sender combine + exchange (D3/D7)
        dst, payload, valid, ovf_edges = gen_messages(vert, value, gate, gs,
                                                      layout)
        presorted = False
        ovf_pack = jnp.zeros((), jnp.int32)
        if plan.sender_combine:
            with jax.named_scope(SENDER_COMBINE):
                dst, payload, valid = sender_combine(dst, payload, valid)
                presorted = True  # sort_combine leaves dst ascending
                capc = n_parts * ec.bucket_cap
                if fuse_pack and capc < dst.shape[1]:
                    dst, payload, valid, ovf_pack = compact_combined(
                        dst, payload, valid, capc)
        collect_msgs = ec.ooc_collect or ec.exchange_apart
        with jax.named_scope(ROUTE):
            r_dst, r_pay, r_val, ovf = route(
                dst, payload, valid, ec.bucket_cap, Np,
                collect=collect_msgs, presorted=presorted)
        # 5. mutations (D6)
        m_ovf = jnp.zeros((), jnp.int32)
        mut_buckets = None
        vid, edge_dst, edge_val = vert.vid, vert.edge_dst, vert.edge_val
        if (out.insert_vid is not None or out.delete_self is not None
                or out.new_edge_dst is not None
                or out.new_edge_val is not None):
            with jax.named_scope(MUTATE):
                (vid, value, halt, edge_dst, edge_val, m_ovf,
                 mut_buckets) = apply_mutations(vert, value, halt, out, gs)
        with jax.named_scope(GLOBAL):
            ovf_f = frontier[2].sum() if frontier is not None else 0
            # 6. global state (D4/D5/D8/D9). Overflow is counted PER SOURCE
            # (bucket / frontier / mutation / edge) so the drivers' regrow
            # paths double only the capacity that actually overflowed.
            msg_count = red_sum(r_val).astype(jnp.int32)
            # (order = relations.OVF_BUCKET/FRONTIER/MUTATION/EDGE)
            zero = jnp.zeros((), jnp.int32)
            overflow = jnp.stack([
                red_sum(ovf).astype(jnp.int32) +
                red_sum(ovf_pack).astype(jnp.int32),
                (red_sum(ovf_f).astype(jnp.int32) if frontier is not None
                 else zero),
                red_sum(m_ovf).astype(jnp.int32),
                red_sum(ovf_edges).astype(jnp.int32)])
            active_count = red_sum(active).astype(jnp.int32)
            if agg is not None:
                contrib, mask = agg
                local = jnp.where(mask[..., None], contrib, 0.0) \
                    .reshape(-1, program.agg_dims).sum(0)
                agg_val = (jax.lax.psum(local, ec.axis_name)
                           if ec.axis_name is not None else local)
            else:
                agg_val = gs.aggregate
            halt_all = red_all(halt | (vid < 0))
            g_halt = halt_all & (msg_count == 0)
            new_vert = VertexRel(vid=vid, halt=halt, value=value,
                                 edge_src=vert.edge_src, edge_dst=edge_dst,
                                 edge_val=edge_val)
            # under ooc_collect / exchange_apart new_msg carries the
            # PRE-EXCHANGE (P_local, n_parts, C) buckets — same pytree, one
            # extra axis; the driver runs the exchange itself
            new_msg = MsgRel(dst=r_dst, payload=r_pay, valid=r_val)
            new_gs = GlobalState(
                halt=g_halt | program.is_converged(gs),
                aggregate=jnp.asarray(agg_val, jnp.float32).reshape(
                    gs.aggregate.shape),
                superstep=gs.superstep + 1,
                overflow=gs.overflow + overflow,
                active_count=active_count,
                msg_count=msg_count)
            if ec.ooc_collect:
                # extra outputs for the OOC collector: per-(src, dst) bucket
                # occupancy counts (computed on-device so the host never has
                # to scan the bucket tensors for the inbox run-width trim /
                # readiness bookkeeping of the barrier-free pipeline), and
                # the collected insert-proposal buckets (sp, P, Cm) for the
                # host mutation inbox — None when the program never proposes
                # inserts (the pytree stays static per program)
                counts = jnp.sum(r_val, axis=2, dtype=jnp.int32)
                return new_vert, new_msg, new_gs, counts, mut_buckets
            return new_vert, new_msg, new_gs

    return superstep


def jit_superstep(program: VertexProgram, plan: PhysicalPlan,
                  ec: EngineConfig, *, donate_vertex: bool = False):
    """jit the superstep, optionally DONATING the vertex-relation input
    buffers to their updated outputs (the shapes match field-for-field).
    The OOC streaming executor keeps several super-partitions in flight
    at once; donation lets XLA reuse each uploaded vertex block for its
    result instead of doubling the resident footprint per pipeline slot.
    The message and global-state arguments are never donated: the
    streaming dispatcher shares one GlobalState across every in-flight
    super-partition, and the collected bucket outputs do not alias the
    inbox-slice shapes.

    The returned callable participates in ``repro.obs`` tracing: each
    invocation is a ``compute``-category span (and, while a JAX profiler
    session records, a ``jax.profiler.TraceAnnotation`` — the bridge that
    lines host spans up with device activity under the JAX profiler).
    With tracing off the wrapper is one extra Python call around the
    jitted function."""
    from repro.obs import trace

    fn = make_superstep(program, plan, ec)
    jf = (jax.jit(fn, donate_argnums=(0,)) if donate_vertex
          else jax.jit(fn))

    def traced(*args):
        with trace.annotate("superstep", "compute"):
            return jf(*args)

    return traced
