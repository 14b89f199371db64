"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs jnp oracle."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.csr_spmv import ops as spmv_ops
from repro.kernels.csr_spmv.ref import edge_gather_ref
from repro.kernels.flash_attention.flash_attention import \
    flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.moe_gmm import ops as gmm_ops
from repro.kernels.moe_gmm.ref import grouped_matmul_ref
from repro.kernels.segment_combine.ref import segment_combine_ref
from repro.kernels.segment_combine.segment_combine import \
    segment_combine_pallas

RNG = np.random.default_rng(7)


@pytest.mark.parametrize("M,D,op", [
    (128, 1, "sum"), (256, 4, "sum"), (512, 8, "min"), (1024, 2, "max"),
    (96, 3, "sum"), (513, 2, "min"),
])
def test_segment_combine(M, D, op):
    seg = np.sort(RNG.integers(0, max(M // 3, 1), M)).astype(np.int32)
    pay = RNG.normal(size=(M, D)).astype(np.float32)
    val = RNG.random(M) > 0.1
    order = np.argsort(~val, kind="stable")
    seg, pay, val = seg[order], pay[order], val[order]
    f1, l1 = segment_combine_ref(
        jnp.asarray(np.where(val, seg, np.iinfo(np.int32).max)),
        jnp.asarray(pay), jnp.asarray(val), op)
    f2, l2 = segment_combine_pallas(jnp.asarray(seg), jnp.asarray(pay),
                                    jnp.asarray(val), op, block_m=128,
                                    interpret=True)
    assert (np.asarray(l1) == np.asarray(l2)).all()
    np.testing.assert_allclose(np.asarray(f1)[np.asarray(l1)],
                               np.asarray(f2)[np.asarray(l2)], atol=1e-5)


@pytest.mark.parametrize("B,Sq,Sk,hd,causal,dtype", [
    (2, 128, 128, 64, True, np.float32),
    (1, 256, 256, 128, True, np.float32),
    (2, 128, 128, 64, False, np.float32),
    (1, 128, 384, 64, True, np.float32),   # decode-suffix layout
    (1, 128, 128, 64, True, jnp.bfloat16),
])
def test_flash_attention(B, Sq, Sk, hd, causal, dtype):
    q = RNG.normal(size=(B, Sq, hd)).astype(np.float32)
    k = RNG.normal(size=(B, Sk, hd)).astype(np.float32)
    v = RNG.normal(size=(B, Sk, hd)).astype(np.float32)
    qj = jnp.asarray(q).astype(dtype)
    kj = jnp.asarray(k).astype(dtype)
    vj = jnp.asarray(v).astype(dtype)
    o1 = attention_ref(qj, kj, vj, causal=causal)
    o2 = flash_attention_pallas(qj, kj, vj, causal=causal, block_q=128,
                                block_k=128, interpret=True)
    atol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), atol=atol)


@pytest.mark.parametrize("T,d,f,E,bm", [
    (300, 64, 128, 4, 64), (1024, 128, 256, 8, 128), (50, 32, 64, 8, 16),
    (17, 16, 32, 3, 8),
])
def test_moe_gmm(T, d, f, E, bm):
    sizes = RNG.multinomial(T, np.ones(E) / E).astype(np.int32)
    x = RNG.normal(size=(T, d)).astype(np.float32)
    w = (RNG.normal(size=(E, d, f)) / np.sqrt(d)).astype(np.float32)
    o1 = grouped_matmul_ref(jnp.asarray(x), jnp.asarray(w),
                            jnp.asarray(sizes))
    o2 = gmm_ops.grouped_matmul(jnp.asarray(x), jnp.asarray(w),
                                jnp.asarray(sizes), impl="pallas",
                                block_m=bm)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-4)


@pytest.mark.parametrize("N,E,V", [(500, 3000, 2), (1000, 8000, 4),
                                   (128, 100, 1), (64, 64, 8)])
def test_csr_spmv(N, E, V):
    src = RNG.integers(0, N, E).astype(np.int32)
    src[RNG.random(E) < 0.05] = -1
    ev = RNG.normal(size=E).astype(np.float32)
    vals = RNG.normal(size=(N, V)).astype(np.float32)
    layout = spmv_ops.plan_layout(src, N, block_m=128, block_r=64)
    o1 = edge_gather_ref(jnp.asarray(vals), jnp.asarray(src),
                         jnp.asarray(ev))
    o2 = spmv_ops.edge_gather(jnp.asarray(vals), jnp.asarray(src),
                              jnp.asarray(ev), layout=layout,
                              impl="pallas", block_m=128, block_r=64)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)


def _edge_block(seed, P, Ep, Np, ordered):
    """(P, Ep) edge sources over (P, Np) rows, awkward for the gather's
    tiles: each partition has a hub row whose edges fill two tiles and
    more, then sparse rows (about one edge each) that spread one tile over
    several row blocks, then -1 pads at its tail (a whole tile of them at
    the end of the last of several partitions; for one partition, the
    last tile holds valid edges, and the items that pad the layout repeat
    it). Partitions of 4,000 edges straddle tiles of 1,024, and P*Ep is
    not a multiple of 1,024."""
    rng = np.random.default_rng(seed)
    src = np.full((P, Ep), -1, np.int32)
    for p in range(P):
        n_pad = 1100 if p == P - 1 > 0 else 37 + 50 * p
        hub = np.full(2100, 3 + p)
        rest = rng.integers(0, Np, Ep - n_pad - len(hub))
        row = np.concatenate([hub, rest])
        src[p, :len(row)] = np.sort(row) if ordered else rng.permutation(row)
    return src


@pytest.mark.parametrize("ordered", [True, False])
@pytest.mark.parametrize("P", [1, 3])
def test_csr_spmv_writes_edge_order(P, ordered):
    """The engine's kernel gather, written straight in edge order,
    equals take_along_axis exactly on valid lanes (inf, -inf and nan
    included) and reads 0.0 on invalid lanes, for a stream sorted by
    source within each partition and for an unsorted one."""
    from repro.kernels import backend as kbackend
    Ep, Np, V = 4000, 3000, 2
    src = _edge_block(P, P, Ep, Np, ordered)
    vals = RNG.normal(size=(P, Np, V)).astype(np.float32)
    for bad in (np.inf, -np.inf, np.nan):
        vals = np.where(RNG.random((P, Np, V)) < 0.05, bad,
                        vals).astype(np.float32)
    vals[:, 3:3 + P, 0] = np.inf                # the hubs
    layout = kbackend.plan_edge_layout(src, Np)
    got = np.asarray(kbackend.edge_gather_values(
        jnp.asarray(vals), jnp.asarray(src), jnp.asarray(layout),
        impl_r="pallas"))
    want = np.take_along_axis(vals, np.maximum(src, 0)[:, :, None], axis=1)
    ok = src >= 0
    np.testing.assert_array_equal(got[ok], want[ok])
    assert (got[~ok] == 0.0).all()


@pytest.mark.parametrize("P", [1, 3])
def test_csr_spmv_layout_of_sorted_stream(P):
    """For edges sorted by source within each partition, the gather
    layout is one word per work item, fewer than tiles + row blocks of
    them, in the shape ``edge_layout_shapes`` gives from (P, Ep, n_rows)
    alone: nothing in it has a per-edge length. Its items list every
    tile in order and, for each, exactly the row blocks its valid edges
    touch. Edges as ``load_graph`` leaves them give the same shape."""
    from repro.core import load_graph
    from repro.graph import rmat_graph
    from repro.kernels import backend as kbackend
    Ep, Np = 4000, 3000
    src = _edge_block(10 + P, P, Ep, Np, True)
    vert = load_graph(rmat_graph(1000, 5000, seed=P), 1000, P=P,
                      value_dims=1)
    for block, n_rows in ((src, Np), (np.asarray(vert.edge_src),
                                      vert.capacity)):
        P_, Ep_ = block.shape
        layout = kbackend.plan_edge_layout(block, n_rows)
        shape = kbackend.edge_layout_shapes(P_, Ep_, n_rows)
        assert (layout.shape, layout.dtype) == (shape.shape, shape.dtype)
        n_tiles = -(-P_ * Ep_ // kbackend.GATHER_BLOCK_M)
        n_blocks = -(-P_ * n_rows // kbackend.GATHER_BLOCK_R)
        assert layout.shape == (n_tiles + n_blocks,)
        items, tiles = kbackend.edge_layout_counts(layout, P_, n_rows)
        assert tiles == n_tiles and items < n_tiles + n_blocks
        item_tile, item_row = spmv_ops.unpack_items(layout, n_blocks)
        flat = np.where(block >= 0, block + np.arange(P_)[:, None] * n_rows,
                        -1).reshape(-1)
        pos = np.flatnonzero(flat >= 0)
        need = set(zip(pos // kbackend.GATHER_BLOCK_M,
                       flat[pos] // kbackend.GATHER_BLOCK_R))
        real = list(zip(item_tile[:items], item_row[:items]))
        assert real == sorted(set(real))
        assert set(real) - need <= {(t, 0) for t in range(n_tiles)}
        assert need <= set(real)
        assert {t for t, _ in real} == set(range(n_tiles))
        assert (item_tile[items:] == item_tile[items - 1]).all()
        assert (item_row[items:] == item_row[items - 1]).all()


def test_csr_spmv_layout_of_unsorted_stream_grows():
    """An unsorted stream needs more work items than a sorted one, and
    its layout keeps them: the shape then grows with the data."""
    from repro.kernels import backend as kbackend
    Ep, Np = 4000, 3000
    src = _edge_block(5, 1, Ep, Np, False)
    layout = kbackend.plan_edge_layout(src, Np)
    cap = kbackend.edge_layout_shapes(1, Ep, Np).shape[0]
    items, _ = kbackend.edge_layout_counts(layout, 1, Np)
    assert layout.shape[0] == items > cap


def test_csr_spmv_padded_layout_gathers_the_same():
    """Blocks of equal shape, one sorted by source and one not (as
    ``run_sharded``'s workers may hold), plan layouts of different
    lengths; each, padded to the longest by repeating its last item,
    stacks with the other and gathers exactly what it gathered
    unpadded."""
    from repro.kernels import backend as kbackend
    Ep, Np, V = 4000, 3000, 2
    blocks = [_edge_block(11, 1, Ep, Np, ordered) for ordered in (True,
                                                                  False)]
    layouts = [kbackend.plan_edge_layout(b, Np) for b in blocks]
    assert len(layouts[0]) != len(layouts[1])
    cap = max(len(x) for x in layouts)
    vals = jnp.asarray(RNG.normal(size=(1, Np, V)).astype(np.float32))
    for src, layout in zip(blocks, layouts):
        padded = kbackend.pad_edge_layout(layout, cap, 1, Np)
        assert padded.shape == (cap,)
        assert kbackend.edge_layout_counts(padded, 1, Np) == \
            kbackend.edge_layout_counts(layout, 1, Np)
        got = [np.asarray(kbackend.edge_gather_values(
            vals, jnp.asarray(src), jnp.asarray(x), impl_r="pallas"))
            for x in (layout, padded)]
        np.testing.assert_array_equal(got[0], got[1])


@pytest.mark.parametrize("case", range(6))
def test_csr_spmv_rows_plan_is_the_flat_streams_plan(case):
    """The row-by-row plan of a (P, Ep) block (``plan_edge_layout``) is
    item for item the plan of the flat stream it stands for, row p's
    sources offset by p * n_rows: for rows sorted ahead of their pads,
    rows that are empty or full, tiles and row blocks that rows straddle,
    and (through the flat planner) a row out of order."""
    rng = np.random.default_rng(case)
    P, Ep, n_rows = [(1, 4000, 3000), (3, 700, 90), (4, 1025, 5000),
                     (2, 64, 9), (5, 300, 700), (3, 500, 40)][case]
    bm, br = [(1024, 256), (16, 8), (1024, 256), (8, 4), (32, 8),
              (16, 4)][case]
    src = np.full((P, Ep), -1, np.int32)
    for p in range(P):
        c = [Ep, 0, int(rng.integers(0, Ep + 1))][p % 3]
        src[p, :c] = np.sort(rng.integers(0, n_rows, c))
    if case == 5:
        rng.shuffle(src[0])
    off = (np.arange(P, dtype=np.int64) * n_rows)[:, None]
    flat = np.where(src >= 0, src + off, -1).reshape(-1)
    want = spmv_ops.plan_layout_fixed(flat, P * n_rows, block_m=bm,
                                      block_r=br)
    assert (spmv_ops._plan_items_rows(src, n_rows, bm, br) is None) == \
        (case == 5)
    got = spmv_ops.plan_rows_layout_fixed(src, n_rows, block_m=bm,
                                          block_r=br)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)
