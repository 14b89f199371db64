"""The generators: fixed slot counts, seed determinism, the deployment's
shape (Graph500 R-MAT)."""
import numpy as np
import pytest

from bench import harness


def _gen(name):
    return harness.load_module(harness.ROOT / "bench" / "gens" /
                               f"{name}.py")


RMAT = {"scale": 9, "edgefactor": 16, "a": 0.57, "b": 0.19, "c": 0.19,
        "partitions": 1}


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 2**40 + 3])
def test_rmat_slot_count_is_fixed_and_sorted(seed):
    g = _gen("rmat").generate(RMAT, seed)
    assert g.n == 512 and g.edge_slots == 2 * 16 * 512
    assert g.src.dtype == np.int32 and g.dst.dtype == np.int32
    assert (np.diff(g.src) >= 0).all()
    assert g.src.min() >= 0 and g.src.max() < g.n
    # both directions: the multiset of (u, v) equals that of (v, u)
    fwd = np.sort(g.src.astype(np.int64) * g.n + g.dst)
    rev = np.sort(g.dst.astype(np.int64) * g.n + g.src)
    assert np.array_equal(fwd, rev)


def test_rmat_is_deterministic_and_seeded():
    gen = _gen("rmat")
    a, b = gen.generate(RMAT, 5), gen.generate(RMAT, 5)
    assert np.array_equal(a.src, b.src) and np.array_equal(a.dst, b.dst)
    c = gen.generate(RMAT, 2**32 + 5)   # differs from 5 past 32 bits
    assert not np.array_equal(a.dst, c.dst)


def test_rmat_is_skewed_and_keeps_self_loops():
    g = _gen("rmat").generate(dict(RMAT, scale=12), 3)
    deg = np.bincount(g.src, minlength=g.n)
    # power law: the largest degree is far above the mean of 32
    assert deg.max() > 20 * deg.mean()
    assert (g.src == g.dst).any()
