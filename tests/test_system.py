"""End-to-end behaviour tests: Pregelix algorithms vs exact oracles."""
import heapq

import numpy as np
import pytest

from repro.core import (PhysicalPlan, gather_values, load_graph, run_host,
                        run_jit)
from repro.graph import (BFS, SSSP, ConnectedComponents, PageRank,
                         Reachability, rmat_graph, uniform_graph)

N = 300


def _edges():
    return rmat_graph(N, 1800, seed=11)


def _dijkstra(edges, n, src):
    adj = {}
    for s, d in edges:
        adj.setdefault(int(s), []).append(int(d))
    dist = [float("inf")] * n
    dist[src] = 0
    h = [(0.0, src)]
    while h:
        dd, u = heapq.heappop(h)
        if dd > dist[u]:
            continue
        for v in adj.get(u, []):
            if dd + 1 < dist[v]:
                dist[v] = dd + 1
                heapq.heappush(h, (dd + 1, v))
    return np.array(dist)


def _union_find_cc(edges, n):
    parent = list(range(n))

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for s, d in edges:
        a, b = find(int(s)), find(int(d))
        if a != b:
            parent[a] = b
    return np.array([find(i) for i in range(n)])


def test_sssp_matches_dijkstra():
    edges = _edges()
    oracle = _dijkstra(edges, N, 5)
    vert = load_graph(edges, N, P=4, value_dims=1)
    res = run_host(vert, SSSP(source=5), SSSP(5).suggested_plan,
                   max_supersteps=40)
    d = gather_values(res.vertex, N)[:, 0]
    d = np.where(d > 1e37, np.inf, d)
    assert np.allclose(np.nan_to_num(oracle, posinf=1e9),
                       np.nan_to_num(d, posinf=1e9))


def test_cc_matches_union_find():
    edges = uniform_graph(200, 400, seed=12, undirected=True)
    oracle = _union_find_cc(edges, 200)
    vert = load_graph(edges, 200, P=4, value_dims=1)
    cc = ConnectedComponents()
    res = run_host(vert, cc, cc.suggested_plan, max_supersteps=60)
    labels = gather_values(res.vertex, 200)[:, 0].astype(int)
    # same partition <=> same label
    for comp in set(oracle):
        members = np.where(oracle == comp)[0]
        assert len(set(labels[members])) == 1
    assert len(set(labels)) == len(set(oracle))


def test_pagerank_mass_and_convergence():
    edges = _edges()
    vert = load_graph(edges, N, P=4, value_dims=2)
    pr = PageRank(N, iterations=10)
    res = run_jit(vert, pr, pr.suggested_plan, max_supersteps=15)
    ranks = gather_values(res.vertex, N)[:, 0]
    assert (ranks >= 0).all()
    # total mass bounded by 1 (dangling leakage only reduces it)
    assert 0.1 < ranks.sum() <= 1.0 + 1e-4
    assert res.supersteps == 10


def test_pagerank_against_numpy_power_iteration():
    edges = _edges()
    n = N
    A = np.zeros((n, n), np.float64)
    for s, d in edges:
        A[int(d), int(s)] += 1.0
    deg = np.maximum(A.sum(axis=0), 1.0)
    M = A / deg
    r = np.full(n, 1.0 / n)
    for _ in range(9):
        r = 0.15 / n + 0.85 * (M @ r)
    vert = load_graph(edges, n, P=2, value_dims=2)
    pr = PageRank(n, iterations=10)
    res = run_jit(vert, pr, pr.suggested_plan, max_supersteps=12)
    ranks = gather_values(res.vertex, n)[:, 0]
    has_out = np.asarray(deg > 1.0) | (A.sum(axis=0) > 0)
    assert np.allclose(ranks, r, atol=5e-5)


def test_bfs_and_reachability_agree():
    edges = _edges()
    vert = load_graph(edges, N, P=4, value_dims=1)
    res_b = run_host(vert, BFS(source=3), BFS(3).suggested_plan,
                     max_supersteps=40)
    lv = gather_values(res_b.vertex, N)[:, 0]
    vert2 = load_graph(edges, N, P=4, value_dims=1)
    rc = Reachability(source=3)
    res_r = run_host(vert2, rc, rc.suggested_plan, max_supersteps=40)
    reach = gather_values(res_r.vertex, N)[:, 0] > 0
    assert ((lv < 1e37) == reach).all()


def test_jit_and_host_drivers_agree():
    edges = _edges()
    vert = load_graph(edges, N, P=2, value_dims=1)
    r1 = run_jit(vert, SSSP(source=0), PhysicalPlan(), max_supersteps=30)
    vert2 = load_graph(edges, N, P=2, value_dims=1)
    r2 = run_host(vert2, SSSP(source=0), PhysicalPlan(), max_supersteps=30)
    assert np.allclose(gather_values(r1.vertex, N),
                       gather_values(r2.vertex, N))


def test_weighted_sssp_matches_dijkstra():
    """Weighted edges exercise edge_val through send (paper Fig 9 uses
    weighted SSSP)."""
    rng = np.random.default_rng(17)
    edges = _edges()
    w = rng.uniform(0.5, 3.0, len(edges)).astype(np.float32)
    adj = {}
    for (s, d), wt in zip(edges, w):
        adj.setdefault(int(s), []).append((int(d), float(wt)))
    dist = [float("inf")] * N
    dist[4] = 0.0
    h = [(0.0, 4)]
    while h:
        dd, u = heapq.heappop(h)
        if dd > dist[u]:
            continue
        for v, wt in adj.get(u, []):
            if dd + wt < dist[v]:
                dist[v] = dd + wt
                heapq.heappush(h, (dd + wt, v))
    from repro.core import load_graph as lg
    vert = lg(edges, N, P=4, value_dims=1, edge_values=w)
    res = run_host(vert, SSSP(source=4), SSSP(4).suggested_plan,
                   max_supersteps=60)
    d = gather_values(res.vertex, N)[:, 0]
    d = np.where(d > 1e37, np.inf, d)
    assert np.allclose(np.nan_to_num(np.array(dist), posinf=1e9),
                       np.nan_to_num(d, posinf=1e9), atol=1e-4)


def test_kcore_matches_peeling_oracle():
    from repro.graph import uniform_graph
    from repro.graph.algorithms import KCore
    n, k = 150, 3
    edges = uniform_graph(n, 420, seed=23, undirected=True)
    # numpy peeling oracle
    deg = np.bincount(edges[:, 0], minlength=n).astype(float)
    alive = np.ones(n, bool)
    changed = True
    adj = {}
    for s, d in edges:
        adj.setdefault(int(s), []).append(int(d))
    while changed:
        changed = False
        for v in range(n):
            if alive[v] and sum(alive[u] for u in adj.get(v, [])) < k:
                alive[v] = False
                changed = True
    vert = load_graph(edges, n, P=4, value_dims=2)
    prog = KCore(k)
    res = run_host(vert, prog, prog.suggested_plan, max_supersteps=60)
    got = gather_values(res.vertex, n)[:, 1] > 0
    assert (got == alive).all()


def test_importing_the_cli_leaves_xla_flags_alone(monkeypatch):
    """The launcher sets the fake-device count in main(), never on import
    (a --dryrun argv once rewrote XLA_FLAGS for any importer)."""
    import importlib
    import os
    import sys
    monkeypatch.setattr(sys, "argv", ["pregel_run", "--dryrun"])
    before = os.environ.get("XLA_FLAGS")
    import repro.launch.pregel_run as cli
    importlib.reload(cli)
    assert os.environ.get("XLA_FLAGS") == before


def test_compile_cache_dir_comes_from_outside_or_the_checkout(
        monkeypatch, tmp_path):
    import jax
    from pathlib import Path
    from repro.launch import compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        # set from outside: JAX reads the variable itself, the code names
        # no other directory
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.setup_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was
        # unset: one fixed directory at the root of the checkout
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        root = Path(compile_cache.__file__).resolve().parents[3]
        want = str(root / ".jax_cache")
        assert compile_cache.setup_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert (root / "src" / "repro").is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
