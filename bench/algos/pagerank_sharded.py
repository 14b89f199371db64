"""PageRank on several chips through ``load_graph(mesh=...)`` ->
``run_sharded``, one job that the window cuts at a superstep boundary.

The graph's partitions shard one per chip over a mesh of the first
``graph.partitions`` devices: ``load_graph`` places each chip's
partition from the host on that chip alone, and ``run_sharded`` runs the
superstep under ``shard_map`` with the message exchange as its own
all_to_all program. Set-up ends after the engine's first superstep; the
window then runs consecutive supersteps and closes at the first boundary
at or after its length, where the callback's ``True`` ends the job. One
unit of work is one superstep over every edge slot. The comparison is
``pagerank.py``'s; the reference is ``pagerank.py``'s iteration with its
roundings, as sparse products in threads (a test holds the two within
float64 rounding). The job's float64 reference iterates on a host thread
beside the chips, one update behind the engine's supersteps, so the
reference is ready when the window closes.
"""
from __future__ import annotations

import inspect
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

from bench.chips import log_memory
from bench.common import Graph, Outcome, exact, log
from bench.harness import Refused, load_module

_PAGERANK = load_module(Path(__file__).with_name("pagerank.py"))
compare = _PAGERANK.compare
_THREADS = min(8, os.cpu_count() or 1)
# each partition's edge slots, as a share of the mean: the largest hash
# partition of an R-MAT draw lies up to 2.4% over the mean at scale 22
# (12 seeds) and closer at larger scales, so every seed loads in one
# shape and a run finds the programs an earlier seed compiled
_EDGE_SLACK = 1.03
_AHEAD = {}    # id(graph) -> the _Ahead iterating its reference

try:
    from repro.core import load_graph as _load_graph
except ImportError:  # the harness refuses a checkout without the program
    _load_graph = None
if (_load_graph is not None
        and "mesh" not in inspect.signature(_load_graph).parameters):
    # before the graph is generated: without placement every chip's
    # share would land on one device
    raise Refused("load_graph cannot place partitions on a mesh")


def drive(graph: Graph, traffic: dict, window) -> Outcome:
    import jax

    from repro.core import gather_values, load_graph
    from repro.core.relations import vertex_rel_shapes
    from repro.core.sharded import compile_ahead, run_sharded
    from repro.graph import PageRank

    workers = graph.partitions
    devices = jax.devices()[:workers]
    if len(devices) < workers:
        raise RuntimeError(f"{workers} partitions, one a chip; JAX sees "
                           f"{len(devices)} device(s)")
    mesh = jax.make_mesh((workers,), ("workers",), devices=devices)
    ahead = _AHEAD[id(graph)] = _Ahead(graph, traffic["damping"])
    program = PageRank(graph.n, damping=traffic["damping"],
                       iterations=traffic["iterations"])
    plan, slots = program.suggested_plan, edge_capacity(graph, workers)
    # the first superstep compiles while the graph loads
    compiled = compile_ahead(
        vertex_rel_shapes(graph.n, workers, value_dims=program.value_dims,
                          edge_slots=slots, mesh=mesh),
        program, plan, mesh=mesh, kernel_impl="auto")
    t = time.perf_counter()
    vert = load_graph(graph.edge_list(), graph.n, P=workers,
                      value_dims=program.value_dims, mesh=mesh,
                      edge_capacity=slots)
    largest = int(jax.numpy.max(jax.numpy.sum(vert.edge_src >= 0, axis=1)))
    log(f"load_graph in {time.perf_counter() - t:.3f} s: "
        f"{vert.edge_src.shape[1]} edge slots a partition, the largest "
        f"holding {largest} ({largest * workers / graph.edge_slots - 1:+.4%}"
        f" on the mean)")
    log_memory("after placement", devices)
    last = {}

    def on_superstep(i, rec):
        log(f"superstep {i}: {rec['wall_s']:.3f} s, exchange "
            f"{rec['exchange_stall_s']:.3f} s")
        if i == 1:
            window.open()
        elif window.expired():
            window.close()
            last["superstep"] = i
            ahead.allow(i - 1)
            return True
        ahead.allow(i)
        return False

    try:
        res = run_sharded(vert, program, plan, mesh=mesh,
                          max_supersteps=traffic["iterations"],
                          kernel_impl="auto", on_superstep=on_superstep,
                          ahead=compiled)
    except BaseException:
        ahead.allow(-1)
        _AHEAD.pop(id(graph), None)
        raise
    if "superstep" not in last:
        raise RuntimeError("PageRank halted before the window closed: "
                           "raise the traffic's iterations")
    log_memory("after the window", devices)
    del vert
    values = gather_values(res.vertex, graph.n)[:, 0]
    steps = last["superstep"] - 1
    return Outcome(work=steps * graph.edge_slots, steps=steps,
                   answers=[values], supersteps=last["superstep"],
                   value_channels=program.value_dims)


def edge_capacity(graph: Graph, partitions: int) -> int:
    """Edge slots a partition, from the graph's size alone: ``_EDGE_SLACK``
    times the mean, in whole 1,024-slot tiles."""
    mean = graph.edge_slots / partitions
    return -(-int(mean * _EDGE_SLACK) // 1024) * 1024


def reference(graph: Graph, traffic: dict, outcome: Outcome, rnd):
    """``pagerank.reference``'s ranks after ``outcome.supersteps - 1``
    updates, from ``_iterates``: the ones the job's thread computed
    where ``drive`` ran on this graph and ``rnd`` is ``exact``."""
    ahead = _AHEAD.pop(id(graph), None)
    k = outcome.supersteps - 1
    if ahead is not None and ahead.graph is graph and rnd is exact \
            and ahead.damping == traffic["damping"]:
        r = ahead.ranks(k)
        if r is not None:
            return r
    src = graph.src
    if not np.all(src[1:] >= src[:-1]):
        return _PAGERANK.reference(graph, traffic, outcome, rnd)
    for j, r in enumerate(_iterates(graph, traffic["damping"], rnd)):
        if j == k:
            return r


def _iterates(graph: Graph, damping: float, rnd):
    """``pagerank.reference``'s iteration, with its roundings, as sparse
    products in threads; yields the ranks after 0, 1, 2, ... updates. The
    slots, sorted by source, are a column-compressed (CSC) matrix with a
    column a source, so column block k scatters its sources' terms
    rnd(r[u] * rnd(1 / outdeg(u))) into its destinations in slot order,
    as ``np.bincount`` does, without a per-slot copy of the terms (SciPy
    releases the GIL); the blocks' partial sums add up in block order.
    Only the order of the float64 additions differs from
    ``pagerank.reference``."""
    import scipy.sparse

    src, dst = graph.src, graph.dst
    n, d = graph.n, damping
    deg = np.bincount(src, minlength=n)
    w = rnd(1.0 / np.maximum(deg, 1))
    start = np.zeros(n + 1, np.int64)
    np.cumsum(deg, out=start[1:])
    cuts = np.linspace(0, n, _THREADS + 1).astype(np.int64)
    blocks = []
    for lo, hi in zip(cuts[:-1], cuts[1:]):
        a, b = start[lo], start[hi]
        ptr = (start[lo:hi + 1] - a).astype(dst.dtype)
        blocks.append(scipy.sparse.csc_matrix(
            (np.ones(b - a), dst[a:b], ptr), shape=(n, hi - lo), copy=False))

    r = rnd(np.full(n, 1.0 / n))
    yield r
    with ThreadPoolExecutor(_THREADS) as pool:
        while True:
            x = rnd(r * w)
            acc = np.zeros(n)
            for part in pool.map(lambda k: blocks[k] @ x[cuts[k]:cuts[k + 1]],
                                 range(_THREADS)):
                acc += part
            r = rnd((1 - d) / n + d * rnd(acc))
            yield r


class _Ahead:
    """The float64 reference of a job on a graph sorted by source, iterated
    on a daemon thread while the chips run the job: update k runs once
    ``allow(k)`` (or more) came, so the thread trails the engine's
    supersteps by one update and stops where the job stopped."""

    def __init__(self, graph: Graph, damping: float):
        self.graph, self.damping = graph, damping
        self._cond = threading.Condition()
        self._allowed = 0
        self._last = None          # (updates, ranks)
        self._error = None
        self._ended = False
        threading.Thread(target=self._run, name="pagerank-reference",
                         daemon=True).start()

    def allow(self, k: int) -> None:
        """Let the thread reach k updates; -1 ends it for good."""
        with self._cond:
            if self._allowed >= 0:
                self._allowed = k
            self._cond.notify_all()

    def ranks(self, k: int):
        """The ranks after k updates, once the thread has them (None if it
        went past them), and the thread ends."""
        self.allow(k)
        with self._cond:
            self._cond.wait_for(lambda: self._ended or (
                self._last is not None and self._last[0] >= k))
            error, (j, r) = self._error, self._last or (None, None)
        self.allow(-1)
        if error is not None:
            raise error
        return r if j == k else None

    def _run(self) -> None:
        try:
            src = self.graph.src
            if not np.all(src[1:] >= src[:-1]):
                raise ValueError("the graph's slots are not sorted by source")
            for j, r in enumerate(_iterates(self.graph, self.damping, exact)):
                with self._cond:
                    self._last = (j, r)
                    self._cond.notify_all()
                    self._cond.wait_for(lambda: self._allowed != j)
                    if self._allowed < j:
                        return
        except BaseException as e:  # noqa: BLE001  handed to ranks()
            with self._cond:
                self._error = e
        finally:
            with self._cond:
                self._ended = True
                self._cond.notify_all()
