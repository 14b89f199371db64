"""PageRank through ``load_graph`` -> ``run_host``, one job that the window
cuts at a superstep boundary, and its plain reference.

Set-up ends after the engine's first superstep (its compile or cache
load); the window then runs consecutive supersteps and closes at the
first boundary at or after its length. One unit of work is one
superstep over every edge slot. The traffic's iteration count is far
beyond any window, so the job never halts inside it.
"""
from __future__ import annotations

import time

import numpy as np

from bench.common import Graph, Outcome, log


class _WindowClosed(Exception):
    """Raised from the superstep callback to end the job at a boundary."""


def drive(graph: Graph, traffic: dict, window) -> Outcome:
    from repro.core import gather_values, load_graph, run_host
    from repro.graph import PageRank

    program = PageRank(graph.n, damping=traffic["damping"],
                       iterations=traffic["iterations"])
    t = time.perf_counter()
    vert = load_graph(graph.edge_list(), graph.n, P=graph.partitions,
                      value_dims=program.value_dims)
    log(f"load_graph in {time.perf_counter() - t:.3f} s")
    last = {}

    def on_superstep(i, vert, msg, gs, rec):
        log(f"superstep {i}: {rec['wall_s']:.3f} s")
        if i == 1:
            window.open()
        elif window.expired():
            window.close()
            last.update(vert=vert, superstep=i)
            raise _WindowClosed

    try:
        run_host(vert, program, program.suggested_plan,
                 max_supersteps=traffic["iterations"], kernel_impl="auto",
                 on_superstep=on_superstep)
    except _WindowClosed:
        pass
    else:
        raise RuntimeError("PageRank halted before the window closed: "
                           "raise the traffic's iterations")
    del vert
    values = gather_values(last.pop("vert"), graph.n)[:, 0]
    steps = last["superstep"] - 1
    return Outcome(work=steps * graph.edge_slots, steps=steps,
                   answers=[values], supersteps=last["superstep"],
                   value_channels=program.value_dims)


def reference(graph: Graph, traffic: dict, outcome: Outcome, rnd):
    """The engine's PageRank semantics, in NumPy: every vertex starts at
    1/n and takes one update per superstep after the first,
    r = (1 - d)/n + d * sum over in-slots of r[u] / outdeg(u), outdeg
    counting every slot (self-loops and duplicates too); dangling mass is
    not redistributed. ``rnd`` rounds every intermediate."""
    n, d = graph.n, traffic["damping"]
    src, dst = graph.src, graph.dst
    w = rnd(1.0 / np.maximum(np.bincount(src, minlength=n), 1))[src]
    r = rnd(np.full(n, 1.0 / n))
    for _ in range(outcome.supersteps - 1):
        r = rnd((1 - d) / n + d * rnd(np.bincount(
            dst, weights=rnd(r[src] * w), minlength=n)))
    return r


def compare(got, want) -> dict:
    """Largest relative error of any vertex's rank."""
    return {"rank_max_rel_err": float(np.max(np.abs(got - want) / want))}
