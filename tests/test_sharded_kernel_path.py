"""``run_sharded`` on the kernel path, on four devices: each worker's
``csr_spmv`` gather and ``segment_combine`` fold (Pallas, interpret mode
on the CPU) under ``shard_map``, a relation that ``load_graph(mesh=...)``
placed block by block, and the callback's stop signal in both resident
drivers.

The tier-1 run has one JAX device, so the checks run once, in a child
process started with ``--xla_force_host_platform_device_count=4``; each
test reads its part of the child's report.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent

CHILD = r'''
import json, sys
import jax
import numpy as np
from repro.core import gather_values, load_graph, run_host
from repro.core.sharded import run_sharded
from repro.graph import PageRank
from bench import common, harness

SUPERSTEPS = 4
gen = harness.load_module(harness.ROOT / "bench" / "gens" / "rmat.py")
pagerank = harness.load_module(harness.ROOT / "bench" / "algos" /
                               "pagerank.py")
graph = gen.generate({"scale": 12, "edgefactor": 16, "a": 0.57, "b": 0.19,
                      "c": 0.19, "partitions": 4}, 2**33 + 7)
program = PageRank(graph.n, iterations=100)
plan = program.suggested_plan
mesh = jax.make_mesh((4,), ("workers",), devices=jax.devices()[:4])
host = load_graph(graph.edge_list(), graph.n, P=4, value_dims=1)
placed = load_graph(graph.edge_list(), graph.n, P=4, value_dims=1,
                    mesh=mesh)
out = {"devices": len(jax.devices())}

blocks = {}
for f in ("vid", "halt", "value", "edge_src", "edge_dst", "edge_val"):
    whole, arr = np.asarray(getattr(host, f)), getattr(placed, f)
    ok = len(arr.addressable_shards) == 4
    for s in arr.addressable_shards:
        w = mesh.devices.tolist().index(s.device)
        ok &= s.data.shape == (1,) + whole.shape[1:]
        ok &= np.array_equal(np.asarray(s.data), whole[w:w + 1])
    blocks[f] = bool(ok)
out["blocks"] = blocks

def ranks(res):
    return gather_values(res.vertex, graph.n)[:, 0]

def run(driver, vert, stop_at=None, supersteps=SUPERSTEPS):
    seen = []
    if driver is run_host:
        cb = lambda i, *_: seen.append(i) or i == stop_at
        res = run_host(vert, program, plan, kernel_impl="pallas",
                       max_supersteps=supersteps, on_superstep=cb)
    else:
        cb = lambda i, rec: seen.append(i) or i == stop_at
        res = run_sharded(vert, program, plan, mesh=mesh,
                          kernel_impl="pallas", max_supersteps=supersteps,
                          on_superstep=cb)
    return res, seen

h, _ = run(run_host, host)
from repro.obs import trace
trace.start()
s, _ = run(run_sharded, placed)
out["counters"] = sorted({e[1] for _, _, evs in trace.stop().drain()
                          for e in evs if e[0] == "C"})
want = pagerank.reference(graph, {"damping": program.d},
                          common.Outcome(0, 0, [], SUPERSTEPS, 1),
                          common.exact)
out["supersteps"] = [h.supersteps, s.supersteps]
out["identical"] = ranks(h).tobytes() == ranks(s).tobytes()
out["rel_err"] = pagerank.compare(ranks(s), want)["rank_max_rel_err"]
out["kernel_impl"] = s.plan.kernel_impl

from repro.core.relations import vertex_rel_shapes
from repro.core.sharded import compile_ahead
slots = placed.edge_src.shape[1]
shapes = vertex_rel_shapes(graph.n, 4, value_dims=1, edge_slots=slots,
                           mesh=mesh)
out["shapes"] = [
    f for f in ("vid", "halt", "value", "edge_src", "edge_dst", "edge_val")
    if (getattr(shapes, f).shape, getattr(shapes, f).dtype)
    != (getattr(placed, f).shape, getattr(placed, f).dtype)
    or not getattr(shapes, f).sharding.is_equivalent_to(
        getattr(placed, f).sharding, getattr(placed, f).ndim)]
compiles = []
jax.monitoring.register_event_duration_secs_listener(
    lambda e, *_, **__: compiles.append(e)
    if e == "/jax/core/compile/backend_compile_duration" else None)
ahead_runs = {}
for name, edge_slots in (("matching", slots), ("other", slots + 1024)):
    fut = compile_ahead(
        vertex_rel_shapes(graph.n, 4, value_dims=1, edge_slots=edge_slots,
                          mesh=mesh),
        program, plan, mesh=mesh, kernel_impl="pallas")
    fut.result()
    del compiles[:]
    res = run_sharded(placed, program, plan, mesh=mesh,
                      kernel_impl="pallas", max_supersteps=SUPERSTEPS,
                      ahead=fut)
    ahead_runs[name] = {"compiles": len(compiles),
                        "identical": ranks(res).tobytes()
                        == ranks(s).tobytes()}
out["ahead"] = ahead_runs

for name, driver, vert in (("host", run_host, host),
                           ("sharded", run_sharded, placed)):
    stopped, seen = run(driver, vert, stop_at=2, supersteps=50)
    short, _ = run(driver, vert, supersteps=2)
    out["stop_" + name] = {
        "supersteps": stopped.supersteps, "seen": seen,
        "state": ranks(stopped).tobytes() == ranks(short).tobytes(),
        "gs": int(stopped.gs.superstep)}
print("REPORT " + json.dumps(out))
'''


@pytest.fixture(scope="module")
def report():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    env.pop("REPRO_KERNEL_IMPL", None)
    out = subprocess.run([sys.executable, "-c", CHILD], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    lines = [ln for ln in out.stdout.splitlines()
             if ln.startswith("REPORT ")]
    assert out.returncode == 0 and lines, out.stderr[-4000:]
    rep = json.loads(lines[-1][len("REPORT "):])
    assert rep["devices"] == 4
    return rep


@pytest.mark.parametrize("field", ["vid", "halt", "value", "edge_src",
                                   "edge_dst", "edge_val"])
def test_load_graph_places_each_block_on_its_own_device(report, field):
    """Each device holds exactly its own partition block (one of four),
    byte for byte the rows the unplaced relation has there."""
    assert report["blocks"][field]


def test_sharded_kernel_path_matches_run_host(report):
    """Four workers, each with its own gather layout, reproduce the
    one-device P=4 ranks byte for byte, within 1e-4 of the float64
    NumPy reference."""
    assert report["supersteps"] == [4, 4]
    assert report["kernel_impl"] == "pallas"
    # a layout planned per worker (the kernel's input), and the exchange
    assert report["counters"] == sorted(
        [f"gather.{c}.w{w}" for c in ("items", "tiles") for w in range(4)]
        + ["exchange.bytes"])
    assert report["identical"]
    assert report["rel_err"] < 1e-4


@pytest.mark.parametrize("driver", ["host", "sharded"])
def test_callback_true_stops_at_that_superstep(report, driver):
    """``on_superstep`` returning True after superstep 2 ends the run
    there, with the state of that boundary; no further superstep runs."""
    got = report["stop_" + driver]
    assert got["supersteps"] == 2 and got["seen"] == [1, 2]
    assert got["gs"] == 2
    assert got["state"]


def test_vertex_rel_shapes_describe_the_placed_relation(report):
    """``vertex_rel_shapes`` gives every field's shape, dtype and sharding
    as ``load_graph(mesh=...)`` places it."""
    assert report["shapes"] == []


def test_compile_ahead_serves_the_run_it_matches(report):
    """A run handed the programs ``compile_ahead`` compiled for its shapes
    compiles nothing more; one handed programs of other shapes compiles
    its own. Both reproduce the ranks of a run without them."""
    got = report["ahead"]
    assert got["matching"] == {"compiles": 0, "identical": True}
    assert got["other"]["compiles"] > 0 and got["other"]["identical"]
