"""The superstep's stages as the profiler sees them.

Each stage of ``make_superstep`` runs under the ``jax.named_scope`` that
``superstep.STAGES`` names, so every HLO instruction's ``op_name`` tells
which stage it belongs to after XLA has fused and renamed it; and
``run_host`` puts its host legs (dispatch, wait, readback, callback) on
the JAX profiler's clock as ``TraceAnnotation`` spans that carry the
superstep number. Both are read from the compiled program and from a
CPU profiler trace here.
"""
import dataclasses
import glob
import re

import jax
import pytest

from repro.core import load_graph, run_host
from repro.core.driver import (default_engine_config, init_vertex_values,
                               plan_gather_layout)
from repro.core.relations import empty_msgs, init_gs
from repro.core.superstep import STAGES, make_superstep
from repro.graph import PageRank, PathMerge, chain_graph, rmat_graph
from repro.obs import trace

N = 1 << 10   # Graph500 scale 10
EDGES = rmat_graph(N, 16 * N, seed=1)
_INST = re.compile(r"^\s*(?:ROOT )?%(\S+) = (?:\(.*?\)|\S+) ([a-z][\w-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _stage(op_name: str):
    found = re.findall(r"pregel\.[a-z_]+", op_name.split(";", 1)[0])
    return found[-1] if found else None


def _compiled_ops(program, plan, vert):
    """(opcode, stage) of every instruction of the compiled superstep."""
    ec = default_engine_config(vert, program, plan)
    gs = init_gs(program.agg_dims)
    vert = init_vertex_values(vert, program, gs)
    msg = empty_msgs(vert.num_partitions, ec.n_parts * ec.bucket_cap,
                     program.msg_dims)
    text = jax.jit(make_superstep(program, plan, ec)).lower(
        vert, msg, gs, None, plan_gather_layout(plan, vert)) \
        .compile().as_text()
    ops = []
    for line in text.splitlines():
        m = _INST.match(line)
        if m:
            name = _OP_NAME.search(line)
            ops.append((m.group(2), _stage(name.group(1)) if name else None))
    return ops


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_every_stage_names_its_operations(impl):
    program = PageRank(N, iterations=5)
    plan = dataclasses.replace(program.suggested_plan, kernel_impl=impl)
    vert = load_graph(EDGES, N, P=1, value_dims=program.value_dims)
    ops = _compiled_ops(program, plan, vert)
    # PageRank mutates nothing: every other stage is in the program
    assert {s for _, s in ops if s} == set(STAGES) - {"pregel.mutate"}
    sorts = [s for op, s in ops if op == "sort"]
    assert "pregel.sender_combine" in sorts and "pregel.route" in sorts
    assert set(sorts) <= {"pregel.sender_combine", "pregel.route"}
    gathers = {s for op, s in ops if op == "gather"}
    assert {"pregel.edge_gate", "pregel.route"} <= gathers
    assert None not in gathers
    if impl == "ref":
        assert "pregel.gather" in gathers
    else:
        # csr_spmv's one-hot matmuls write the gather in edge order:
        # nothing gathers their output back
        assert "pregel.gather" not in gathers
        assert ("dot", "pregel.gather") in ops


def test_mutations_run_under_their_own_scope():
    program = PathMerge(rounds=4)
    vert = load_graph(chain_graph(64), 64, P=2,
                      value_dims=program.value_dims)
    ops = _compiled_ops(program, program.suggested_plan, vert)
    assert "pregel.mutate" in {s for _, s in ops}


HOST_SPANS = ("pregel.dispatch", "pregel.wait", "pregel.readback",
              "pregel.callback")


@pytest.mark.parametrize("tracer", ["started", "off"])
def test_run_host_spans_land_on_the_profiler_clock(tmp_path, tracer):
    """Three supersteps under ``jax.profiler``: the host CPU plane holds
    each host leg of each superstep, with its ``superstep`` stat, whether
    or not ``repro.obs.trace`` was started."""
    from jax.profiler import ProfileData
    program = PageRank(N, iterations=20)
    vert = load_graph(EDGES, N, P=1, value_dims=program.value_dims)
    seen = []
    if tracer == "started":
        trace.start()
    jax.profiler.start_trace(str(tmp_path))
    try:
        run_host(vert, program, program.suggested_plan, max_supersteps=3,
                 on_superstep=lambda i, *_: seen.append(i))
    finally:
        jax.profiler.stop_trace()
        recorded = trace.stop()
    assert seen == [1, 2, 3]
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
    got = {}
    for line in host.lines:
        for e in line.events:
            if e.name in HOST_SPANS:
                got.setdefault(e.name, set()).add(dict(e.stats)["superstep"])
    assert got == {name: {1, 2, 3} for name in HOST_SPANS}
    if tracer == "started":
        names = {ev[1] for _, _, evs in recorded.drain() for ev in evs
                 if ev[0] == "X"}
        assert set(HOST_SPANS) <= names
    else:
        assert recorded is None
