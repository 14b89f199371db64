"""The stage scopes: read from the HLO a profiler trace keeps (a CPU
trace recorded here), and reduced to stage times on a small trace
recorded on a v5e (two PageRank supersteps at Graph500 scale 22, cut to
the longest instructions and the longest of each stage, with their
scopes and the raw HLO the neighbour rule read to place them)."""
import glob
import json
from pathlib import Path

import pytest

from bench import harness, scopes, tracedata

FIXTURE = json.loads((Path(__file__).parent / "fixtures" /
                      "trace_pagerank_v5e_scoped.json").read_text())
OLD_FIXTURE = json.loads((Path(__file__).parent / "fixtures" /
                          "trace_pagerank_v5e.json").read_text())
READERS = {"gather_ms_per_step": "pregel.gather",
           "edge_gate_ms_per_step": "pregel.edge_gate",
           "sender_combine_ms_per_step": "pregel.sender_combine",
           "route_ms_per_step": "pregel.route"}


def _record(trace):
    fields = {f: None for f in harness.RunRecord.__dataclass_fields__}
    return harness.RunRecord(**dict(fields, trace=trace))


def test_load_scopes_reads_the_hlo_the_profiler_kept(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def superstep(x, i):
        with jax.named_scope("pregel.gather"):
            y = jnp.take(x, i)
        with jax.named_scope("pregel.sender_combine"):
            return jax.lax.sort(y) * 2

    x, i = jnp.arange(64.0), jnp.arange(64)[::-1]
    superstep(x, i).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    superstep(x, i).block_until_ready()
    jax.profiler.stop_trace()
    got = scopes.load_scopes(str(tmp_path))
    (program, names), = got[scopes.SCOPES].items()
    assert set(got[scopes.NEIGHBOURS]) == {program}
    assert program.startswith("jit_superstep(")
    text = superstep.lower(x, i).compile().as_text()
    sort = next(n for n in names if n.startswith("sort"))
    assert f'%{sort} = ' in text
    assert scopes.stage_of(names[sort]) == "pregel.sender_combine"
    assert "pregel.gather" in {scopes.stage_of(v) for v in names.values()}
    # one trace file per directory, as tracedata.load_xspace asks
    assert len(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                         recursive=True)) == 1


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n >> 7 else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _len(num, body: bytes) -> bytes:
    return _varint(num << 3 | 2) + _varint(len(body)) + body


def _inst(i, name, op_name, operands=(), packed=True):
    body = _len(1, name.encode()) + _len(7, _len(2, op_name.encode())) + \
        _varint(35 << 3) + _varint(i)
    if packed and operands:
        body += _len(36, b"".join(_varint(j) for j in operands))
    for j in operands if not packed else ():
        body += _varint(36 << 3) + _varint(j)
    return _len(2, body)


def test_an_instruction_without_a_stage_takes_its_neighbours():
    """XLA's own instructions (no op_name) count for the stage whose
    values they read, else for the stage that reads theirs; a parameter
    keeps its own name."""
    comp = b"".join([
        _inst(1, "a", "jit(superstep)/pregel.receive/x"),
        _inst(2, "sort.1", "", [1]),
        _inst(3, "c", "jit(superstep)/pregel.route/y", [2, 4], packed=False),
        _inst(4, "copy.1", ""),
        _inst(5, "vert_vid.1", "vert.vid"),
    ])
    names, by_rule = scopes.hlo_op_names(_len(1, _len(3, comp)))
    assert by_rule == {"sort.1", "copy.1"}
    assert names == {"a": "jit(superstep)/pregel.receive/x",
                     "sort.1": "jit(superstep)/pregel.receive/x",
                     "c": "jit(superstep)/pregel.route/y",
                     "copy.1": "jit(superstep)/pregel.route/y",
                     "vert_vid.1": "vert.vid"}


@pytest.mark.parametrize("op_name,stage", [
    ("jit(superstep)/pregel.route/vmap()/sort", "pregel.route"),
    ("jit(superstep)/pregel.mutate/pregel.route/gather", "pregel.route"),
    ("jit(superstep)/pregel.gather/x;jit(superstep)/pregel.route/y",
     "pregel.gather"),
    ("vert.vid", None),
    ("", None),
])
def test_stage_of_takes_the_innermost_scope(op_name, stage):
    assert scopes.stage_of(op_name) == stage


def test_the_reader_stages_are_the_program_stages():
    from repro.core.superstep import STAGES
    for name, stage in READERS.items():
        module = harness.load_module(harness.ROOT / "bench" / "metrics" /
                                     f"{name}.py")
        assert module.STAGE == stage and stage in STAGES


def _fixture_stage_ns():
    """Stage nanoseconds of the fixture, summed by hand."""
    names = FIXTURE[scopes.SCOPES]
    lo, hi = tracedata.window_ns(FIXTURE)
    plane = tracedata.device_planes(FIXTURE)[0]
    ops = tracedata.line_events(plane, tracedata.OPS_LINE)
    out = {}
    for prog, s, d in tracedata.line_events(plane, tracedata.MODULES_LINE):
        assert lo <= s and s + d <= hi
        for op, os_, od in ops:
            if s <= os_ and os_ + od <= s + d:
                stage = scopes.stage_of(names[prog][tracedata.op_name(op)])
                out[stage] = out.get(stage, 0) + od
    return out


@pytest.mark.parametrize("name", sorted(READERS))
def test_stage_readers_on_the_fixture(name):
    want = _fixture_stage_ns()[READERS[name]] / 1e6 / 2
    assert want > 0
    assert harness.metric_reader(name)(_record(FIXTURE)) == \
        pytest.approx(want, rel=1e-12)
    assert scopes.stage_ms_per_step(FIXTURE, READERS[name]) == \
        pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("stage,instructions", [
    # csr_spmv and the three gathers that put its output in edge order
    ("pregel.gather", {"csr_spmv.1", "fusion.1", "fusion.2", "fusion.3"}),
    ("pregel.edge_gate", {"fusion.4"}),
    # the combine sort, and the compaction's scatter, which the TPU runs
    # as a sort and a fusion that carry no op_name of their own
    ("pregel.sender_combine", {"sort.10", "sort.13", "fusion.14"}),
    ("pregel.route", {"sort.0"}),
    (None, {"copy.83"}),
])
def test_fixture_stages_hold_their_instructions(stage, instructions):
    names = FIXTURE[scopes.SCOPES]["jit_superstep(7989270935473421821)"]
    assert {i for i, op in names.items()
            if scopes.stage_of(op) == stage} == instructions
    want = sum(d for p in tracedata.device_planes(FIXTURE)
               for e, _, d in tracedata.line_events(p, tracedata.OPS_LINE)
               if tracedata.op_name(e) in instructions)
    totals, runs = scopes.stage_ns(FIXTURE)
    assert totals[stage] == want and runs == 2


def test_the_neighbour_rule_on_the_fixture_hlo():
    """The v5e's own HLO, cut to what the rule examines for the kept
    instructions: it places each as the whole HLO placed it, and the
    compaction's scatter (``sort.13``, ``fusion.14``), which carries no
    op_name of its own, lands in ``pregel.sender_combine``."""
    (program, comps), = FIXTURE["hlo"].items()
    proto = _len(1, b"".join(
        _len(3, b"".join(_inst(i, name, op, ids) for i, name, op, ids in c))
        for c in comps))
    names, by_rule = scopes.hlo_op_names(proto)
    kept = FIXTURE[scopes.SCOPES][program]
    assert {k: names[k] for k in kept} == kept
    assert by_rule & set(kept) == set(FIXTURE[scopes.NEIGHBOURS][program])
    raw = {name: op for c in comps for _, name, op, _ in c}
    for inst in ("sort.13", "fusion.14"):
        assert scopes.stage_of(raw[inst]) is None and inst in by_rule
        assert scopes.stage_of(names[inst]) == "pregel.sender_combine"
    assert scopes.stage_of(names["copy.83"]) is None


def test_neighbour_share_on_the_fixture():
    (program, ruled), = FIXTURE[scopes.NEIGHBOURS].items()
    lo, hi = tracedata.window_ns(FIXTURE)
    ops = tracedata.line_events(tracedata.device_planes(FIXTURE)[0],
                                tracedata.OPS_LINE)
    whole = sum(d for _, s, d in ops if lo <= s and s + d <= hi)
    mine = sum(d for e, s, d in ops
               if tracedata.op_name(e) in ruled and lo <= s and s + d <= hi)
    assert 0 < mine < whole
    assert scopes.neighbour_share(FIXTURE) == pytest.approx(mine / whole,
                                                           rel=1e-12)
    bare = {k: v for k, v in FIXTURE.items() if k != scopes.NEIGHBOURS}
    assert scopes.neighbour_share(bare) == 0


def test_unscoped_share_on_the_fixture():
    by_stage = _fixture_stage_ns()
    assert scopes.unscoped_share(FIXTURE) == pytest.approx(
        by_stage.get(None, 0) / sum(by_stage.values()), rel=1e-12)
    totals, runs = scopes.stage_ns(FIXTURE)
    assert runs == 2 and totals == pytest.approx(by_stage)


def test_the_old_readers_read_the_same_on_the_scoped_fixture():
    """The scope key leaves the event triples as they were: a reader that
    ignores it reads the same with or without it."""
    bare = {k: v for k, v in FIXTURE.items() if k != scopes.SCOPES}
    for name in ("idle_share", "sort_ms_per_step", "edge_gather.roofline"):
        reader = harness.metric_reader(name)
        rec = dict(edge_slots=134217728, vertices=4194304,
                   value_channels=2, peaks={"hbm_bytes_per_s": 819e9})
        fields = {f: None for f in harness.RunRecord.__dataclass_fields__}
        with_key = reader(harness.RunRecord(**dict(fields, **rec,
                                                   trace=FIXTURE)))
        without = reader(harness.RunRecord(**dict(fields, **rec,
                                                  trace=bare)))
        assert with_key == without


@pytest.mark.parametrize("name", sorted(READERS))
def test_stage_readers_find_nothing_without_scopes(name):
    read = harness.metric_reader(name)
    assert read(_record(None)) is None
    assert read(_record({"planes": []})) is None
    # a trace from a program (or a harness) that names no stages
    assert read(_record(OLD_FIXTURE)) is None
    assert scopes.unscoped_share(OLD_FIXTURE) is None


def test_the_exchange_program_counts_for_route():
    """``run_sharded`` runs its ``all_to_all`` as a program of its own,
    ``jit_exchange``, under the ``pregel.route`` scope: its operations
    count for that stage, and only superstep programs count as runs."""
    ev = lambda name, s, d: [f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p)",
                             s, d]
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_superstep(1)", 1000, 100], ["jit_exchange(2)", 1100, 50],
                ["jit_superstep(1)", 1200, 100], ["jit_exchange(2)", 1300, 50],
                ["jit_other(3)", 1400, 50]]},
            {"name": "XLA Ops", "events": [
                ev("a", 1010, 60), ev("all-to-all.1", 1110, 30),
                ev("a", 1210, 60), ev("all-to-all.1", 1310, 30),
                ev("b", 1410, 40)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [["bench.window", 900, 700]]}]}],
        scopes.SCOPES: {
            "jit_superstep(1)": {"a": "jit(superstep)/pregel.gather/x"},
            "jit_exchange(2)": {
                "all-to-all.1": "jit(exchange)/pregel.route/all_to_all"}},
        scopes.NEIGHBOURS: {"jit_superstep(1)": ["a"]}}
    totals, runs = scopes.stage_ns(trace)
    assert runs == 2
    assert totals == {"pregel.gather": 120, "pregel.route": 60}
    assert scopes.stage_ms_per_step(trace, "pregel.route") == 30 / 1e6
    assert scopes.unscoped_share(trace) == 0
    assert scopes.neighbour_share(trace) == pytest.approx(120 / 180)
