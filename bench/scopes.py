"""Which stage of the superstep each device operation ran for, and the
device time of each stage.

The program traces every stage of its superstep under a
``jax.named_scope`` named in ``repro.core.superstep.STAGES``
(``pregel.gather``, ``pregel.sender_combine``, ...), and XLA keeps the
name in each HLO instruction's ``op_name``. A fusion takes the
``op_name`` of its root instruction, so a fusion counts for the stage of
its root. The profiler keeps the HLO of every program it saw
(``HloProto``) in the ``/host:metadata`` plane of the same
``.xplane.pb`` that ``tracedata.load_xspace`` reads, one event metadata
per program, named as the ``XLA Modules`` line names the program's runs
(``jit_superstep(<id>)``; ``run_sharded``'s exchange is
``jit_exchange(<id>)``). ``load_scopes`` reads it from there, with no
recompile; the plain-data trace holds it under ``SCOPES``
(``{program: {instruction: op_name}}``) and ``NEIGHBOURS`` (the
instructions whose ``op_name`` came from the neighbour rule of
``hlo_op_names``).

A trace without that key (one taken before the program named its
stages, or by a harness that does not load it) has nothing to read:
every reader here then returns None.
"""
from __future__ import annotations

import glob
import re
from pathlib import Path

from bench import tracedata

SCOPES = "scopes"
NEIGHBOURS = "scopes_from_neighbours"
METADATA_PLANE = "/host:metadata"
SUPERSTEP = "jit_superstep"
# run_sharded's all_to_all runs as a program of its own, under the
# ``pregel.route`` scope: its operations count for that stage
EXCHANGE = "jit_exchange"
PROGRAMS = (SUPERSTEP, EXCHANGE)
_STAGE = re.compile(r"pregel\.[A-Za-z_]+")


# ---- the few protobuf messages read here, by field number ------------
# XSpace.planes 1; XPlane.name 2, .event_metadata 4 (map entry: value 2),
# .stat_metadata 5 (map entry: value 2); XEventMetadata.name 2, .stats 5;
# XStatMetadata.id 1, .name 2; XStat.metadata_id 1, .bytes_value 6;
# HloProto.hlo_module 1; HloModuleProto.computations 3;
# HloComputationProto.instructions 2; HloInstructionProto.name 1,
# .metadata 7, .id 35, .operand_ids 36; OpMetadata.op_name 2.

def _fields(buf: bytes):
    """(field number, value) of each field of one protobuf message:
    an int for varint and fixed-width fields, bytes for the rest."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 1:
            value, pos = int.from_bytes(buf[pos:pos + 8], "little"), pos + 8
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value, pos = buf[pos:pos + size], pos + size
        elif wire == 5:
            value, pos = int.from_bytes(buf[pos:pos + 4], "little"), pos + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {pos}")
        yield num, value


def _varint(buf: bytes, pos: int):
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _all(buf: bytes, num: int) -> list:
    return [v for n, v in _fields(buf) if n == num]


def _one(buf: bytes, num: int, default=b""):
    found = _all(buf, num)
    return found[-1] if found else default


def _ints(values) -> list:
    """A repeated integer field, packed or not."""
    out = []
    for v in values:
        if isinstance(v, bytes):
            pos = 0
            while pos < len(v):
                x, pos = _varint(v, pos)
                out.append(x)
        else:
            out.append(v)
    return out


def hlo_computations(hlo_proto: bytes) -> list:
    """The instructions of each computation of an HloProto, in order:
    ``[[id, name, op_name, [operand ids]], ...]`` per computation."""
    return [[[_one(inst, 35, 0), _one(inst, 1).decode(),
              _one(_one(inst, 7), 2).decode(), _ints(_all(inst, 36))]
             for inst in _all(comp, 2)]
            for comp in _all(_one(hlo_proto, 1), 3)]


def hlo_op_names(hlo_proto: bytes):
    """({instruction name: op_name} of every instruction of an HloProto,
    the names of those whose op_name the neighbour rule gave).

    The neighbour rule: an instruction whose ``op_name`` names no stage
    (XLA adds some with none: the sorts and fusions a TPU scatter
    becomes, layout copies) takes the ``op_name`` of the nearest
    instruction of its computation that names one, searched breadth-first
    through its operands, then through its users: what XLA added to carry
    out a stage's operation reads that stage's values, so it counts for
    that stage."""
    out, by_rule = {}, set()
    for comp in hlo_computations(hlo_proto):
        name, op, operands, users = {}, {}, {}, {}
        for i, nm, op_name, ids in comp:
            name[i], op[i], operands[i] = nm, op_name, ids
            for j in ids:
                users.setdefault(j, []).append(i)
        for i in name:
            found = op[i]
            if stage_of(found) is None:
                near = (_nearest(i, operands, op) or
                        _nearest(i, users, op))
                if near is not None:
                    found = near
                    by_rule.add(name[i])
            out[name[i]] = found
    return out, by_rule


def _nearest(start, edges: dict, op: dict):
    """The op_name of the nearest instruction along ``edges`` that names
    a stage, or None."""
    seen, frontier = {start}, [start]
    while frontier:
        nxt = []
        for i in frontier:
            for j in edges.get(i, ()):
                if j in seen or j not in op:
                    continue
                if stage_of(op[j]) is not None:
                    return op[j]
                seen.add(j)
                nxt.append(j)
        frontier = nxt
    return None


def hlo_protos(log_dir: str) -> dict:
    """{program: HloProto bytes} for every superstep and exchange program
    the profiler kept in the one ``.xplane.pb`` under ``log_dir``."""
    paths = glob.glob(str(Path(log_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace file under {log_dir}, "
                           f"found {len(paths)}")
    space = Path(paths[0]).read_bytes()
    out = {}
    for plane in _all(space, 1):
        if _one(plane, 2).decode() != METADATA_PLANE:
            continue
        stat_names = {}
        for entry in _all(plane, 5):
            meta = _one(entry, 2)
            stat_names[_one(meta, 1, 0)] = _one(meta, 2).decode()
        for entry in _all(plane, 4):
            meta = _one(entry, 2)
            name = _one(meta, 2).decode()
            if not name.startswith(PROGRAMS):
                continue
            for stat in _all(meta, 5):
                if stat_names.get(_one(stat, 1, 0)) == "Hlo Proto":
                    out[name] = _one(stat, 6)
    return out


def load_scopes(log_dir: str) -> dict:
    """The keys this module reads, for the plain-data trace of the same
    ``log_dir``: ``SCOPES`` ``{program: {instruction: op_name}}`` and
    ``NEIGHBOURS`` ``{program: [instructions whose op_name the neighbour
    rule gave]}``."""
    scopes, neighbours = {}, {}
    for program, proto in hlo_protos(log_dir).items():
        scopes[program], by_rule = hlo_op_names(proto)
        neighbours[program] = sorted(by_rule)
    return {SCOPES: scopes, NEIGHBOURS: neighbours}


def stage_of(op_name: str):
    """The innermost ``pregel.*`` scope of an ``op_name`` (of its first
    name, where XLA joined several with ``;``), or None."""
    found = _STAGE.findall(op_name.split(";", 1)[0])
    return found[-1] if found else None


def _program_ops(trace: dict):
    """([(program, instruction, device ns)] of every operation the
    window's superstep and exchange runs ran, over the device planes,
    superstep runs on the first device), or None where the trace has no
    device plane, no scopes, no window or no superstep run."""
    if not (trace or {}).get(SCOPES) or not tracedata.device_planes(trace) \
            or tracedata.window_ns(trace) is None:
        return None
    lo, hi = tracedata.window_ns(trace)
    out, runs = [], None
    for plane in tracedata.device_planes(trace):
        ops = tracedata.line_events(plane, tracedata.OPS_LINE)
        mine = [(name, s, d) for name, s, d in
                tracedata.line_events(plane, tracedata.MODULES_LINE)
                if name.startswith(PROGRAMS) and s >= lo and s + d <= hi]
        if runs is None:
            runs = sum(1 for name, _, _ in mine
                       if name.startswith(SUPERSTEP))
        for name, s, d in mine:
            out += [(name, tracedata.op_name(op), od) for op, os_, od in ops
                    if os_ >= s and os_ + od <= s + d]
    return (out, runs) if runs else None


def stage_ns(trace: dict):
    """({stage or None: device nanoseconds}, runs): the device time of the
    operations each stage ran inside the window's superstep (and
    exchange) runs, summed over the device planes, and the number of
    superstep runs on the first device. None where ``_program_ops``
    finds nothing."""
    got = _program_ops(trace)
    if got is None:
        return None
    ops, runs = got
    scopes, totals = trace[SCOPES], {}
    for program, inst, ns in ops:
        stage = stage_of(scopes.get(program, {}).get(inst, ""))
        totals[stage] = totals.get(stage, 0.0) + ns
    return totals, runs


def stage_ms_per_step(trace: dict, stage: str):
    """Device milliseconds of ``stage`` (a name in ``STAGES``) per
    superstep run in the window: every device's time over the first
    device's runs, as ``sort_ms_per_step`` counts."""
    got = stage_ns(trace)
    if got is None:
        return None
    totals, runs = got
    return totals.get(stage, 0.0) / 1e6 / runs


def unscoped_share(trace: dict):
    """The share of the superstep programs' operation time that no stage
    scope names (0 to 1), or None."""
    got = stage_ns(trace)
    if got is None:
        return None
    totals, _ = got
    whole = sum(totals.values())
    return totals.get(None, 0.0) / whole if whole else None


def neighbour_share(trace: dict):
    """The share of the superstep programs' operation time whose stage
    the neighbour rule decided (0 to 1), or None: how much of the stage
    times rests on that rule and not on an op_name of the instruction's
    own."""
    got = _program_ops(trace)
    if got is None:
        return None
    ops, _ = got
    by_rule = {p: set(v) for p, v in trace.get(NEIGHBOURS, {}).items()}
    whole = sum(ns for _, _, ns in ops)
    ruled = sum(ns for p, inst, ns in ops if inst in by_rule.get(p, ()))
    return ruled / whole if whole else None
