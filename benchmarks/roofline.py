"""Superstep hot-path roofline: per-leg flops + bytes vs machine ceilings.

Walks the cost model's per-term raw ledger (``PlanCost.detail``) for one
modeled superstep — recv_groupby / join_compute / send / sender_combine /
connector / exchange — and reports each leg's flops and bytes on every
machine axis against the machine-model ceilings (peak_flops, hbm_bw,
link_bw, ...), for BOTH kernel implementations ("ref" jnp path vs
"pallas" kernel path) on BOTH machine models (the TPU-v5e default, where
"pallas" resolves to compiled pallas_tpu, and the emulated single-host
machine, where it stays in interpret mode and carries the interpreter
penalty). That is the quantitative version of the dispatch story: the
send leg's random-gather byte amplification turns into MXU matmul flops,
the sender-combine fold drops to a single streamed pass, and the fused
pack caps the connector at the bucket capacity.

A full run cross-checks the modeled totals against the trip-count-aware
HLO analyzer on a real lowered superstep (``hlo_calibrate``) for both
implementations; ``--smoke`` skips the compile-heavy cross-check.

Writes ``BENCH_roofline.json`` (schema ``roofline/v1``); ``--validate
PATH`` re-opens an artifact and checks the schema (the CI gate). Its
numbers are cost-model estimates computed on the CPU, not device metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math

from benchmarks.common import record

SCHEMA = "roofline/v1"

# the per-leg ledger axes and the machine ceiling each one is priced at
AXES = (
    ("flops", "peak_flops"),
    ("hbm_bytes", "hbm_bw"),
    ("exchange_bytes", "link_bw"),
    ("host_bytes", "host_bw"),
    ("disk_bytes", "disk_bw"),
    ("serial_bytes", "host_mem_bw"),
)
IMPLS = ("ref", "pallas")
# the superstep legs the kernel dispatch actually touches
HOT_LEGS = ("send", "sender_combine", "connector")


def _algos(n_vertices: int):
    from repro.graph import SSSP, ConnectedComponents, PageRank
    return {
        "pagerank": PageRank(n_vertices, iterations=15),
        "sssp": SSSP(source=0),
        "cc": ConnectedComponents(),
    }


def _stats(smoke: bool):
    from repro.planner import GraphStats
    if smoke:
        return GraphStats(n_vertices=4_000, n_edges=24_000, n_partitions=4,
                          vertex_capacity=1_300, edge_capacity=7_200)
    # WEB-scale per-partition shapes (paper Table 1 ballpark, scaled to
    # one host): the analytic model is shape-linear, so the leg RATIOS —
    # which is what the roofline reads — are representative
    return GraphStats(n_vertices=130_000, n_edges=800_000, n_partitions=8,
                      vertex_capacity=16_250, edge_capacity=100_000)


def leg_rows(cost, machine) -> dict:
    """Per-leg roofline rows from a PlanCost's raw ledger."""
    m = dataclasses.asdict(machine)
    legs = {}
    for term, d in cost.detail.items():
        axis_s = {ax: d[ax] / m[ceil] for ax, ceil in AXES}
        bound = max(axis_s, key=axis_s.get)
        row = {ax: d[ax] for ax, _ in AXES}
        row["seconds"] = cost.terms.get(term, 0.0)
        row["bound"] = bound
        # classic roofline coordinates for the device legs: operational
        # intensity vs the attainable flop ceiling at that intensity
        if d["hbm_bytes"] > 0:
            oi = d["flops"] / d["hbm_bytes"]
            row["intensity_flop_per_byte"] = oi
            row["attainable_flops"] = min(machine.peak_flops,
                                          oi * machine.hbm_bw)
        legs[term] = row
    return legs


def model_superstep(program, g, machine, impl: str, *, join="full_outer"):
    """One modeled superstep for (machine, kernel impl): the plan, the
    resolved implementation, and the per-leg ledger."""
    from repro.core import PhysicalPlan
    from repro.kernels import backend as kbackend
    from repro.planner import Observation, estimate

    plan = PhysicalPlan(join=join, groupby="sort",
                        connector="partitioning", sender_combine=True,
                        kernel_impl=impl).validate(program.combine_op)
    cost = estimate(plan, g, Observation(frontier_density=1.0), machine)
    return {
        "impl": impl,
        "resolved": kbackend.resolve(impl, tpu=machine.mxu),
        "plan": dataclasses.asdict(plan),
        "legs": leg_rows(cost, machine),
        "totals": {
            "flops": cost.flops,
            "hbm_bytes": cost.bytes,
            "exchange_bytes": cost.exchange_bytes,
            "seconds": cost.seconds(machine),
        },
    }


def hlo_check(program, g, impls=IMPLS) -> list:
    """Ground-truth the modeled totals on a real lowered superstep: the
    trip-count-aware HLO analyzer over the CPU-lowered step for each
    kernel impl ("pallas" lowers the interpret-mode kernels — same
    dataflow shape the model prices for the emulated machine)."""
    from repro.core import PhysicalPlan
    from repro.planner import EMULATED_MACHINE, Observation, estimate
    from repro.planner.cost import hlo_calibrate

    out = []
    for impl in impls:
        plan = PhysicalPlan(join="full_outer", groupby="sort",
                            connector="partitioning", sender_combine=True,
                            kernel_impl=impl)
        meas = hlo_calibrate(program, plan, g)
        cost = estimate(plan, g, Observation(frontier_density=1.0),
                        EMULATED_MACHINE)
        P = max(g.n_partitions, 1)
        out.append({
            "impl": impl,
            "measured_flops_per_part": meas.flops / P,
            "measured_bytes_per_part": meas.bytes / P,
            "modeled_flops": cost.flops,
            "modeled_hbm_bytes": cost.bytes,
        })
    return out


def build(smoke: bool, algos=None, with_hlo=None) -> dict:
    from repro.planner import DEFAULT_MACHINE, EMULATED_MACHINE

    g = _stats(smoke)
    progs = _algos(g.n_vertices)
    names = list(algos) if algos else list(progs)
    machines = {"tpu-v5e": DEFAULT_MACHINE, "emulated": EMULATED_MACHINE}
    if with_hlo is None:
        with_hlo = not smoke

    results = []
    for name in names:
        program = progs[name]
        for mname, machine in machines.items():
            for impl in IMPLS:
                r = model_superstep(program, g, machine, impl)
                r["algo"] = name
                r["machine"] = mname
                results.append(r)

    art = {
        "schema": SCHEMA,
        "generated_by": "benchmarks/roofline.py",
        "smoke": bool(smoke),
        "graph": dataclasses.asdict(g),
        "machines": {k: dataclasses.asdict(m)
                     for k, m in machines.items()},
        "results": results,
        "hlo_check": (hlo_check(progs[names[0]], _stats(True))
                      if with_hlo else []),
    }
    return art


def console(art: dict):
    for r in art["results"]:
        tag = f"roofline/{r['algo']}/{r['machine']}/{r['impl']}"
        hot_s = sum(r["legs"][l]["seconds"] for l in HOT_LEGS
                    if l in r["legs"])
        bounds = ";".join(f"{l}={r['legs'][l]['bound']}"
                          for l in HOT_LEGS if l in r["legs"])
        record(tag, hot_s * 1e6, f"resolved={r['resolved']};{bounds}")
    for h in art["hlo_check"]:
        record(f"roofline/hlo_check/{h['impl']}",
               h["measured_bytes_per_part"] / 2 ** 20,
               f"model_bytes={h['modeled_hbm_bytes'] / 2 ** 20:.1f}MiB;"
               f"meas_flops={h['measured_flops_per_part']:.3g}")


def validate(art: dict) -> list:
    """Schema check for BENCH_roofline.json (the CI gate). Returns a list
    of human-readable problems; empty = valid."""
    errs = []
    if art.get("schema") != SCHEMA:
        errs.append(f"schema={art.get('schema')!r}, want {SCHEMA!r}")
    for key in ("graph", "machines", "results"):
        if not art.get(key):
            errs.append(f"missing/empty {key!r}")
    if errs:
        return errs
    for mname, m in art["machines"].items():
        for _, ceil in AXES:
            if not (isinstance(m.get(ceil), (int, float)) and m[ceil] > 0):
                errs.append(f"machines[{mname}].{ceil} not positive")
    seen = set()
    for i, r in enumerate(art["results"]):
        where = f"results[{i}]"
        for key in ("algo", "machine", "impl", "resolved", "plan",
                    "legs", "totals"):
            if key not in r:
                errs.append(f"{where} missing {key!r}")
        if not all(k in r for k in ("algo", "machine", "impl", "legs")):
            continue
        seen.add((r["machine"], r["impl"]))
        for leg in HOT_LEGS:
            if leg not in r["legs"]:
                errs.append(f"{where} missing hot leg {leg!r}")
        for lname, leg in r["legs"].items():
            for key in [ax for ax, _ in AXES] + ["seconds", "bound"]:
                if key not in leg:
                    errs.append(f"{where}.legs[{lname}] missing {key!r}")
                    continue
                v = leg[key]
                if key != "bound" and not (
                        isinstance(v, (int, float)) and
                        math.isfinite(v) and v >= 0):
                    errs.append(
                        f"{where}.legs[{lname}].{key}={v!r} not a "
                        "finite non-negative number")
    for machine in art["machines"]:
        for impl in IMPLS:
            if (machine, impl) not in seen:
                errs.append(f"no result for machine={machine!r} "
                            f"impl={impl!r}")
    return errs


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes, skip the HLO cross-check (CI)")
    ap.add_argument("--algos", nargs="*", default=None,
                    help="subset of pagerank/sssp/cc (default: all)")
    ap.add_argument("--hlo", dest="hlo", action="store_true", default=None,
                    help="force the lowered-superstep HLO cross-check "
                         "(default: on unless --smoke)")
    ap.add_argument("--out", default="BENCH_roofline.json")
    ap.add_argument("--validate", metavar="PATH", default=None,
                    help="validate an existing artifact and exit")
    args = ap.parse_args(argv)

    if args.validate:
        with open(args.validate) as f:
            art = json.load(f)
        errs = validate(art)
        if errs:
            for e in errs:
                print(f"INVALID: {e}")
            raise SystemExit(1)
        print(f"{args.validate}: valid {art['schema']} "
              f"({len(art['results'])} results, "
              f"{len(art['hlo_check'])} hlo checks)")
        return 0

    art = build(args.smoke, algos=args.algos, with_hlo=args.hlo)
    errs = validate(art)
    if errs:   # never ship an artifact the CI gate would reject
        raise SystemExit("generated artifact failed its own schema: "
                         + "; ".join(errs))
    console(art)
    with open(args.out, "w") as f:
        json.dump(art, f, indent=1)
    print(f"wrote {args.out} ({len(art['results'])} results)")
    return 0


if __name__ == "__main__":
    main()
