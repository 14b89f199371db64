"""Smoke test of the Pregel superstep on a TPU, at Graph500 scale.

Run from the root of a checkout, in one process:

    python chip_smoke.py [--scale 22] [--seed 0]
    python chip_smoke.py --four-chips [--scale 22] [--seed 0]

It builds a Graph500 R-MAT graph (edgefactor 16, A/B/C = 0.57/0.19/0.19,
every edge in both directions) from the seed with the in-repo generator,
loads it with ``load_graph`` and runs PageRank (15 iterations, damping
0.85) and SSSP (source 0, unit weights) through the normal drivers with
``kernel_impl="auto"``: ``run_host`` on one chip, or ``run_sharded`` over
a mesh of four chips with ``--four-chips``. Each answer is checked
against a plain NumPy reference that does not use the engine. One line
per algorithm, then a JSON line with the device, goes to stdout; progress
goes to stderr. Any failure or mismatch, or a platform other than TPU,
exits non-zero without the JSON line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

DAMPING, ITERATIONS, SOURCE = 0.85, 15, 0
# PageRank: the engine sums float32 contributions in its own order, the
# reference in float64; each vertex must agree to this relative error
PAGERANK_RTOL = 1e-4


def log(msg: str) -> None:
    print(f"[chip_smoke {time.strftime('%H:%M:%S')}] {msg}",
          file=sys.stderr, flush=True)


def graph500_edges(scale: int, seed: int) -> np.ndarray:
    from repro.graph import rmat_graph
    n = 1 << scale
    e = rmat_graph(n, 16 * n, seed=seed)
    return np.concatenate([e, e[:, ::-1]]).astype(np.int32)


def pagerank_reference(src, dst, n: int) -> np.ndarray:
    """The engine's PageRank semantics in float64: every vertex starts at
    1/n and takes ITERATIONS - 1 updates r = (1 - d)/n + d * sum over
    in-edges of r[u] / outdeg(u); dangling mass is not redistributed."""
    inv_deg = 1.0 / np.maximum(np.bincount(src, minlength=n), 1)
    w = inv_deg[src]
    r = np.full(n, 1.0 / n)
    for _ in range(ITERATIONS - 1):
        r = (1 - DAMPING) / n + DAMPING * np.bincount(
            dst, weights=r[src] * w, minlength=n)
    return r


def bfs_levels(src, dst, n: int) -> np.ndarray:
    """Unit-weight shortest paths from SOURCE by breadth-first levels;
    unreachable vertices stay at inf."""
    level = np.full(n, np.inf)
    level[SOURCE] = 0
    frontier = np.zeros(n, bool)
    frontier[SOURCE] = True
    k = 0
    while frontier.any():
        k += 1
        nb = dst[frontier[src]]
        nb = nb[np.isinf(level[nb])]
        level[nb] = k
        frontier = np.zeros(n, bool)
        frontier[nb] = True
    return level


def check(name: str, got: np.ndarray, want: np.ndarray) -> str:
    """Raise unless the engine's answer matches the reference; returns a
    one-line summary of the agreement."""
    if name == "pagerank":
        rel = np.abs(got - want) / want
        if not rel.max() <= PAGERANK_RTOL:
            raise AssertionError(f"pagerank: max relative error "
                                 f"{rel.max():.3g} > {PAGERANK_RTOL}")
        return f"max_rel_err={rel.max():.3g}"
    reached = np.isfinite(want)
    if not np.array_equal(got[reached], want[reached]) \
            or not (got[~reached] >= 3e38).all():
        bad = int((got[reached] != want[reached]).sum())
        raise AssertionError(f"sssp: {bad} reachable distances differ")
    return f"reached={int(reached.sum())} depth={int(want[reached].max())}"


def peak_bytes(devices) -> int | None:
    """Process-lifetime peak of device memory in use, the largest over
    ``devices`` (None where the backend keeps no statistics)."""
    stats = [d.memory_stats() for d in devices]
    if not all(stats):
        return None
    return max(s["peak_bytes_in_use"] for s in stats)


def run_one_chip(vert, program, plan, n: int, device):
    """run_host on one chip, after compiling its superstep once more by
    hand for the compile time, memory_analysis and the kernel check (the
    persistent compilation cache serves run_host the same program)."""
    import jax
    from repro.core import gather_values, run_host
    from repro.core.driver import default_engine_config
    from repro.core.relations import empty_msgs, init_gs
    from repro.core.superstep import make_superstep
    from repro.kernels import backend as kbackend

    ec = default_engine_config(vert, program, plan)
    shapes = jax.eval_shape(lambda v: (
        v, empty_msgs(v.num_partitions, ec.n_parts * ec.bucket_cap,
                      program.msg_dims), init_gs(program.agg_dims)), vert)
    P, Ep = vert.edge_src.shape
    layout = (kbackend.edge_layout_shapes(P, Ep, vert.capacity)
              if kbackend.wants_edge_layout(plan) else None)
    t = time.time()
    compiled = jax.jit(make_superstep(program, plan, ec)).lower(
        *shapes, None, layout).compile()
    compile_s = time.time() - t
    mem = compiled.memory_analysis()
    kernels = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    log(f"compiled in {compile_s:.1f}s, {kernels} Pallas kernels")
    t = time.time()
    res = run_host(vert, program, plan, max_supersteps=200, ec=ec)
    wall = time.time() - t
    values = gather_values(res.vertex, n)[:, 0]
    stats = {
        "supersteps": res.supersteps,
        "compile_s": round(compile_s, 3),
        "run_s": round(wall, 3),
        "steady_s_per_superstep": _steady(res.stats),
        "first_superstep_s": round(res.stats[0]["wall_s"], 3),
        "mem_argument_bytes": mem.argument_size_in_bytes,
        "mem_output_bytes": mem.output_size_in_bytes,
        "mem_temp_bytes": mem.temp_size_in_bytes,
        "peak_bytes_in_use": peak_bytes([device]),
        "pallas_kernels": kernels,
    }
    return values, stats


def run_four_chips(vert, program, plan, n: int, devices):
    from repro.core import gather_values
    from repro.core.sharded import run_sharded
    from repro.launch.mesh import make_host_mesh
    t = time.time()
    res = run_sharded(vert, program, plan, mesh=make_host_mesh(4),
                      max_supersteps=200)
    wall = time.time() - t
    values = gather_values(res.vertex, n)[:, 0]
    ex = [s["exchange_stall_s"] for s in res.stats[1:]
          if "exchange_stall_s" in s]
    stats = {
        "supersteps": res.supersteps,
        "run_s": round(wall, 3),
        "first_superstep_s": round(res.stats[0]["wall_s"], 3),
        "steady_s_per_superstep": _steady(res.stats),
        "steady_exchange_s_per_superstep": (
            round(float(np.median(ex)), 6) if ex else None),
        "peak_bytes_in_use": peak_bytes(devices),
    }
    return values, stats


def _steady(records) -> float | None:
    walls = [r["wall_s"] for r in records
             if "wall_s" in r and not r.get("recompiled")]
    return round(float(np.median(walls)), 6) if walls else None


def run(scale: int, seed: int, four_chips: bool):
    """Build the graph, run both algorithms, check both answers. Returns
    one result dict per algorithm."""
    import jax
    import jax.numpy as jnp
    from repro.core import PhysicalPlan, load_graph
    from repro.graph import SSSP, PageRank
    from repro.kernels import backend as kbackend

    n = 1 << scale
    devices = jax.devices()[:4 if four_chips else 1]
    parts = len(devices)   # one partition per chip
    t = time.time()
    edges = graph500_edges(scale, seed)
    log(f"R-MAT scale {scale}: {n} vertices, {len(edges)} directed edges "
        f"in {time.time() - t:.1f}s")
    t = time.time()
    vert = load_graph(edges, n, P=parts, value_dims=2)
    if four_chips:
        # host arrays: run_sharded places each partition on its own chip
        # instead of staging the whole graph on the first one
        vert = jax.tree.map(np.asarray, vert)
    log(f"load_graph P={parts}: {vert.edge_src.shape[1]} edge slots per "
        f"partition in {time.time() - t:.1f}s")
    src, dst = edges[:, 0], edges[:, 1]
    del edges
    # both algorithms scan every vertex each superstep (full-outer join)
    plan = PhysicalPlan(join="full_outer")
    programs = {"pagerank": PageRank(n, damping=DAMPING,
                                     iterations=ITERATIONS),
                "sssp": SSSP(source=SOURCE)}
    results = []
    for name, program in programs.items():
        v = dataclasses.replace(vert, value=np.zeros(
            vert.vid.shape + (program.value_dims,), np.float32))
        if not four_chips:
            v = dataclasses.replace(v, value=jnp.asarray(v.value))
        log(f"{name}: running")
        if four_chips:
            got, stats = run_four_chips(v, program, plan, n, devices)
        else:
            got, stats = run_one_chip(v, program, plan, n, devices[0])
        del v
        t = time.time()
        want = (pagerank_reference(src, dst, n) if name == "pagerank"
                else bfs_levels(src, dst, n))
        agree = check(name, got, want)
        log(f"{name}: matches the NumPy reference ({agree}); reference "
            f"took {time.time() - t:.1f}s")
        results.append({"algo": name, "driver": ("run_sharded" if four_chips
                                                  else "run_host"),
                        "chips": parts, "scale": scale, "vertices": n,
                        "edges": int(len(src)),
                        "kernel_impl": kbackend.resolve(plan.kernel_impl),
                        **stats, "check": agree})
    return results


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--scale", type=int, default=22,
                    help="Graph500 scale: 2**scale vertices (default 22)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run_sharded over a 4-chip mesh, nothing else")
    args = ap.parse_args(argv)
    if os.environ.get("REPRO_KERNEL_IMPL"):
        log("REPRO_KERNEL_IMPL is set; the smoke test runs the kernel "
            "choice the platform makes")
        return 1
    import jax
    from repro.kernels import backend as kbackend
    from repro.launch.compile_cache import setup_compile_cache
    cache = setup_compile_cache()
    dev = jax.devices()[0]
    need = 4 if args.four_chips else 1
    if dev.platform != "tpu" or len(jax.devices()) < need:
        log(f"needs {need} TPU chip(s); JAX sees {len(jax.devices())} "
            f"{dev.platform} device(s)")
        return 1
    impl = kbackend.resolve("auto")
    if impl != "pallas_tpu":
        log(f"kernel_impl='auto' resolved to {impl!r}, not compiled Pallas")
        return 1
    log(f"{dev.device_kind} x{len(jax.devices())}, compile cache {cache}")
    try:
        results = run(args.scale, args.seed, args.four_chips)
    except Exception as e:  # noqa: BLE001 — report, then fail
        import traceback
        traceback.print_exc()
        log(f"FAILED: {e!r}")
        return 1
    if not args.four_chips and any(r["pallas_kernels"] < 2
                                   for r in results):
        log("the compiled superstep holds fewer than 2 Pallas kernels")
        return 1
    for r in results:
        print(json.dumps(r), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
