"""The four-chip cell's own files: the generator that draws each chip's
share (``rmat_sharded``), the per-chip trace readers (``exchange_ms_per_step``,
``chip_skew``) on a small synthetic four-plane trace, and the whole cell
at a tiny size on four virtual CPU devices, in a child process (the
tier-1 run has one device)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import harness
from bench.tests.test_bench_trace import FIXTURE as ONE_CHIP
from bench.tests.test_bench_trace import _record

FIXTURE = json.loads((Path(__file__).parent / "fixtures" /
                      "trace_sharded_x4.json").read_text())
CELL = "g500-s24.pagerank.x4"
RMAT = {"name": "tiny", "scale": 9, "edgefactor": 16, "a": 0.57, "b": 0.19,
        "c": 0.19, "partitions": 1, "chips": 1}


def _gen():
    return harness.load_module(harness.ROOT / "bench" / "gens" /
                               "rmat_sharded.py")


def _read(name, trace):
    return harness.metric_reader(name)(_record(trace=trace, cell=CELL))


def test_exchange_ms_per_step_reads_the_slowest_chip():
    # chips 0-2: all_to_all 8.0 and 7.0 us plus a 0.5 us reorder a run;
    # chip 3: 9.0 and 9.5 us plus the same
    assert _read("exchange_ms_per_step", FIXTURE) == pytest.approx(
        (9500 + 10000) / 2 / 1e6)


def test_chip_skew_reads_the_slow_chip_without_collectives():
    # work outside the all-reduce: 60/60/60/80 us, then 50/50/50/70 us
    want = 100 * ((4 * 80 / 260 - 1) + (4 * 70 / 220 - 1)) / 2
    assert _read("chip_skew", FIXTURE) == pytest.approx(want, rel=1e-12)


def test_chip_skew_matches_runs_by_overlap():
    """A chip whose programs start late, or that has a run cut by the
    window, still pairs each superstep with the others'."""
    from bench import chips
    runs = chips.program_runs(FIXTURE, "jit_superstep",
                              skip=chips.COLLECTIVE)
    assert [len(r) for r in runs] == [2, 2, 2, 2]
    assert chips.matched(runs) == [[60000, 60000, 60000, 80000],
                                   [50000, 50000, 50000, 70000]]


@pytest.mark.parametrize("name", ["exchange_ms_per_step", "chip_skew"])
def test_readers_find_nothing_on_one_chip_or_no_trace(name):
    assert _read(name, ONE_CHIP) is None
    assert _read(name, None) is None


@pytest.mark.parametrize("seed", [0, 2**31 + 11, 2**40 + 3])
def test_rmat_sharded_slot_count_is_fixed_and_sorted(seed):
    g = _gen().generate(RMAT, seed)
    assert g.n == 512 and g.edge_slots == 2 * 16 * 512
    assert g.src.dtype == np.int32 and g.dst.dtype == np.int32
    assert (np.diff(g.src) >= 0).all()
    fwd = np.sort(g.src.astype(np.int64) * g.n + g.dst)
    rev = np.sort(g.dst.astype(np.int64) * g.n + g.src)
    assert np.array_equal(fwd, rev)


def test_rmat_sharded_is_deterministic_seeded_and_skewed():
    gen = _gen()
    a, b = gen.generate(RMAT, 5), gen.generate(RMAT, 5)
    assert np.array_equal(a.src, b.src) and np.array_equal(a.dst, b.dst)
    assert not np.array_equal(a.dst, gen.generate(RMAT, 2**32 + 5).dst)
    g = gen.generate(dict(RMAT, scale=12), 3)
    deg = np.bincount(g.src, minlength=g.n)
    assert deg.max() > 20 * deg.mean()
    assert (g.src == g.dst).any()


def test_by_source_sorts_past_16_bits_with_ties_in_input_order():
    rng = np.random.default_rng(3)
    src = rng.integers(0, 1 << 24, 50000).astype(np.int32)
    src[::7] = src[0]
    dst = np.arange(len(src), dtype=np.int32)
    order = np.argsort(src, kind="stable")
    for bits in (24, 25, 32):
        s, d = _gen()._by_source(src, dst, bits)
        assert np.array_equal(s, src[order]) and np.array_equal(d, dst[order])


def test_rmat_sharded_draw_sorts_nothing_on_the_chip():
    """The chips' program draws and relabels; sorting is the host's (a TPU
    compile of a sort takes over half a minute whatever its length)."""
    import jax
    mesh = jax.make_mesh((1,), ("chips",), devices=jax.devices()[:1])
    draw = _gen()._draw(mesh, scale=9, m=16 << 9, a=0.57, b=0.19, c=0.19)
    text = draw.lower(np.zeros((1, 2), np.uint32),
                      np.arange(512, dtype=np.int32)).as_text()
    assert "stablehlo.sort" not in text and "stablehlo.gather" in text


def test_rmat_sharded_refuses_more_chips_than_devices():
    with pytest.raises(RuntimeError, match="chips"):
        _gen().generate(dict(RMAT, chips=64), 1)


def test_reference_folds_in_threads_as_pagerank_reference():
    """The sharded cell's reference is ``pagerank.reference`` but for the
    order of its float64 additions; on slots not sorted by source it is
    ``pagerank.reference`` itself."""
    from bench.common import Graph, Outcome, exact
    algos = harness.ROOT / "bench" / "algos"
    mine = harness.load_module(algos / "pagerank_sharded.py").reference
    plain = harness.load_module(algos / "pagerank.py").reference
    g = _gen().generate(dict(RMAT, scale=11), 7)
    out = Outcome(work=0, steps=0, answers=[], supersteps=6,
                  value_channels=1)
    traffic = {"damping": 0.85}
    want = plain(g, traffic, out, exact)
    np.testing.assert_allclose(mine(g, traffic, out, exact), want,
                               rtol=1e-12, atol=0)
    perm = np.random.default_rng(0).permutation(g.edge_slots)
    shuffled = Graph(n=g.n, src=g.src[perm], dst=g.dst[perm])
    assert np.array_equal(mine(shuffled, traffic, out, exact),
                          plain(shuffled, traffic, out, exact))



def test_reference_iterated_beside_the_job_is_the_reference():
    """The thread that iterates the float64 reference while the chips run
    the job hands back, update for update, what ``reference`` computes on
    its own, trailing the engine by one update; it gives none for a count
    it went past, and a job that fails ends it."""
    from bench.common import Outcome, exact
    mod = harness.load_module(harness.ROOT / "bench" / "algos" /
                              "pagerank_sharded.py")
    g = _gen().generate(dict(RMAT, scale=10), 11)
    traffic = {"damping": 0.85}

    def want(k):
        out = Outcome(work=0, steps=0, answers=[], supersteps=k + 1,
                      value_channels=1)
        return mod.reference(g, traffic, out, exact)

    for k in (0, 1, 4):
        ahead = mod._Ahead(g, 0.85)
        for i in range(1, k + 1):
            ahead.allow(i)
        assert np.array_equal(ahead.ranks(k), want(k))
    ahead = mod._Ahead(g, 0.85)
    ahead.allow(3)
    ahead.allow(5)
    r = ahead.ranks(2)
    assert r is None or np.array_equal(r, want(2))
    ahead = mod._Ahead(g, 0.85)
    ahead.allow(-1)
    assert ahead.ranks(3) is None


CHILD = r'''
import json, time
import jax
import numpy as np
from bench import control, harness

spec = harness.load_spec()
out = {"devices": len(jax.devices())}
cell = harness.resolve_cell(spec, "g500-s24.pagerank.x4")
cell.config.update(scale=10)
g1 = cell.gen.generate(cell.config, 2**35 + 1)
g2 = cell.gen.generate(cell.config, 2**35 + 1)
fwd = np.sort(g1.src.astype(np.int64) * g1.n + g1.dst)
rev = np.sort(g1.dst.astype(np.int64) * g1.n + g1.src)
out["gen"] = {
    "slots": g1.edge_slots, "n": g1.n,
    "sorted": bool((np.diff(g1.src) >= 0).all()),
    "both_directions": bool(np.array_equal(fwd, rev)),
    "deterministic": bool(np.array_equal(g1.src, g2.src)
                          and np.array_equal(g1.dst, g2.dst))}
out["control"] = control.readings(cell, 99, supersteps=6)
for trace in (False, True):
    line = harness.execute(spec, cell, 2**35 + 3, 0.3, trace,
                           jax.devices()[:4], {"hbm_bytes_per_s": 819e9},
                           time.perf_counter())
    out["trace" if trace else "plain"] = {
        "correct": line["correct"], "attempted": line["attempted"],
        "metrics": sorted(line["metrics"]), "count": line["device"]["count"],
        "checks": line["checks"]}
print("REPORT " + json.dumps(out))
'''


@pytest.fixture(scope="module")
def four_devices():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(harness.ROOT),
                                           str(harness.ROOT / "src")]))
    env.pop("REPRO_KERNEL_IMPL", None)
    out = subprocess.run([sys.executable, "-c", CHILD], cwd=harness.ROOT,
                         env=env, capture_output=True, text=True,
                         timeout=600)
    lines = [ln for ln in out.stdout.splitlines()
             if ln.startswith("REPORT ")]
    assert out.returncode == 0 and lines, out.stderr[-4000:]
    rep = json.loads(lines[-1][len("REPORT "):])
    assert rep["devices"] == 4
    return rep


def test_rmat_sharded_on_four_chips(four_devices):
    """Four shares, one a device, merged: 2 * 16 * 2**10 slots sorted by
    source, both directions of every edge, the same for the same seed."""
    assert four_devices["gen"] == {"slots": 2 * 16 * 1024, "n": 1024,
                                   "sorted": True, "both_directions": True,
                                   "deterministic": True}


@pytest.mark.parametrize("mode", ["plain", "trace"])
def test_cell_runs_correct_on_four_devices(four_devices, mode):
    """The cell end to end at scale 10 on four virtual devices:
    ``load_graph(mesh=...)`` -> ``run_sharded``, cut by the callback."""
    got = four_devices[mode]
    assert got["correct"] is True and got["attempted"] == 1
    assert got["count"] == 4
    if mode == "plain":
        assert got["metrics"] == ["edges_per_s", "setup_s"]
    c = got["checks"]["rank_max_rel_err"]
    assert c["value"] <= c["limit"] == 1e-4


def test_control_fails_the_cells_limit(four_devices):
    """The reference in bfloat16, in the program's place, fails the
    limit: the limit tells the precisions apart."""
    assert four_devices["control"]["rank_max_rel_err"] > 1e-4
