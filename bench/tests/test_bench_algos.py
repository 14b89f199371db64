"""Each traffic's answers against its plain reference at a tiny size, the
references against independent implementations, and the control (the
reference in bfloat16) failing every cell's limit."""
import numpy as np
import pytest

from bench import common, harness
from bench.tests import tiny

CELLS = ["g500-s22.pagerank"]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, capsys):
    line = tiny.run(name)
    assert "compile in the window:" in capsys.readouterr().err
    assert line["correct"] is True, line
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) >= {"edges_per_s", "setup_s"}
    assert list(line)[-1] == "checks"
    for c in line["checks"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_the_limit(name):
    """The reference computed in bfloat16, put in the program's place."""
    from bench import control
    _, cell = tiny.cell(name)
    readings = control.readings(cell, 99, supersteps=6)
    assert any(readings[k] > lim
               for k, lim in cell.traffic["limits"].items()), readings


@pytest.mark.parametrize("name", CELLS)
def test_reference_in_float32_passes_the_limit(name):
    _, cell = tiny.cell(name)
    graph = cell.gen.generate(cell.config, 99)
    outcome = common.Outcome(0, 0, [], supersteps=6, value_channels=1)
    want = cell.algo.reference(graph, cell.traffic, outcome, common.exact)
    same = cell.algo.compare(want.astype(np.float32), want)
    assert all(same[k] <= lim for k, lim in cell.traffic["limits"].items())


def test_pagerank_reference_matches_dense_iteration():
    _, cell = tiny.cell("g500-s22.pagerank")
    rng = np.random.default_rng(1)
    n = 50
    src = np.sort(rng.integers(0, n, 400)).astype(np.int32)
    dst = rng.integers(0, n, 400).astype(np.int32)
    g = common.Graph(n=n, src=src, dst=dst)
    outcome = common.Outcome(0, 0, [], supersteps=7, value_channels=1)
    got = cell.algo.reference(g, cell.traffic, outcome, common.exact)
    a = np.zeros((n, n))
    np.add.at(a, (dst, src), 1.0)
    a /= np.maximum(a.sum(axis=0), 1)
    r = np.full(n, 1.0 / n)
    for _ in range(6):
        r = 0.15 / n + 0.85 * a @ r
    np.testing.assert_allclose(got, r, rtol=1e-12)
