"""The harness: its refusals, cells found by name from files and entries
alone, and `correct` coming out false when the timed path is broken."""
import dataclasses
import hashlib
import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.tests import tiny

PEAKS = json.loads((harness.ROOT / "bench" / "peaks.json").read_text())


def _dev(platform="tpu", kind="TPU v5 lite"):
    return SimpleNamespace(platform=platform, device_kind=kind)


def test_check_device_takes_a_tpu_of_a_known_kind():
    assert harness.check_device([_dev()], 1, PEAKS) is PEAKS["TPU v5 lite"]
    assert harness.check_device([_dev()] * 4, 4, PEAKS)["hbm_bytes_per_s"] \
        == 819e9


@pytest.mark.parametrize("devices,chips", [
    ([_dev("cpu", "cpu")], 1),
    ([], 1),
    ([_dev()], 4),
    ([_dev(kind="TPU v9 imaginary")], 1),
])
def test_check_device_refuses(devices, chips):
    with pytest.raises(harness.Refused):
        harness.check_device(devices, chips, PEAKS)


def test_compile_cache_is_the_programs(monkeypatch, tmp_path):
    """The program's fixed directory inside the checkout, or
    ``$JAX_COMPILATION_CACHE_DIR`` untouched, with every program kept."""
    from repro.launch import compile_cache
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.__setitem__(k, v))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.CHECKOUT_CACHE == harness.ROOT / ".jax_cache"
    assert harness.setup_compile_cache() == str(compile_cache.CHECKOUT_CACHE)
    assert calls == {
        "jax_compilation_cache_dir": str(compile_cache.CHECKOUT_CACHE),
        "jax_persistent_cache_min_compile_time_secs": 0}
    calls.clear()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert harness.setup_compile_cache() == str(tmp_path)
    assert calls == {"jax_persistent_cache_min_compile_time_secs": 0}


def _main(argv, capsys):
    from bench.run import parse
    rc = harness.main(parse(argv), 0.0)
    return rc, capsys.readouterr()


def test_main_refuses_off_tpu_and_prints_no_result(capsys):
    rc, out = _main(["--workload", "g500-s22.pagerank", "--seed", "1",
                     "--seconds", "1"], capsys)
    assert rc != 0 and out.out == ""
    assert "TPU" in out.err


def test_main_refuses_a_kernel_override(capsys, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "ref")
    rc, out = _main(["--workload", "g500-s22.pagerank", "--seed", "1",
                     "--seconds", "1"], capsys)
    assert rc != 0 and out.out == "" and "REPRO_KERNEL_IMPL" in out.err


def test_main_refuses_an_unknown_workload(capsys):
    rc, out = _main(["--workload", "nope", "--seed", "1", "--seconds", "1"],
                    capsys)
    assert rc != 0 and out.out == ""


def test_a_checkout_of_the_benchmark_alone_is_refused(tmp_path):
    shutil.copytree(harness.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "g500-s22.pagerank",
         "--seed", "1", "--seconds", "1"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120)
    assert out.returncode != 0 and out.stdout == ""
    assert "program is not in this checkout" in out.stderr


def _digest(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_cell_traffic_config_and_metric_added_as_files(tmp_path):
    """A new configuration, traffic mix, per-layer metric and cell are new
    files plus new entries in BENCHMARK.json; every file already there
    stays byte for byte, and the harness finds the new ones by name."""
    root = tmp_path / "checkout"
    shutil.copytree(harness.ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(harness.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    before = _digest(root / "bench")

    (root / "bench/configs/tiny-rmat.json").write_text(json.dumps({
        "generator": "rmat", "scale": 8, "edgefactor": 8, "a": 0.57,
        "b": 0.19, "c": 0.19, "partitions": 1}))
    (root / "bench/traffic/pagerank-d90.json").write_text(json.dumps({
        "algorithm": "pagerank", "damping": 0.9, "iterations": 10**6,
        "limits": {"rank_max_rel_err": 1e-4}}))
    (root / "bench/metrics/supersteps.py").write_text(
        "def read(run):\n    return run.steps\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-rmat", "source": "test",
                            "file": "bench/configs/tiny-rmat.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny.pagerank-d90",
                              "config": "tiny-rmat",
                              "traffic": "pagerank-d90", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "supersteps", "unit": "steps",
                              "better": "higher", "source": "host_clock",
                              "layer": "driver loop",
                              "moves": "edges_per_s",
                              "workloads": ["tiny.pagerank-d90"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.resolve_cell(harness.load_spec(root),
                                "tiny.pagerank-d90", root)
    assert cell.traffic["damping"] == 0.9 and cell.config["scale"] == 8
    line = tiny.run("tiny.pagerank-d90", trace=True, root=root)
    assert line["correct"] is True
    assert line["metrics"]["supersteps"]["value"] >= 1
    after = _digest(root / "bench")
    assert {k: v for k, v in after.items() if k in before} == before


# faults planted under the timed path: the superstep that run_host builds
def _unchanged(step):
    def f(vert, msg, gs, *a):
        _, msg2, gs2 = step(vert, msg, gs, *a)
        return vert, msg2, gs2
    return f


def _half_dropped(step):
    def f(vert, msg, gs, *a):
        vert2, msg2, gs2 = step(vert, msg, gs, *a)
        keep = jnp.arange(msg2.valid.shape[1]) % 2 == 0
        return vert2, dataclasses.replace(msg2, valid=msg2.valid & keep), \
            gs2
    return f


def _altered(step):
    def f(vert, msg, gs, *a):
        vert2, msg2, gs2 = step(vert, msg, gs, *a)
        value = vert2.value.at[0, 1, 0].multiply(1.001)
        return dataclasses.replace(vert2, value=value), msg2, gs2
    return f


@pytest.mark.parametrize("fault", [_unchanged, _half_dropped, _altered])
@pytest.mark.parametrize("name", ["g500-s22.pagerank"])
def test_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    from repro.core import driver
    real = driver.make_superstep
    monkeypatch.setattr(driver, "make_superstep",
                        lambda *a, **k: fault(real(*a, **k)))
    line = tiny.run(name)
    assert line["correct"] is False, line
    assert line["failed"] >= 1
