"""BENCHMARK.json against its schema: keys, names, units,
bounds, files found by name, and the check's time budget."""
import json
import re

import pytest

from bench import harness

SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
LINE = re.compile(r"^[^\n\t]{1,200}$")
CELLS = {w["name"] for w in SPEC["workloads"]}


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_the_full_check_fits_its_time_with_24_cells():
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs():
    assert 1 <= len(SPEC["configs"]) <= 24
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert LINE.match(c["source"]) and LINE.match(c["why"])
        assert c["file"].startswith("bench/")
        cfg = json.loads((harness.ROOT / c["file"]).read_text())
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
        assert all(NAME.match(k) and k in cfg for k in c["reduced"])
        assert (harness.ROOT / "bench/gens" /
                f"{cfg['generator']}.py").is_file()
    assert len({c["file"] for c in SPEC["configs"]}) == len(SPEC["configs"])


def test_workloads():
    pairs = set()
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert LINE.match(w["why"])
        assert w["chips"] == 1
        pairs.add((w["config"], w["traffic"]))
        traffic = json.loads((harness.ROOT / "bench/traffic" /
                              f"{w['traffic']}.json").read_text())
        assert (harness.ROOT / "bench/algos" /
                f"{traffic['algorithm']}.py").is_file()
        assert traffic["limits"]
    assert len(pairs) == len(SPEC["workloads"]) == len(CELLS)


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_metrics(kind):
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if kind == "end_to_end" else {"layer", "moves"})
    for m in SPEC[kind]:
        assert set(m) - {"workloads"} == keys
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert (harness.ROOT / "bench/metrics" / f"{m['name']}.py").is_file()
        assert set(m.get("workloads", CELLS)) <= CELLS
        if kind == "end_to_end":
            assert m["source"] in ("host_clock", "device_trace")
            assert 0.01 <= m["bound"] <= 0.25
        else:
            assert m["moves"] == "edges_per_s" and LINE.match(m["layer"])
            assert "workloads" in m
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))


def test_every_cell_reports_setup_another_metric_and_a_layer():
    e2e = {m["name"]: set(m.get("workloads", CELLS))
           for m in SPEC["end_to_end"]}
    assert e2e["setup_s"] == CELLS
    for cell in CELLS:
        assert sum(cell in c for n, c in e2e.items() if n != "setup_s")
        assert any(cell in m.get("workloads", CELLS)
                   for m in SPEC["per_layer"])
