"""Cells, configurations, mixes, references and metrics are found by name,
and a new cell needs files and entries only."""
import ast
import json
import pathlib
import shutil

import numpy as np
import pytest

from bench import harness, spec, traffic
from bench.tests.held import HELD, held_names, root_with_held

ROOT = spec.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]] + held_names()
ENTRIES = BENCH["workloads"] + json.loads(HELD.read_text())["workloads"]
MIXES = sorted({w["traffic"] for w in ENTRIES})
TINY_LLM = {"n_requests": 48}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return root_with_held(tmp_path_factory.mktemp("root"))


@pytest.fixture
def no_cache(monkeypatch):
    """Keep the tests' compiles out of the persistent cache."""
    monkeypatch.setattr(harness, "use_compile_cache", lambda root: "off")


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves(name, root):
    cell = spec.load_cell(name, root)
    ref = cell.reference()
    assert set(ref.CELL_KEYS) >= {"seeds"}
    assert callable(ref.simulate) and callable(ref.events)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "events_per_s"}
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(cell.reader(m["name"]).read)
    assert traffic.n_cells(cell.traffic) > 0


def test_unknown_cell_is_named():
    with pytest.raises(KeyError, match="no workload"):
        spec.load_cell("no_such.cell")


@pytest.mark.parametrize("mix", MIXES)
def test_traffic_is_deterministic_in_the_seed(mix, root):
    mix_data = json.loads((ROOT / "bench" / "traffic" / f"{mix}.json")
                          .read_text())
    w = next(w for w in ENTRIES if w["traffic"] == mix)
    params = spec.load_cell(w["name"], root).config["params"]
    big = 2 ** 31 + 987_654_321
    a = traffic.sweep_cells(mix_data, params, big, 5)
    b = traffic.sweep_cells(mix_data, params, big, 5)
    c = traffic.sweep_cells(mix_data, params, big + 1, 5)
    d = traffic.sweep_cells(mix_data, params, big, 6)
    assert a.keys() == b.keys() == c.keys() == d.keys()
    for k in a:
        assert np.array_equal(a[k], b[k])
        assert a[k].shape == c[k].shape == d[k].shape      # same sizes
    assert not np.array_equal(a["seeds"], c["seeds"])
    assert not np.array_equal(a["seeds"], d["seeds"])
    assert len(a["seeds"]) == traffic.n_cells(mix_data)
    neg = traffic.sweep_cells(mix_data, params, -3, 0)
    assert len(neg["seeds"]) == len(a["seeds"])


def _imports(path: pathlib.Path):
    tree = ast.parse(path.read_text())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            yield node.module


def test_benchmark_imports_no_benchmarks_package_and_references_no_program():
    for path in (ROOT / "bench").rglob("*.py"):
        assert not any(m.split(".")[0] == "benchmarks"
                       for m in _imports(path)), path
    for path in (ROOT / "bench" / "reference").glob("*.py"):
        assert not any(m.split(".")[0] in ("repro", "bench")
                       for m in _imports(path)), path
    for path in (ROOT / "bench" / "traffic").iterdir():
        assert path.suffix == ".json", path     # mixes are data, not code


def test_a_cell_added_as_files_and_entries_runs(tmp_path, no_cache):
    """A new mix, a new per-layer metric and a new cell, each added as a
    file or an entry in a copy of the benchmark, run with no edit of the
    harness."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax-cache", "__pycache__"))
    mix = {"members": 3, "seeds_per_member": 2,
           "member_axes": {"placement": {"placement_keys": [0.0, 1.0]}},
           "sweep": {}, "check_lanes": 2}
    (tmp_path / "bench" / "traffic" / "tiny_grid.json").write_text(
        json.dumps(mix))
    (tmp_path / "bench" / "metrics" / "sweeps.count.py").write_text(
        "def read(t):\n    return float(len(t.phases)) or None\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["workloads"].append(
        {"name": "llmserve_helix24.tiny_grid", "config": "llmserve_helix24",
         "traffic": "tiny_grid", "chips": 1, "why": "test"})
    bench["per_layer"].append(
        {"name": "sweeps.count", "unit": "sweeps", "better": "higher",
         "source": "device_trace", "layer": "sweep scheduler",
         "moves": "events_per_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("llmserve_helix24.tiny_grid", tmp_path)
    assert "sweeps.count" in {m["name"] for m in cell.per_layer}
    out = harness.run("llmserve_helix24.tiny_grid", 11, 0.3, False,
                      t_start=0.0, require_chip=False, root=tmp_path,
                      overrides=TINY_LLM)
    assert out["correct"] and out["attempted"] >= 1
    assert set(out["metrics"]) == {"events_per_s", "setup_s"}
    assert out["metrics"]["events_per_s"]["value"] > 0
    assert list(out)[-1] == "check"
