"""A checkout root whose ``BENCHMARK.json`` also names the cells held out
of the benchmark (``data/held_cells.json``), so that tests keep their
configurations, mixes and references working for the PR that adds them
back."""
import json
import pathlib

from bench import spec

HELD = pathlib.Path(__file__).resolve().parent / "data" / "held_cells.json"


def held_names():
    return [w["name"] for w in json.loads(HELD.read_text())["workloads"]]


def root_with_held(tmp: pathlib.Path) -> pathlib.Path:
    """``tmp`` holding ``BENCHMARK.json`` with the held entries added and a
    link to the real ``bench`` directory."""
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    held = json.loads(HELD.read_text())
    for key in ("configs", "workloads"):
        names = {e["name"] for e in bench[key]}
        bench[key] += [e for e in held[key] if e["name"] not in names]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp / "bench").symlink_to(spec.ROOT / "bench")
    return tmp
