"""The program's spans and counters read beside the harness's trace
reduction: host time per span, counters per sweep, and idle gaps named by
the host work behind them."""
import json
import pathlib

import pytest

from bench import programspans, spec
from bench.programspans import Window, reduce, split, values
from bench.tracereduce import Recorded
from bench.tracereduce import reduce as reduce_trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
FIXTURES = sorted(DATA.glob("recorded_*.json"))
WITH_PROGRAM = [p for p in FIXTURES if "program" in json.loads(p.read_text())]
READERS = ("prep_ms", "loop_ms", "sched_gap_ms", "finalize_ms",
           "device_idle_share")
VALUES = ("host_prepare_ms", "host_dispatch_ms", "host_wait_ms",
          "host_finalize_ms", "dispatches_per_sweep", "h2d_mb_per_sweep")
# What the five readers read on the fixtures recorded before the program
# wrote spans, to the last bit.
READ_BEFORE = {
    "recorded_power_planetlab800.cem_compact": {
        "prep_ms": 136.43202299999996, "loop_ms": 17.98856650000119,
        "sched_gap_ms": 30.99501299999885, "finalize_ms": 125.52380700000005,
        "device_idle_share": 0.9422023067008302},
    "recorded_power_planetlab800.sweep_mono": {
        "prep_ms": 80.34085799999995, "loop_ms": 14.413935999999989,
        "sched_gap_ms": 7.15000000317545e-05, "finalize_ms": 191.86111,
        "device_idle_share": 0.9497882495346479},
}


def _reader(name):
    return spec.load_module(spec.ROOT / "bench" / "metrics" / f"{name}.py")


def _read(rec):
    t = reduce_trace(rec, n_chips=1)
    return {n: _reader(n).read(t) for n in READERS}


def _window():
    """Two sweeps on one chip.  Sweep 7 prepares, dispatches twice and
    finalizes; sweep 8 holds one dispatch."""
    rec = Recorded(spans=[(7, 0.0, 1.0), (8, 1.5, 2.0)],
                   ops=[(0, "while.1", 0.33, 0.55), (0, "while.1", 0.7, 0.88),
                        (0, "fusion.2", 0.4, 0.5), (0, "while.1", 1.6, 1.7)])
    program = [
        ("sweep", 0.01, 0.99, {"id": 5, "dispatches": 2, "h2d_bytes": 1000,
                               "compacted": 1, "sharding": "pmap"}),
        ("sweep.prepare", 0.02, 0.3, {}),
        ("sweep.prepare.build", 0.02, 0.25, {}),
        ("sweep.dispatch", 0.3, 0.35, {}),
        ("sweep.wait", 0.35, 0.6, {}),
        ("sweep.dispatch", 0.6, 0.72, {}),
        ("sweep.wait", 0.72, 0.9, {}),
        ("sweep.finalize", 0.9, 0.98, {}),
        ("sweep", 1.5, 2.0, {"id": 6, "dispatches": 1, "h2d_bytes": 3000,
                             "compacted": 1}),
        ("sweep.dispatch", 1.5, 1.6, {}),
        ("sweep.wait", 1.6, 1.75, {}),
    ]
    return Window(rec, program)


def test_host_time_and_counters_per_sweep():
    p = reduce(_window())
    assert p.n_sweeps == 2
    assert p.host_ms["sweep"] == pytest.approx(1e3 * (0.98 + 0.5) / 2)
    assert p.host_ms["sweep.prepare"] == pytest.approx(1e3 * 0.28 / 2)
    assert p.host_ms["sweep.dispatch"] == pytest.approx(
        1e3 * (0.05 + 0.12 + 0.1) / 2)
    assert p.counters == pytest.approx(
        {"dispatches": 1.5, "h2d_bytes": 2000.0, "compacted": 1.0})
    v = values(p)
    assert v["dispatches_per_sweep"] == 1.5
    assert v["h2d_mb_per_sweep"] == pytest.approx(0.002)
    assert v["host_wait_ms"] == pytest.approx(1e3 * (0.25 + 0.18 + 0.15) / 2)
    assert v["host_finalize_ms"] == pytest.approx(40.0)


def test_gaps_are_named_by_the_innermost_span():
    p = reduce(_window())
    gaps = dict(p.idle_gaps)
    assert gaps["prep:sweep.prepare.build@sweep7"] == pytest.approx(0.23)
    assert gaps["prep:sweep.prepare@sweep7"] == pytest.approx(0.05)
    assert gaps["prep:sweep.dispatch@sweep7"] == pytest.approx(0.03)
    assert gaps["scheduler:sweep.dispatch@sweep7"] == pytest.approx(0.1)
    assert gaps["between:-@sweep7"] == pytest.approx(0.5)
    assert p.gap_ms["finalize:sweep.finalize"] == pytest.approx(40.0)
    assert p.gap_ms["finalize:sweep.wait"] == pytest.approx(
        1e3 * (0.02 + 0.05) / 2)
    # Outside the program's sweep span, inside the harness's.
    assert p.gap_ms["prep:-"] == pytest.approx(1e3 * 0.01 / 2)
    assert p.gap_ms["finalize:-"] == pytest.approx(1e3 * 0.01 / 2)
    assert split(_window().program, 0.28, 0.32) == [
        ("sweep.prepare", pytest.approx(0.02)),
        ("sweep.dispatch", pytest.approx(0.02))]


def _phase_sums(p: programspans.Program, n: int):
    sums = {}
    for key, ms in p.gap_ms.items():
        phase = key.split(":", 1)[0]
        sums[phase] = sums.get(phase, 0.0) + ms * n / 1e3
    return sums


@pytest.mark.parametrize("win", [_window()] + [
    Window.from_json(p.read_text()) for p in WITH_PROGRAM],
    ids=["made"] + [p.stem for p in WITH_PROGRAM])
def test_pieces_add_up_to_each_phase(win):
    t = reduce_trace(win.rec, n_chips=1, top=10 ** 6)
    p = reduce(win, top=10 ** 6)
    n = len(win.rec.spans)
    sums = _phase_sums(p, n)
    assert sums["prep"] == pytest.approx(sum(x.prep_s for x in t.phases))
    assert sums.get("scheduler", 0.0) == pytest.approx(
        sum(x.gap_s for x in t.phases), abs=1e-12)
    assert sums["finalize"] == pytest.approx(
        sum(x.finalize_s for x in t.phases))
    between = [d for name, d in t.idle_gaps if name.startswith("between@")]
    assert sums.get("between", 0.0) == pytest.approx(sum(between), abs=1e-12)
    per_piece = {}
    for name, d in p.idle_gaps:
        phase, rest = name.split(":", 1)
        sweep = rest.rsplit("@", 1)[1]
        per_piece[(phase, sweep)] = per_piece.get((phase, sweep), 0.0) + d
    for x in t.phases:
        assert per_piece[("prep", f"sweep{x.sweep}")] == pytest.approx(x.prep_s)


@pytest.mark.parametrize("path", FIXTURES, ids=lambda p: p.stem)
def test_existing_readers_read_alike_with_and_without_program_spans(path):
    text = path.read_text()
    bare = {k: v for k, v in json.loads(text).items() if k != "program"}
    with_program = _read(Window.from_json(text).rec)
    assert with_program == _read(Recorded.from_json(json.dumps(bare)))
    if path.stem in READ_BEFORE:
        assert with_program == READ_BEFORE[path.stem]


@pytest.mark.parametrize("path", FIXTURES, ids=lambda p: p.stem)
def test_program_values_only_where_the_program_wrote_spans(path):
    got = values(reduce(Window.from_json(path.read_text())))
    assert set(got) == set(VALUES)
    if path in WITH_PROGRAM:
        assert all(v is not None and v > 0 for v in got.values()), got
    else:
        assert all(v is None for v in got.values()), got


def test_outermost_ops_keep_the_union():
    ops = [(0, "while", 0.0, 1.0), (0, "fusion", 0.2, 0.3),
           (0, "copy", 0.9, 1.2), (1, "fusion", 0.2, 0.3)]
    kept = programspans.outermost_ops(ops)
    assert kept == [(0, "while", 0.0, 1.0), (0, "copy", 0.9, 1.2),
                    (1, "fusion", 0.2, 0.3)]


def test_nothing_written_reads_nothing():
    rec = _window().rec
    assert reduce(Window(rec, [])) is None
    outside = [("sweep", 1.1, 1.2, {"dispatches": 1})]
    assert reduce(Window(rec, outside)) is None
    assert all(v is None for v in values(None).values())


@pytest.mark.parametrize("path", WITH_PROGRAM, ids=lambda p: p.stem)
def test_only_time_outside_the_program_goes_unnamed(path):
    """On the chip's trace every idle piece inside a sweep is named by a
    program span, except the stretches between the harness's span and the
    program's ``sweep`` span at either end."""
    win = Window.from_json(path.read_text())
    p = reduce(win, top=10 ** 6)
    outside = 0.0
    for _, s, e in win.rec.spans:
        (_, ps, pe, _), = [q for q in win.program
                           if q[0] == "sweep" and s <= q[1] and q[2] <= e]
        outside += (ps - s) + (e - pe)
    unnamed = {k: v for k, v in p.gap_ms.items()
               if k.endswith(":-") and not k.startswith("between:")}
    assert set(unnamed) <= {"prep:-", "finalize:-"}
    assert sum(unnamed.values()) * p.n_sweeps / 1e3 == pytest.approx(outside)
    names = {k.split(":", 1)[1] for k in p.gap_ms} - {"-"}
    assert names and all(programspans.is_program(n) for n in names)


def test_a_recorded_excerpt_reads_back_alike():
    win = _window()
    part = programspans.excerpt(win, (8,))
    assert part.rec.spans == [(8, 1.5, 2.0)]
    assert [p[0] for p in part.program] == ["sweep", "sweep.dispatch",
                                            "sweep.wait"]
    back = Window.from_json(part.to_json())
    assert back.rec.spans == [tuple(s) for s in part.rec.spans]
    assert values(reduce(back)) == values(reduce(part))
    assert values(reduce(part))["dispatches_per_sweep"] == 1.0
