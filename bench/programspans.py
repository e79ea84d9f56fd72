#!/usr/bin/env python3
"""The program's own host spans and counters in a traced window.

The sweep path writes host spans into the profiler's trace
(``repro.core.spans``): one ``sweep`` span per ``run_sweep`` call, which
carries the sweep's counters as stats, and under it ``sweep.validate``,
``sweep.prepare`` (``.build``, ``.pack``), ``sweep.stage``,
``sweep.dispatch``, ``sweep.wait``, ``sweep.retire`` and
``sweep.finalize``.  This module reads them beside what
:mod:`bench.tracereduce` reads (the ``bench_sweep`` spans and the device
operations), and gives for a traced window:

* ``host_ms``: for each span name, the mean over ``bench_sweep`` spans of
  the summed inclusive durations of the spans of that name inside one;
* ``counters``: the mean over ``bench_sweep`` spans of each numeric stat
  of the ``sweep`` spans inside one;
* ``gap_ms`` and ``idle_gaps``: the device's idle stretches, cut as
  :func:`bench.tracereduce.reduce` cuts them (``prep``, ``scheduler``,
  ``finalize``, ``between``, ``no_device_op``) and further at every
  program-span boundary.  A piece is named ``<phase>:<innermost
  span>@sweepN``, or ``<phase>:-@sweepN`` where no program span covers it;
  ``gap_ms`` sums the pieces per ``<phase>:<span>``, as a mean per sweep;
* :func:`values`: six numbers from the above (``host_prepare_ms``,
  ``host_dispatch_ms``, ``host_wait_ms``, ``host_finalize_ms``,
  ``dispatches_per_sweep``, ``h2d_mb_per_sweep``).

Run on a chip, it traces a few sweeps of a cell and prints all of it as
one JSON line; ``--record`` also writes two of the sweeps as a test
fixture (spans, program spans, and the device operations not nested in
another on the same chip):

    python3 bench/programspans.py --workload <cell> --seed <n> --sweeps 6 \\
        [--record FILE]
"""
from __future__ import annotations

import json
import math
import sys
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

if __name__ == "__main__":
    import pathlib
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import tracereduce                               # noqa: E402
from bench.tracereduce import Interval, Recorded, clip, merge  # noqa: E402

SWEEP = "sweep"
UNCOVERED = "-"

# (name, start, end, stats), seconds on the trace's clock.
Span = Tuple[str, float, float, Dict[str, Any]]


def is_program(name: str) -> bool:
    return name == SWEEP or name.startswith(SWEEP + ".")


@dataclass
class Window:
    """A traced window: what :mod:`bench.tracereduce` reads, and the
    program's spans."""
    rec: Recorded
    program: List[Span] = field(default_factory=list)

    @classmethod
    def from_json(cls, text: str) -> "Window":
        d = json.loads(text)
        return cls(Recorded.from_json(text),
                   [(n, s, e, dict(st)) for n, s, e, st
                    in d.get("program", [])])

    def to_json(self) -> str:
        return json.dumps({"spans": [list(s) for s in self.rec.spans],
                           "ops": [list(o) for o in self.rec.ops],
                           "program": [list(p) for p in self.program]})


@dataclass
class Program:
    """The program's spans and counters over a traced window."""
    n_sweeps: int
    host_ms: Dict[str, float]
    counters: Dict[str, float]
    gap_ms: Dict[str, float] = field(default_factory=dict)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)


def load_program(path: str) -> List[Span]:
    """The program's spans, with their stats, from a ``.xplane.pb``
    file's host planes."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    out: List[Span] = []
    for plane in data.planes:
        if tracereduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if is_program(e.name):
                    out.append((e.name, e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9,
                                dict(e.stats)))
    out.sort(key=lambda p: (p[1], -p[2]))
    return out


def load(path: str) -> Window:
    return Window(tracereduce.load_xplane(path), load_program(path))


def union(rec: Recorded) -> List[Interval]:
    """The union over chips of the device operations inside the traced
    window, as :func:`bench.tracereduce.reduce` takes it."""
    lo = min(s for _, s, _ in rec.spans)
    hi = max(e for _, _, e in rec.spans)
    return merge([(max(s, lo), min(e, hi)) for _, _, s, e in rec.ops
                  if min(e, hi) > max(s, lo)])


def idle_phases(rec: Recorded, busy: Sequence[Interval]
                ) -> List[Tuple[int, str, List[Interval]]]:
    """Per ``bench_sweep`` span, its idle stretches by phase:
    ``(sweep, phase, intervals)``, cut as :func:`bench.tracereduce.reduce`
    cuts them."""
    out = []
    for i, (sweep, s, e) in enumerate(rec.spans):
        pieces = clip(busy, s, e)
        if pieces:
            first, last = pieces[0][0], pieces[-1][1]
            out.append((sweep, "prep", [(s, first)]))
            out.append((sweep, "scheduler",
                        [(a[1], b[0]) for a, b in zip(pieces, pieces[1:])]))
            out.append((sweep, "finalize", [(last, e)]))
        else:
            out.append((sweep, "no_device_op", [(s, e)]))
        if i + 1 < len(rec.spans):
            nxt = rec.spans[i + 1][1]
            edges = [e] + [x for iv in clip(busy, e, nxt) for x in iv] + [nxt]
            out.append((sweep, "between",
                        list(zip(edges[::2], edges[1::2]))))
    return out


def innermost(program: Sequence[Span], lo: float, hi: float) -> str:
    """The name of the latest-starting program span that covers all of
    ``[lo, hi]`` (spans nest on one thread), or ``-``."""
    best: Optional[Span] = None
    for p in program:
        if p[1] <= lo and p[2] >= hi and (
                best is None or (p[1], -p[2]) > (best[1], -best[2])):
            best = p
    return best[0] if best else UNCOVERED


def split(program: Sequence[Span], lo: float, hi: float
          ) -> List[Tuple[str, float]]:
    """``[lo, hi]`` cut at every program-span boundary inside it, each
    piece named by its innermost span; neighbours of one name merged."""
    cuts = sorted({x for _, s, e, _ in program for x in (s, e)
                   if lo < x < hi})
    edges = [lo] + cuts + [hi]
    out: List[Tuple[str, float]] = []
    for a, b in zip(edges, edges[1:]):
        if b <= a:
            continue
        name = innermost(program, a, b)
        if out and out[-1][0] == name:
            out[-1] = (name, out[-1][1] + (b - a))
        else:
            out.append((name, b - a))
    return out


def reduce(win: Window, top: int = 10) -> Optional[Program]:
    """The program's spans and counters over ``win``; ``None`` when no
    program span lies inside a ``bench_sweep`` span (a program that
    writes none)."""
    rec = win.rec
    per_sweep = [[p for p in win.program if s <= p[1] and p[2] <= e]
                 for _, s, e in rec.spans]
    if not any(per_sweep):
        return None
    n = len(rec.spans)
    host: Dict[str, float] = {}
    counters: Dict[str, float] = {}
    for spans in per_sweep:
        for name, s, e, stats in spans:
            host[name] = host.get(name, 0.0) + (e - s)
            if name == SWEEP:
                for k, v in stats.items():
                    if k != "id" and isinstance(v, (int, float)):
                        counters[k] = counters.get(k, 0.0) + float(v)
    out = Program(n_sweeps=n,
                  host_ms={k: 1e3 * v / n for k, v in host.items()},
                  counters={k: v / n for k, v in counters.items()})
    if not rec.ops:
        return out
    pieces: List[Tuple[str, float]] = []
    gap: Dict[str, float] = {}
    busy = union(rec)
    for sweep, phase, intervals in idle_phases(rec, busy):
        for lo, hi in intervals:
            for name, d in split(win.program, lo, hi):
                key = f"{phase}:{name}"
                gap[key] = gap.get(key, 0.0) + d
                pieces.append((f"{key}@sweep{sweep}", d))
    out.gap_ms = {k: 1e3 * v / n for k, v in
                  sorted(gap.items(), key=lambda g: -g[1])}
    out.idle_gaps = sorted(pieces, key=lambda g: -g[1])[:top]
    return out


def values(p: Optional[Program]) -> Dict[str, Optional[float]]:
    """The six per-layer numbers the program's spans and counters give;
    each ``None`` where the window holds nothing to read it from."""
    host = p.host_ms if p else {}
    counters = p.counters if p else {}
    h2d = counters.get("h2d_bytes")
    return {"host_prepare_ms": host.get("sweep.prepare"),
            "host_dispatch_ms": host.get("sweep.dispatch"),
            "host_wait_ms": host.get("sweep.wait"),
            "host_finalize_ms": host.get("sweep.finalize"),
            "dispatches_per_sweep": counters.get("dispatches"),
            "h2d_mb_per_sweep": None if h2d is None else h2d / 1e6}


def outermost_ops(ops: Sequence[Tuple[int, str, float, float]]):
    """The operations not nested inside another on the same chip (the
    union of intervals is unchanged)."""
    out, reach = [], {}
    for op in sorted(ops, key=lambda o: (o[0], o[2], -o[3])):
        chip, _, s, e = op
        if e > reach.get(chip, -math.inf):
            out.append(op)
            reach[chip] = e
    return out


def excerpt(win: Window, sweeps: Sequence[int]) -> Window:
    """The ``bench_sweep`` spans of ``sweeps``, with the program spans and
    the outermost device operations inside them."""
    spans = [s for s in win.rec.spans if s[0] in set(sweeps)]
    lo, hi = min(s for _, s, _ in spans), max(e for _, _, e in spans)
    ops = [o for o in outermost_ops(win.rec.ops) if o[3] > lo and o[2] < hi]
    program = [p for p in win.program if lo <= p[1] and p[2] <= hi]
    return Window(Recorded(spans, ops), program)


def trace_cell(name: str, seed: int, sweeps: int, directory: str) -> str:
    """Trace ``sweeps`` sweeps of cell ``name`` after one warm sweep, each
    in a ``bench_sweep`` span as the harness times them; returns the
    ``.xplane.pb`` file."""
    import glob
    import os

    import jax

    from bench import harness, spec
    cell = spec.load_cell(name)
    harness.devices_for(cell.chips, require_chip=True)
    harness.use_compile_cache(spec.ROOT)
    sys.path.insert(0, str(spec.ROOT / "src"))
    run = harness.Cellrun(cell, seed)
    run.sweep(run.cells(0))
    jax.profiler.start_trace(directory,
                             profiler_options=harness.profile_options())
    try:
        for index in range(1, sweeps + 1):
            cells = run.cells(index)
            with jax.profiler.TraceAnnotation(tracereduce.SPAN, sweep=index):
                run.sweep(cells)
    finally:
        jax.profiler.stop_trace()
    return max(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                         recursive=True))


def main(argv=None) -> int:
    import argparse
    import shutil
    import tempfile

    from bench import harness, spec
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--sweeps", type=int, default=6)
    ap.add_argument("--record", help="write sweeps 2 and 3 here as JSON")
    args = ap.parse_args(argv)
    directory = tempfile.mkdtemp(prefix="program-spans-")
    try:
        try:
            path = trace_cell(args.workload, args.seed, args.sweeps,
                              directory)
        except harness.NoChip as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        win = load(path)
        chips = spec.load_cell(args.workload).chips
        base = tracereduce.reduce(win.rec, n_chips=chips)
        prog = reduce(win)
        result = {
            "phases_ms": {k: 1e3 * sum(getattr(p, k) for p in base.phases)
                          / len(base.phases)
                          for k in ("prep_s", "gap_s", "loop_s",
                                    "finalize_s")} if base else None,
            "values": values(prog),
            "host_ms": prog.host_ms if prog else None,
            "counters": prog.counters if prog else None,
            "gap_ms": prog.gap_ms if prog else None,
            "idle_gaps": prog.idle_gaps if prog else None,
            "device_ops": base.device_ops if base else None,
        }
        if args.record:
            with open(args.record, "w") as f:
                f.write(excerpt(win, (2, 3)).to_json())
    finally:
        shutil.rmtree(directory, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
