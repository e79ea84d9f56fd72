"""From a profiler trace to the per-layer numbers of a traced window.

The harness wraps every timed ``run_sweep`` call in a host span named
``bench_sweep`` (with the sweep's index), so one trace holds both clocks'
views: the host spans and the operations each chip ran.  A sweep's span
splits, on the device's timeline, into four parts that add up to it:

* ``prep``: span start to the sweep's first device operation (host cell
  prep, validation, staging the inputs);
* ``loop``: the union of the sweep's device operation intervals;
* ``gap``: device idle time between its first and last operation
  (the scheduler's round trips between segments, dispatch);
* ``finalize``: last device operation to span end (device-to-host copies,
  host finalizers).

Busy time is the union of operation intervals per chip, averaged over the
chips; idle gaps are the complement of the union over all chips, each
labelled by where it lies in a sweep, or ``between`` sweeps.
"""
from __future__ import annotations

import json
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

SPAN = "bench_sweep"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]


@dataclass
class Recorded:
    """What the reduction reads: host spans and device operations, in
    seconds on the trace's clock."""
    spans: List[Tuple[int, float, float]]            # (sweep, start, end)
    ops: List[Tuple[int, str, float, float]]         # (chip, name, start, end)

    @classmethod
    def from_json(cls, text: str) -> "Recorded":
        d = json.loads(text)
        return cls([tuple(s) for s in d["spans"]], [tuple(o) for o in d["ops"]])


@dataclass
class Phases:
    sweep: int
    prep_s: float
    loop_s: float
    gap_s: float
    finalize_s: float


@dataclass
class Reduced:
    """The traced window, reduced: what every metric reader is given."""
    n_chips: int
    window_s: float
    busy_s: float                                    # mean over chips
    phases: List[Phases] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)
    device_ops: List[Tuple[str, float]] = field(default_factory=list)


def load_xplane(path: str) -> Recorded:
    """Spans and device operations from a ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    spans, ops = [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                chip = int(m.group(1))
                for e in line.events:
                    ops.append((chip, short_name(e.name), e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9))
            elif not m:
                for e in line.events:
                    if e.name == SPAN:
                        idx = int(dict(e.stats).get("sweep", -1))
                        spans.append((idx, e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9))
    spans.sort(key=lambda s: s[1])
    return Recorded(spans, ops)


def short_name(op: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` → ``fusion.12``."""
    return op.split(" = ", 1)[0].lstrip("%")


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in merged if e > lo and s < hi]


def length(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def reduce(rec: Recorded, n_chips: int, top: int = 10) -> Optional[Reduced]:
    """The per-sweep phases, busy time and breakdown of a traced window;
    ``None`` when the trace holds no span or no device operation."""
    if not rec.spans or not rec.ops:
        return None
    lo = min(s for _, s, _ in rec.spans)
    hi = max(e for _, _, e in rec.spans)
    per_chip: Dict[int, List[Interval]] = {}
    op_time: Dict[str, float] = {}
    for chip, name, s, e in rec.ops:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            per_chip.setdefault(chip, []).append((s, e))
            op_time[name] = op_time.get(name, 0.0) + (e - s)
    if not per_chip:
        return None
    busy = sum(length(merge(iv)) for iv in per_chip.values()) / n_chips
    union = merge([iv for ivs in per_chip.values() for iv in ivs])
    out = Reduced(n_chips=n_chips, window_s=hi - lo, busy_s=busy)
    gaps: List[Tuple[str, float]] = []
    for i, (sweep, s, e) in enumerate(rec.spans):
        pieces = clip(union, s, e)
        if pieces:
            first, last = pieces[0][0], pieces[-1][1]
            loop = length(pieces)
            out.phases.append(Phases(sweep, first - s, loop,
                                     (last - first) - loop, e - last))
            gaps.append((f"prep@sweep{sweep}", first - s))
            gaps += [(f"scheduler@sweep{sweep}", b[0] - a[1])
                     for a, b in zip(pieces, pieces[1:])]
            gaps.append((f"finalize@sweep{sweep}", e - last))
        else:
            gaps.append((f"no_device_op@sweep{sweep}", e - s))
        if i + 1 < len(rec.spans):
            nxt = rec.spans[i + 1][1]
            between = (nxt - e) - length(clip(union, e, nxt))
            gaps.append((f"between@sweep{sweep}", between))
    out.idle_gaps = sorted(gaps, key=lambda g: -g[1])[:top]
    out.device_ops = sorted(((k, v / n_chips) for k, v in op_time.items()),
                            key=lambda o: -o[1])[:top]
    return out
