"""One run of one cell: set-up, a closed-loop measured window, and the
comparison with the plain reference.

The window stands for one caller of a policy search: it calls
``repro.core.backend.run_sweep`` back to back, each call on a fresh
population drawn from ``--seed`` and the sweep's index, and each call
returns host-side outputs before the next starts.  Every shape is
compiled and run once before the window opens, so nothing compiles in
it; a ``jax.monitoring`` listener counts what does.
"""
from __future__ import annotations

import gc
import glob
import math
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from . import check, spec, traffic, tracereduce

WARM_SWEEPS = 1                 # sweep index 0 compiles; the window starts at 1
COMPILE = "/jax/core/compile/backend_compile_duration"
TRACE = "/jax/core/compile/jaxpr_trace_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class Counter:
    """Backend compilations, persistent-cache hits and jaxpr traces seen by
    ``jax.monitoring``.  A cache hit is also reported as a backend
    compilation (the load from the cache), so what was compiled afresh is
    ``compiles - cache_hits``."""

    def __init__(self) -> None:
        import jax
        self.compiles = self.cache_hits = self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        jax.monitoring.register_event_listener(self._event)

    def _listen(self, event: str, duration: float, **_) -> None:
        if event == COMPILE:
            self.compiles += 1
        elif event == TRACE:
            self.traces += 1

    def _event(self, event: str, **_) -> None:
        if event == CACHE_HIT:
            self.cache_hits += 1


@dataclass
class Window:
    """What the measured window saw."""
    durations: List[float] = field(default_factory=list)
    starts: List[float] = field(default_factory=list)
    full_gcs: List[Tuple[float, float]] = field(default_factory=list)
    events: int = 0
    seconds: float = 0.0
    failed: int = 0
    compiles: int = 0
    traces: int = 0
    report: Dict[str, Any] = field(default_factory=dict)
    kept_cells: List[Dict[str, np.ndarray]] = field(default_factory=list)
    kept_outputs: List[Dict[str, np.ndarray]] = field(default_factory=list)


def devices_for(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def use_compile_cache(root) -> str:
    """JAX's persistent cache at a fixed path inside the checkout, holding
    every program however fast it compiled.  Eviction stays off whatever
    the environment asks: it reads an access-time file per entry and fails
    every write once one entry lacks it."""
    import jax
    path = root / "bench" / ".jax-cache"
    path.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return str(path)


def profile_options():
    """Host spans and device operations only: no Python function tracer
    (it would slow the host prep it measures) and no HLO protos."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


class Cellrun:
    """The cell's inputs and the program's entry, for one process."""

    def __init__(self, cell: spec.Cell, seed: int, overrides=None,
                 program: Optional[Callable] = None) -> None:
        from repro.core.backend import run_sweep
        from repro.core.sweep import SweepConfig
        self.cell, self.seed = cell, int(seed)
        self.params = dict(cell.config["params"], **(overrides or {}))
        self.ref = cell.reference()
        self.cell_keys = self.ref.CELL_KEYS
        self.statics = {k: v for k, v in self.params.items()
                        if k not in self.cell_keys}
        self.n = traffic.n_cells(cell.traffic)
        self.config = SweepConfig(devices=cell.chips,
                                  **cell.traffic.get("sweep", {}))
        self.kind = cell.config["kind"]
        self.program = program or run_sweep

    def cells(self, index: int) -> Dict[str, np.ndarray]:
        """Every per-cell array of sweep ``index``, the configuration's
        scalars broadcast where the reference wants one per cell."""
        cells = traffic.sweep_cells(self.cell.traffic, self.params,
                                    self.seed, index)
        for k in self.cell_keys:
            if k not in cells:
                cells[k] = np.full(self.n, self.params[k])
        return cells

    def sweep(self, cells: Dict[str, np.ndarray]):
        return self.program(self.kind, dict(self.statics, **cells),
                            config=self.config)

    def sound(self, cells: Dict[str, np.ndarray],
              out: Dict[str, Any]) -> bool:
        """Every lane ran as many loop steps as the reference says its cell
        takes, and no float output is NaN."""
        it = np.asarray(out.get("iterations", ()))
        if it.shape != (self.n,) or np.any(
                it != self.ref.events(cells, **self.params)):
            return False
        return not any(np.isnan(np.asarray(v)).any() for v in out.values()
                       if np.asarray(v).dtype.kind == "f")


def measure(run: Cellrun, seconds: float, counter: Counter) -> Window:
    import jax
    win = Window()
    keep = int(run.cell.traffic.get("check_lanes", 2))
    c0, t0_traces = counter.compiles, counter.traces
    gc_start = []

    def full_gc(phase: str, info: Dict[str, Any]) -> None:
        if info["generation"] == 2:
            now = time.perf_counter()
            if phase == "start":
                gc_start[:] = [now]
            elif gc_start:
                win.full_gcs.append((gc_start[0], now - gc_start[0]))
    gc.callbacks.append(full_gc)
    start = time.perf_counter()
    index = WARM_SWEEPS
    while time.perf_counter() - start < seconds:
        cells = run.cells(index)
        with jax.profiler.TraceAnnotation(tracereduce.SPAN, sweep=index):
            t0 = time.perf_counter()
            try:
                res = run.sweep(cells)
                out = res.outputs
            except Exception as e:          # count it; the check fails it
                print(f"bench: sweep {index} raised {e!r}", file=sys.stderr)
                out = None
            t1 = time.perf_counter()
        win.durations.append(t1 - t0)
        win.starts.append(t0)
        if out is None or not run.sound(cells, out):
            win.failed += 1
        else:
            win.events += int(np.sum(np.asarray(out["iterations"],
                                                np.int64)))
            win.report = res.report_fields()
            lanes = traffic.rng_for(run.seed, index, 1).choice(
                run.n, keep, replace=False)
            win.kept_cells.append({k: v[lanes] for k, v in cells.items()})
            win.kept_outputs.append({k: np.asarray(v)[lanes]
                                     for k, v in out.items()})
        index += 1
    win.seconds = time.perf_counter() - start
    gc.callbacks.remove(full_gc)
    win.compiles = counter.compiles - c0
    win.traces = counter.traces - t0_traces
    return win


def compare(run: Cellrun, win: Window, control: bool = False
            ) -> Tuple[float, Dict[str, float], int]:
    """``(max_rel_gap, per-output gaps, lanes)`` over a sample, drawn from
    the seed, of the lanes kept in the window.  ``control`` puts the
    reference in the lower precision in the program's place."""
    if not win.kept_cells:
        return check.INF, {}, 0
    cells = {k: np.concatenate([c[k] for c in win.kept_cells])
             for k in win.kept_cells[0]}
    prog = {k: np.concatenate([o[k] for o in win.kept_outputs])
            for k in win.kept_outputs[0]}
    total = len(cells["seeds"])
    cap = int(run.cell.config["check"]["lanes"])
    pick = np.sort(traffic.rng_for(run.seed, 2 ** 32 - 1).choice(
        total, min(cap, total), replace=False))
    cells = {k: v[pick] for k, v in cells.items()}
    prog = {k: v[pick] for k, v in prog.items()}
    if control:
        prog = run.ref.simulate(cells, dtype=np.float32, **run.statics)
    ref = run.ref.simulate(cells, **run.statics)
    gap, gaps = check.compare(prog, ref)
    return gap, gaps, len(pick)


def read_trace(directory: str, n_chips: int):
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return None
    return tracereduce.reduce(tracereduce.load_xplane(max(files)), n_chips)


def run(name: str, seed: int, seconds: float, trace: bool, *, t_start: float,
        require_chip: bool = True, overrides=None, program=None,
        control: bool = False, root=spec.ROOT) -> Dict[str, Any]:
    """One run of cell ``name``; returns the result line's object.  Raises
    :class:`NoChip` before any work where the chips are missing."""
    cell = spec.load_cell(name, root)
    import jax
    t_jax = time.perf_counter()
    devs = devices_for(cell.chips, require_chip)
    d0 = devs[0]
    print(f"bench: device platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devs)}", flush=True)
    print(f"bench: compile cache {use_compile_cache(root)}", flush=True)
    sys.path.insert(0, str(root / "src"))
    counter = Counter()
    t_dev = time.perf_counter()
    runner = Cellrun(cell, seed, overrides, program)
    t_prog = time.perf_counter()
    for index in range(WARM_SWEEPS):
        runner.sweep(runner.cells(index))
    t_warm = time.perf_counter()
    setup_s = t_warm - t_start
    print(f"bench: setup_s={setup_s!r} compiles={counter.compiles} "
          f"cache_hits={counter.cache_hits} traces={counter.traces}",
          flush=True)
    print(f"bench: setup_parts import_jax_s={t_jax - t_start!r} "
          f"devices_s={t_dev - t_jax!r} import_program_s={t_prog - t_dev!r} "
          f"warm_sweeps_s={t_warm - t_prog!r}", flush=True)

    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(tdir, profiler_options=profile_options())
    try:
        win = measure(runner, seconds, counter)
    finally:
        if trace:
            jax.profiler.stop_trace()
    stats = [d.memory_stats() or {} for d in devs]
    peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    print(f"bench: window sweeps={len(win.durations)} failed={win.failed} "
          f"events={win.events} window_s={win.seconds!r} "
          f"compiles_in_window={win.compiles} "
          f"traces_in_window={win.traces}", flush=True)
    if win.durations:
        d = win.durations
        print(f"bench: sweep_s first={d[0]!r} min={min(d)!r} "
              f"median={float(np.median(d))!r} max={max(d)!r}", flush=True)
        slow = [(i, round(t0 - t_start, 3), round(x, 3)) for i, (t0, x)
                in enumerate(zip(win.starts, d)) if x > 2 * np.median(d)]
        print(f"bench: slow sweeps (index, start_s since process start, "
              f"seconds) {slow}", flush=True)
    print(f"bench: full gc (start_s, seconds) "
          f"{[(round(t - t_start, 3), round(x, 3)) for t, x in win.full_gcs]}",
          flush=True)
    print(f"bench: report {win.report}", flush=True)

    reduced = None
    if trace:
        try:
            reduced = read_trace(tdir, len(devs))
        finally:
            shutil.rmtree(tdir, ignore_errors=True)

    gap, gaps, lanes = compare(runner, win, control)
    limit = float(cell.config["check"]["limits"]["max_rel_gap"])
    worst = max(gaps, key=gaps.get) if gaps else None
    print(f"bench: check lanes={lanes} worst_output={worst} "
          f"gaps={ {k: v for k, v in gaps.items() if v} }", flush=True)
    correct = bool(win.failed == 0 and lanes > 0 and gap <= limit)

    metrics: Dict[str, Dict[str, Any]] = {}
    if trace:
        breakdown = None
        if reduced is not None:
            for m in cell.per_layer:
                value = cell.reader(m["name"]).read(reduced)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            breakdown = {"device_ops": [list(o) for o in reduced.device_ops],
                         "idle_gaps": [list(g) for g in reduced.idle_gaps]}
    else:
        values = {"setup_s": setup_s,
                  "events_per_s": win.events / win.seconds}
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    result = {"correct": correct, "attempted": len(win.durations),
              "failed": win.failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reduced.busy_s if reduced else 0.0
        device["window_s"] = reduced.window_s if reduced else win.seconds
        if breakdown:
            result["breakdown"] = breakdown
    # JSON has no infinity: a gap that cannot be measured reads as the
    # largest double.
    shown = gap if math.isfinite(gap) else sys.float_info.max
    result["check"] = {"max_rel_gap": {"value": shown, "limit": limit}}
    return result
