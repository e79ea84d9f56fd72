"""Sweep execution layer — chunked, sharded, divergence-bucketed batch runs.

CloudSim 7G's headline results are run-time and memory wins from a
re-engineered core; our counterpart hot path is the vec substrate's batched
sweeps.  Before this layer each vec engine dispatched its whole scenario
grid as **one** ``jit(vmap(...))`` call on **one** device: memory scaled
with the full grid, and — because a ``vmap``-ed ``lax.while_loop`` iterates
until the *slowest* lane's predicate clears — every lane paid for the
longest lane (measured active-lane fraction ~0.54 on the committed fleet
sweep).  This module is the one place all batched entry points now route
through (``vec_cluster.simulate_fleet_batch``, ``vec_workflow
.simulate_specs``, ``vec_scheduler.simulate_cells``, and the consolidation
driver's host-looped cell batches):

  * **chunked execution** — the cell axis is split into fixed-size chunks
    dispatched sequentially, so device memory is bounded by ``chunk_size``
    lanes and sweeps larger than device memory stream through.  Lanes are
    independent under ``vmap``, so chunked results are **bit-identical** to
    the monolithic call (asserted by tests); the last chunk is padded by
    repeating its final cell so every dispatch reuses one compiled shape.
  * **divergence bucketing** — with a ``predicted_cost`` per cell (steps,
    expected failure-rollback work, DAG size), cells are sorted by
    predicted length before chunking, so short lanes ride with short lanes
    instead of idling behind the grid's longest cell.  The permutation is
    undone on output; per-lane results are unchanged — only co-residency
    changes.
  * **device sharding** — each chunk's lanes are split across
    ``jax.devices()`` by a jitted ``shard_map`` over a 1-D lane mesh
    (:func:`lanes_sharding`; chunks padded to a device multiple), with a
    plain single-device ``jit`` otherwise; results are bit-identical
    either way.
  * **lane compaction** — :func:`compact_sweep` keeps a fixed-size dense
    resident batch and retires/refills lanes mid-flight from a host work
    queue, streaming finished cells to an ``on_chunk`` consumer; device
    memory is O(lanes) and the active-lane fraction approaches 1 by
    construction (see ARCHITECTURE.md, "Streaming sweeps and the
    compacting scheduler").
  * **buffer donation** — chunk inputs are donated (``donate_argnums``) so
    XLA may reuse their buffers for the chunk's outputs/temporaries instead
    of holding both live across the stream of chunks.
  * **divergence accounting** — when the engine reports per-lane loop
    ``iterations``, the :class:`SweepReport` records the active-lane
    fraction actually executed (Σ lane iters / Σ chunk-max × lanes) next to
    the fraction a monolithic dispatch would have achieved, plus the
    device count and chunk size — benchmarks persist these in the BENCH
    JSONs and ``check_regression.py`` compares like-for-like device counts.

The exactness contract is strict: chunking, bucketing, and sharding are
*schedules* over independent lanes — none of them may change a single
output bit relative to the monolithic call (see ARCHITECTURE.md, "Sweep
execution layer").
"""
from __future__ import annotations

import dataclasses
import difflib
import functools
import re
import warnings
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Sequence

import numpy as np

from .spans import span

# jax is imported lazily inside the executors: ``repro.core`` re-exports
# :class:`SweepReport`, and importing the core package must stay light
# (the substrate contract — vec engines themselves load lazily too).

MIN_CHUNK = 16          # smaller dispatches are dominated by fixed overhead
_DIVERGENCE_SPREAD = 1.05   # predicted max/min above this ⇒ bucketing pays

# XLA warns when a donated input cannot be aliased into an output (common:
# i32 params vs f64 outputs).  Donation is best-effort by design; silence
# just that warning, not the user's.
_DONATION_MSG = re.compile(r"[Ss]ome donated buffers were not usable")


@dataclass(frozen=True)
class SweepReport:
    """How one sweep was executed, and how well its lanes stayed busy."""
    n_cells: int
    chunk_size: int
    n_chunks: int
    devices: int
    bucketed: bool
    donated: bool
    # Σ lane iterations / Σ_chunks (chunk max iterations × chunk lanes) —
    # the fraction of executed vmap-lane-iterations doing real work under
    # the schedule actually run (1.0 = no lane ever idled), measured from
    # the *observed* per-lane iteration counts.
    active_lane_fraction: Optional[float] = None
    # Same statistic had the whole grid run as one dispatch — the
    # divergence a monolithic vmap(while_loop) suffers on this grid.
    active_lane_fraction_monolithic: Optional[float] = None
    lane_iterations: Optional[np.ndarray] = None
    # The fraction the scheduler *expected* under the same chunk schedule,
    # using predicted_cost as the iteration proxy — the gap between this
    # and the observed fraction is the cost model's error.
    active_lane_fraction_predicted: Optional[float] = None
    # Multi-device executor ("shard_map"); None when the dispatch ran on a
    # single device.
    sharding: Optional[str] = None
    # Compacting-scheduler accounting (``compact_sweep``): lanes retired
    # mid-flight, lanes refilled from the work queue, compiled segments
    # dispatched, and the peak number of concurrently live lanes.
    compacted: bool = False
    refills: int = 0
    retires: int = 0
    segments: int = 0
    peak_lanes: int = 0
    # Self-robustness accounting (``compact_sweep(..., quarantine=True)``):
    # lanes whose state or outputs went NaN are quarantined — retired
    # without results (their cells listed in ``quarantined_cells``, float
    # outputs NaN-filled) so the rest of the grid streams on; a raising
    # segment is re-dispatched once from host snapshots before giving up.
    quarantined: int = 0
    retried_segments: int = 0
    quarantined_cells: Optional[np.ndarray] = None
    # Bytes of the host (numpy) arrays handed to the sweep's executable
    # calls or put on the device for them, summed: what the sweep copied
    # host to device.
    h2d_bytes: int = 0
    # Copies of the lane-params pytree from host to device: one per chunk
    # on the chunked path; on the compacting path one, plus one for each
    # segment that follows a refill.
    param_uploads: int = 0
    # Bytes of the plan's sweep-wide operand (``shared``) put on the
    # device: once a sweep, whatever the chunks, segments and refills;
    # counted in ``h2d_bytes`` too.  0 for a plan without one.
    shared_bytes: int = 0

    @property
    def dispatches(self) -> int:
        """Executable calls the sweep made: its chunks or segments, and
        the segments a quarantine retried."""
        return self.n_chunks + self.retried_segments

    @property
    def active_lane_fraction_observed(self) -> Optional[float]:
        """Alias: the observed fraction benches and gates key on."""
        return self.active_lane_fraction

    def report_fields(self) -> Dict[str, Any]:
        """The uniform schedule slice every consumer records — BENCH JSONs,
        example printers, the perf gate — so any record reads the same way.

        ``observed_active_lane_fraction`` is the gated occupancy figure —
        actual lane-iterations over dispatched lane-iterations — as opposed
        to the cost model's prediction
        (``active_lane_fraction_predicted``)."""
        return dict(
            devices=self.devices, chunk_size=self.chunk_size,
            n_chunks=self.n_chunks, bucketed=self.bucketed,
            donated=self.donated, sharding=self.sharding,
            compacted=self.compacted, refills=self.refills,
            retires=self.retires, segments=self.segments,
            peak_lanes=self.peak_lanes, quarantined=self.quarantined,
            retried_segments=self.retried_segments,
            h2d_bytes=self.h2d_bytes, param_uploads=self.param_uploads,
            shared_bytes=self.shared_bytes,
            observed_active_lane_fraction=(
                round(self.active_lane_fraction_observed, 4)
                if self.active_lane_fraction_observed is not None else None),
            active_lane_fraction_predicted=(
                round(self.active_lane_fraction_predicted, 4)
                if self.active_lane_fraction_predicted is not None else None),
        )


@dataclass(frozen=True)
class SweepConfig:
    """How to *schedule* a sweep — every control knob the batched entry
    points accept, separated from the scenario's own parameters.

    ``run_sweep(kind, params, config=SweepConfig(...))`` is the typed entry
    point; each field maps 1:1 onto the uniform controls every
    :func:`repro.core.vec_engine.make_batch_entry` entry takes:

      * ``compact`` — route through the compacting lane scheduler
        (O(chunk) device memory, streaming retires, bit-identical);
      * ``chunk_size`` — lanes per dispatch (compact: resident lane count);
      * ``segment_iters`` — compact-mode per-segment iteration budget;
      * ``devices`` — ``None``/"auto" = all local, int n = first n, or an
        explicit placement list;
      * ``on_chunk`` / ``progress`` — streaming consumers;
      * ``precision`` — ``"exact"`` (bit-identical f64) or ``"fast"`` (f32
        loop) where the engine offers the opt-in; ``None`` defers to the
        engine default;
      * ``use_pallas`` — fused next-event kernel opt-in (``True`` /
        ``"force"``);
      * ``donate`` — donate chunk input buffers to XLA;
      * ``quarantine`` — compact-mode self-robustness: NaN'd lanes are
        quarantined (``SweepReport.quarantined``) instead of poisoning
        the run, and a raising segment is retried once.

    Only fields that differ from their defaults are forwarded to the
    handler (:meth:`to_kwargs`), so a default config adds nothing to any
    signature — handlers without e.g. a ``precision`` parameter never see
    the key.
    """

    compact: bool = False
    chunk_size: Optional[int] = None
    segment_iters: Optional[int] = None
    devices: Any = None
    on_chunk: Optional[Callable] = None
    progress: Optional[Callable] = None
    precision: Optional[str] = None
    use_pallas: Any = False
    donate: bool = True
    quarantine: bool = False

    def __post_init__(self):
        if self.precision not in (None, "exact", "fast"):
            raise ValueError(
                f"precision must be None, 'exact' or 'fast': "
                f"{self.precision!r}")
        for name in ("chunk_size", "segment_iters"):
            v = getattr(self, name)
            if v is not None and int(v) < 1:
                raise ValueError(f"{name} must be ≥ 1: {v!r}")

    @classmethod
    def field_names(cls) -> tuple:
        return tuple(f.name for f in dataclasses.fields(cls))

    @classmethod
    def from_kwargs(cls, **kwargs: Any) -> "SweepConfig":
        """Build a config from loose control kwargs (the legacy-shim path),
        rejecting unknown keys with a did-you-mean suggestion."""
        names = cls.field_names()
        unknown = sorted(set(kwargs) - set(names))
        if unknown:
            hints = []
            for k in unknown:
                close = difflib.get_close_matches(k, names, n=1, cutoff=0.6)
                hints.append(f"{k!r}" + (f" (did you mean {close[0]!r}?)"
                                         if close else ""))
            raise TypeError(
                f"SweepConfig got unknown field(s): {', '.join(hints)}; "
                f"valid fields: {', '.join(names)}")
        return cls(**kwargs)

    def to_kwargs(self) -> Dict[str, Any]:
        """The non-default fields, as the uniform control kwargs every
        batched entry point accepts — defaults are omitted so handlers
        only ever see knobs the caller actually set."""
        out = {}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if v is not f.default and v != f.default:
                out[f.name] = v
        return out

    def replace(self, **changes: Any) -> "SweepConfig":
        return dataclasses.replace(self, **changes)


def resolve_devices(devices: Any = None) -> Sequence[Any]:
    """``None``/"auto" → all local devices; int n → first n; list → as-is."""
    import jax
    if devices is None or devices == "auto":
        return jax.devices()
    if isinstance(devices, int):
        avail = jax.devices()
        if not 1 <= devices <= len(avail):
            raise ValueError(
                f"devices={devices} requested, {len(avail)} available")
        return avail[:devices]
    return list(devices)


def auto_chunk_size(n_cells: int, predicted_cost, n_devices: int) -> int:
    """Default chunking policy.

    Chunking only pays when lanes diverge (a vmapped ``while_loop`` runs
    every lane to the chunk's max iteration count): with no cost spread
    predicted (all-equal costs included) — or too few cells to form several
    chunks — run monolithic.  Otherwise target ~8 chunks, floored at
    ``MIN_CHUNK`` lanes per device, and *balance* the split: the chunk count
    is fixed first and cells divided evenly across it, so the final chunk is
    never left nearly empty (almost-all-pad dispatch waste).  ``n_devices``
    is clamped to ``[1, n_cells]`` — a grid smaller than the device fleet
    must not be rounded up to a chunk that is mostly padding.
    """
    n_devices = max(1, min(int(n_devices), max(int(n_cells), 1)))
    if predicted_cost is None or n_cells < 2 * MIN_CHUNK * n_devices:
        return n_cells
    pred = np.asarray(predicted_cost, np.float64)
    # Zero-cost lanes (an empty trace slice, a zero-job cell) say nothing
    # about divergence among the lanes that do run — measure the spread
    # over the positive entries only, and go monolithic only when there
    # are none (or they genuinely don't diverge).
    pos = pred[pred > 0]
    if pos.size == 0 or float(pos.max()) / float(pos.min()) <= \
            _DIVERGENCE_SPREAD:
        return n_cells
    raw = max(MIN_CHUNK * n_devices, n_cells // 8)
    n_chunks = max(1, n_cells // raw)
    chunk = -(-n_cells // n_chunks)                      # balanced split
    chunk = int(-(-chunk // n_devices) * n_devices)      # device multiple
    return n_cells if chunk >= n_cells else chunk


def lanes_sharding(devices: Sequence[Any]):
    """The lane axis split over ``devices``: a 1-D ``lanes`` mesh, the
    placement every multi-device ``shard_map`` executor takes its lane
    arguments in."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    return NamedSharding(Mesh(np.array(list(devices)), ("lanes",)),
                         PartitionSpec("lanes"))


def _put_shared(shared, devices: Sequence[Any]):
    """A plan's sweep-wide operand put on the device, once a sweep:
    ``(the executables' trailing arguments, bytes sent)``.  Replicated over
    the ``lanes`` mesh of several devices, else on the one device (the
    default one where ``devices`` is empty); ``((), 0)`` without one."""
    if shared is None:
        return (), 0
    import jax
    if len(devices) > 1:
        from jax.sharding import NamedSharding, PartitionSpec
        where = NamedSharding(lanes_sharding(devices).mesh, PartitionSpec())
    else:
        where = devices[0] if devices else None
    with span("sweep.stage"):
        return (jax.device_put(shared, where),), _host_bytes(shared)


@functools.lru_cache(maxsize=64)
def _executor(fn: Callable, devices: tuple, donate: bool,
              shared: bool = False) -> Callable:
    """Compiled dispatcher for one (engine fn, device placement) pair.

    ``fn`` takes a single params pytree with a leading lane axis, and with
    ``shared`` the plan's sweep-wide operand as a second argument, which
    has none; the engines hand us a per-statics-cached callable so this
    cache keys on a stable object.  Multi-device wraps it in a jitted
    ``shard_map`` over the 1-D ``lanes`` mesh of exactly the given devices
    (an explicit ``devices=`` list is a *placement*, not just a count);
    the lane axis stays flat and the shared operand is replicated.  Both
    paths donate the chunk's lane inputs when asked, never the shared
    operand.
    """
    import jax
    donate_argnums = (0,) if donate else ()
    if len(devices) > 1:
        from jax.sharding import PartitionSpec
        sh = lanes_sharding(devices)
        specs = (sh.spec, PartitionSpec()) if shared else (sh.spec,)
        # check_vma=False: lanes are independent; the shared operand is
        # only read.
        lanes = jax.shard_map(fn, mesh=sh.mesh, in_specs=specs,
                              out_specs=sh.spec, check_vma=False)
        return jax.jit(lanes, donate_argnums=donate_argnums)
    jitted = jax.jit(fn, donate_argnums=donate_argnums)
    if devices[0] == jax.devices()[0]:
        return jitted                       # default placement: nothing to do

    def on_device(params, *shared_dev):
        return jitted(jax.device_put(params, devices[0]), *shared_dev)
    return on_device


def _take(params, idx: np.ndarray):
    """Gather cells ``idx`` along every leaf's leading axis (host side)."""
    import jax
    with span("sweep.stage"):
        return jax.tree_util.tree_map(
            lambda leaf: np.take(np.asarray(leaf), idx, axis=0), params)


def _host_bytes(*trees) -> int:
    """Bytes of the host (numpy) leaves of ``trees``: what a dispatch of
    them copies to the device (device-resident leaves copy nothing)."""
    import jax
    return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(trees)
               if isinstance(leaf, np.ndarray))


def _dispatch(executor, chunk_params, *shared_dev):
    """Run one chunk and bring its outputs back to the host."""
    with span("sweep.dispatch"):
        out = executor(chunk_params, *shared_dev)
    with span("sweep.wait"):
        return {k: np.asarray(v) for k, v in out.items()}


def execute_sweep(fn: Callable[[Any], Dict[str, Any]], params: Any, *,
                  chunk_size: Optional[int] = None,
                  devices: Any = None,
                  predicted_cost=None,
                  donate: bool = True,
                  iterations_key: str = "iterations",
                  on_chunk: Optional[Callable] = None,
                  shared: Any = None,
                  ):
    """Execute a vmapped simulation over its cell axis in scheduled chunks.

    (The engine-facing executor; the scenario-level entry point with the
    same report contract is :func:`repro.core.backend.run_sweep`.)

    ``fn(params) -> dict of arrays`` must be a vmapped engine whose every
    input leaf and output array carries the cell axis first, with lanes
    fully independent (the vec engines' contract).  Returns
    ``(outputs, SweepReport)`` where ``outputs`` concatenates all chunks
    back into original cell order — bit-identical to ``fn(params)`` run
    monolithically.

    ``chunk_size=None`` applies :func:`auto_chunk_size` (monolithic unless
    ``predicted_cost`` shows divergence); ``devices=None`` uses all local
    devices (an explicit list is honored as the placement).
    ``predicted_cost`` (one float per cell) buckets cells by predicted
    length so short lanes don't idle behind long ones.  Several devices
    split each chunk's lanes by ``shard_map``, bit-identical to
    single-device dispatch.  ``on_chunk(cells,
    outputs)`` streams each finished chunk to the consumer as it completes
    (original cell indices + that chunk's raw output dict) instead of
    making it wait for the monolithic return.

    ``shared`` (optional) is a pytree with no cell axis that every lane
    reads: ``fn(params, shared)`` then takes it unbatched.  It goes to the
    device once (replicated over the devices) and rides beside every
    chunk, padded ones included; it is never gathered, donated or sent
    again (``SweepReport.shared_bytes``).
    """
    import jax
    leaves = jax.tree_util.tree_leaves(params)
    if not leaves:
        raise ValueError("execute_sweep: params pytree has no array leaves")
    n_cells = int(np.shape(leaves[0])[0])
    devs = tuple(resolve_devices(devices))
    has_shared = shared is not None
    if n_cells == 0:
        # Degenerate grid: one empty dispatch preserves the monolithic
        # contract (empty per-key outputs) instead of crashing.
        shared_dev, _ = _put_shared(shared, devs[:1])
        out = _dispatch(_executor(fn, devs[:1], donate, has_shared), params,
                        *shared_dev)
        return out, SweepReport(
            n_cells=0, chunk_size=0, n_chunks=0, devices=1, bucketed=False,
            donated=donate)
    devs = devs[:n_cells] if len(devs) > n_cells else devs
    n_dev = len(devs)
    if chunk_size is None:
        chunk_size = auto_chunk_size(n_cells, predicted_cost, n_dev)
    chunk_size = max(1, min(int(chunk_size), n_cells))
    # Shards must split evenly: round the chunk up to a device multiple.
    chunk_size = -(-chunk_size // n_dev) * n_dev

    bucketed = predicted_cost is not None and chunk_size < n_cells
    if bucketed:
        pred = np.asarray(predicted_cost, np.float64)
        if pred.shape != (n_cells,):
            raise ValueError(
                f"predicted_cost shape {pred.shape} != ({n_cells},)")
        order = np.argsort(-pred, kind="stable")     # longest lanes together
    else:
        order = np.arange(n_cells)

    executor = _executor(fn, devs, donate, has_shared)
    chunks, chunk_meta = [], []
    shared_dev, shared_bytes = _put_shared(shared, devs)
    h2d, uploads = shared_bytes, 0
    with warnings.catch_warnings():
        if donate:
            warnings.filterwarnings("ignore", message=_DONATION_MSG.pattern)
        for lo in range(0, n_cells, chunk_size):
            idx = order[lo:lo + chunk_size]
            real = len(idx)
            if real < chunk_size:                    # pad: repeat final cell
                idx = np.concatenate(
                    [idx, np.full(chunk_size - real, idx[-1], idx.dtype)])
            if chunk_size == n_cells:
                # One chunk of every cell in order: the gather would be a
                # copy of ``params``.  The caller's host arrays are handed
                # over as they are; donation consumes only the device copy.
                chunk_params = jax.tree_util.tree_map(np.asarray, params)
            else:
                chunk_params = _take(params, idx)
            h2d += _host_bytes(chunk_params)
            uploads += 1
            out = _dispatch(executor, chunk_params, *shared_dev)
            chunks.append({k: v[:real] for k, v in out.items()})
            chunk_meta.append(real)
            if on_chunk is not None:
                on_chunk(idx[:real].copy(),
                         {k: v[:real].copy() for k, v in out.items()})

    inv = np.argsort(order, kind="stable")
    outputs = {k: np.concatenate([c[k] for c in chunks])[inv]
               for k in chunks[0]}

    spans = list(zip(range(0, n_cells, chunk_size), chunk_meta))

    def _schedule_fraction(per_lane) -> Optional[float]:
        """Σ real work / Σ_chunks (chunk max × chunk lanes) for one
        per-lane work estimate, under the schedule actually run."""
        per_lane = np.asarray(per_lane, np.float64)
        if per_lane.shape != (n_cells,) or per_lane.max() <= 0:
            return None
        ordered = per_lane[order]
        executed = sum(float(ordered[lo:lo + chunk_size].max()) * real
                       for lo, real in spans)
        return float(per_lane.sum()) / executed if executed > 0 else None

    frac = frac_mono = lane_iters = None
    if iterations_key in outputs:
        lane_iters = np.asarray(outputs[iterations_key], np.int64)
        if lane_iters.shape == (n_cells,) and lane_iters.max() > 0:
            frac = _schedule_fraction(lane_iters)
            frac_mono = (int(lane_iters.sum())
                         / (int(lane_iters.max()) * n_cells))
    frac_pred = (_schedule_fraction(predicted_cost)
                 if predicted_cost is not None else None)
    report = SweepReport(
        n_cells=n_cells, chunk_size=chunk_size,
        n_chunks=len(chunk_meta), devices=n_dev, bucketed=bucketed,
        donated=donate, active_lane_fraction=frac,
        active_lane_fraction_monolithic=frac_mono,
        lane_iterations=lane_iters,
        active_lane_fraction_predicted=frac_pred,
        sharding="shard_map" if n_dev > 1 else None, h2d_bytes=h2d,
        param_uploads=uploads, shared_bytes=shared_bytes)
    return outputs, report


def compact_sweep(step: Callable, params: Any, *,
                  lanes: int,
                  state_prototype: Any,
                  devices: Sequence[Any] = (),
                  predicted_cost=None,
                  on_chunk: Optional[Callable] = None,
                  iterations_key: str = "iterations",
                  donated: bool = True,
                  max_segments: Optional[int] = None,
                  quarantine: bool = False,
                  shared: Any = None):
    """Compacting lane scheduler: a dense resident batch of ``lanes`` lanes,
    refilled from a host-side work queue as lanes finish mid-flight.

    ``step(lane_params, state, it, fresh) -> (state, it, done, j, out)`` is
    a compiled *segment*: it merges fresh lanes' initial state over the
    resident state, advances every lane's event loop by at most a fixed
    iteration budget, and reports which lanes' loops have terminated
    (``done``), how many iterations this segment executed per lane (``j``),
    and each lane's finalized outputs (``out`` — only meaningful where
    ``done``).  The vec engines build it via
    :func:`repro.core.vec_engine.segment_step`.

    The host loop retires ``done`` lanes (scattering their outputs into the
    per-cell result arrays and streaming them to ``on_chunk(cells,
    outputs)``), refills the freed slots with the next cells from the work
    queue — longest-predicted-first, so stragglers start early — and
    re-dispatches.  Device memory is O(``lanes``), independent of the grid
    size, and the compiled batch is always dense: the active-lane fraction
    approaches 1 by construction instead of depending on how well
    ``predicted_cost`` ordered the grid.

    The resident batch's lane params live on the device for the whole
    sweep: put there once before the first segment (split over the
    ``lanes`` mesh of ``devices`` when there are several, the placement
    the sharded step takes), and sent again only for a segment that
    follows a refill, which rewrites their host mirror.  When the batch
    holds every cell in order, the mirror is ``params`` itself, not a
    gathered copy.  ``SweepReport.param_uploads`` counts the copies.
    ``devices`` are the devices the step's lanes are sharded over; empty
    or one means the default device.  A plan's sweep-wide ``shared``
    operand (no cell axis) is put on the device once, replicated, before
    the first segment, and handed to every call as
    ``step(lane_params, state, it, fresh, shared)``; refills and a retried
    segment reuse it (``SweepReport.shared_bytes``).

    Because lanes are independent and a retired lane's state/iteration pair
    at its final segment equals the monolithic run's, outputs are
    **bit-identical** to monolithic dispatch — the exactness contract of
    the rest of this module extends to compaction (asserted by the
    differential suite).

    Returns ``(outputs, SweepReport)`` in original cell order, with
    ``compacted=True`` and refill/retire/segment/peak-lane accounting.

    ``quarantine=True`` makes the scheduler self-robust instead of letting
    one poisoned lane kill a million-lane run: after every segment the
    resident state and each newly-done lane's outputs are scanned for NaN
    (legitimate ``inf`` — dropped requests, never-served sentinels — is
    *not* quarantined); offending lanes are retired without results, their
    cells listed in ``SweepReport.quarantined_cells`` (float outputs
    NaN-filled, count in ``quarantined``), and their slots refilled.  A
    segment that *raises* is re-dispatched once from the host-side
    state mirrors (``retried_segments`` counts the retry) before the
    error propagates.  Every other lane's outputs are bit-identical to a
    quarantine-less run: the host mirrors hold the same doubles the
    device buffers did.
    """
    import collections

    import jax
    tree = jax.tree_util
    leaves = tree.tree_leaves(params)
    if not leaves:
        raise ValueError("compact_sweep: params pytree has no array leaves")
    n_cells = int(np.shape(leaves[0])[0])
    if n_cells == 0:
        raise ValueError("compact_sweep: empty grid — route degenerate "
                         "batches through execute_sweep")
    devices = tuple(devices)[:n_cells]
    n_devices = max(1, len(devices))
    L = max(1, min(int(lanes), n_cells))
    L = -(-L // n_devices) * n_devices          # shards must split evenly

    # LPT order: the longest-predicted cells enter the resident batch first
    # so no straggler is discovered with an almost-drained queue.
    order = (np.argsort(-np.asarray(predicted_cost, np.float64),
                        kind="stable")
             if predicted_cost is not None else np.arange(n_cells))
    queue = collections.deque(int(c) for c in order)

    slot_cell = np.zeros(L, np.int64)
    alive = np.zeros(L, bool)
    for s in range(L):
        if queue:
            slot_cell[s] = queue.popleft()
            alive[s] = True
        else:
            # Pad slot (grid smaller than a device-multiple batch): run a
            # duplicate of a real cell, never collect it.
            slot_cell[s] = slot_cell[0]
    peak_lanes = int(alive.sum())

    with span("sweep.stage"):
        params_np = tree.tree_map(np.asarray, params)
        if np.array_equal(slot_cell, np.arange(n_cells)):
            # Every cell resident, in order: the queue is empty after this
            # fill, so no refill ever writes the mirror, and it may alias
            # the caller's ``params``.
            lane_params = params_np
        else:
            lane_params = tree.tree_map(
                lambda l: np.take(l, slot_cell, axis=0), params_np)
        lane_leaves = tree.tree_leaves(lane_params)
        src_leaves = tree.tree_leaves(params_np)
        state = tree.tree_map(
            lambda sd: np.zeros((L,) + tuple(sd.shape), sd.dtype),
            state_prototype)
        it = np.zeros(L, np.int32)
        fresh = np.ones(L, bool)
    placement = lanes_sharding(devices) if n_devices > 1 else None
    lane_dev = None            # the mirror's device copy; None once stale
    shared_dev, shared_bytes = _put_shared(shared, devices)

    def dispatch():
        nonlocal h2d, uploads, lane_dev
        with span("sweep.dispatch"):
            if lane_dev is None:
                h2d += _host_bytes(lane_params)
                uploads += 1
                lane_dev = jax.device_put(lane_params, placement)
            h2d += _host_bytes(state, it, fresh)
            return step(lane_dev, state, it, fresh, *shared_dev)

    outputs: Optional[Dict[str, np.ndarray]] = None
    lane_iters = np.zeros(n_cells, np.int64)
    segments = refills = retires = executed = retried = uploads = 0
    h2d = shared_bytes
    quarantined_cells: list = []
    with warnings.catch_warnings():
        if donated:
            warnings.filterwarnings("ignore", message=_DONATION_MSG.pattern)
        while alive.any():
            try:
                state, it, done, j, out = dispatch()
            except Exception:
                if not quarantine:
                    raise
                # Under quarantine the carried state/it are host-side numpy
                # mirrors (converted below), so the donated device buffers
                # the failed dispatch consumed are re-creatable: retry the
                # segment once before letting the error kill the run.
                retried += 1
                state, it, done, j, out = dispatch()
            with span("sweep.wait"):
                if quarantine:
                    state = tree.tree_map(np.asarray, state)
                    it = np.asarray(it)
                done_np = np.asarray(done)
                j_max = int(np.asarray(j).max())
            segments += 1
            executed += L * j_max
            quar = np.zeros(L, bool)
            if quarantine:
                # NaN is the poison signal; inf is a legitimate sentinel
                # (dropped requests, never-served finish times).  A live
                # lane is judged by its state, a done lane by its outputs.
                nan_state = np.zeros(L, bool)
                for leaf in tree.tree_leaves(state):
                    if np.issubdtype(leaf.dtype, np.floating):
                        nan_state |= np.isnan(leaf.reshape(L, -1)).any(axis=1)
                nan_out = np.zeros(L, bool)
                for v in out.values():
                    v = np.asarray(v)
                    if np.issubdtype(v.dtype, np.floating):
                        nan_out |= np.isnan(v.reshape(L, -1)).any(axis=1)
                quar = alive & np.where(done_np, nan_out, nan_state)
            newly = done_np & alive & ~quar
            fresh = np.zeros(L, bool)
            if newly.any() or quar.any():
                with span("sweep.wait"):
                    out_np = {k: np.asarray(v) for k, v in out.items()}
                with span("sweep.retire"):
                    if outputs is None:
                        outputs = {
                            k: np.zeros((n_cells,) + v.shape[1:], v.dtype)
                            for k, v in out_np.items()}
                    if newly.any():
                        cells = slot_cell[newly]
                        for k, v in out_np.items():
                            outputs[k][cells] = v[newly]
                        if iterations_key in out_np:
                            lane_iters[cells] = np.asarray(
                                out_np[iterations_key][newly], np.int64)
                        retires += len(cells)
                        if on_chunk is not None:
                            on_chunk(cells.copy(),
                                     {k: v[newly].copy()
                                      for k, v in out_np.items()})
                    if quar.any():
                        q_cells = slot_cell[quar]
                        quarantined_cells.extend(int(c) for c in q_cells)
                        for v in outputs.values():
                            if np.issubdtype(v.dtype, np.floating):
                                v[q_cells] = np.nan
                    # Freed slots take the next queued cells in slot order;
                    # the rest go idle.
                    freed = np.flatnonzero(newly | quar)
                    n_new = min(len(freed), len(queue))
                    slots = freed[:n_new]
                    alive[freed[n_new:]] = False
                    slot_cell[slots] = [queue.popleft() for _ in range(n_new)]
                    fresh[slots] = True
                    refills += n_new
                if n_new:
                    with span("sweep.stage"):
                        for lp, src in zip(lane_leaves, src_leaves):
                            lp[slots] = src[slot_cell[slots]]
                    lane_dev = None
            elif j_max == 0:
                raise RuntimeError(
                    "compact_sweep: no lane progressed and none finished — "
                    "the engine's cond never clears under this budget")
            if max_segments is not None and segments > max_segments:
                raise RuntimeError(
                    f"compact_sweep: exceeded max_segments={max_segments}")

    frac = frac_mono = None
    iters = lane_iters if lane_iters.max() > 0 else None
    if iters is not None and executed > 0:
        total = int(iters.sum())
        frac = total / executed
        frac_mono = total / (int(iters.max()) * n_cells)
    report = SweepReport(
        n_cells=n_cells, chunk_size=L, n_chunks=segments,
        devices=n_devices, bucketed=predicted_cost is not None,
        donated=donated, active_lane_fraction=frac,
        active_lane_fraction_monolithic=frac_mono,
        lane_iterations=iters,
        sharding="shard_map" if n_devices > 1 else None,
        compacted=True, refills=refills, retires=retires,
        segments=segments, peak_lanes=peak_lanes,
        quarantined=len(quarantined_cells), retried_segments=retried,
        quarantined_cells=(np.asarray(quarantined_cells, np.int64)
                           if quarantined_cells else None),
        h2d_bytes=h2d, param_uploads=uploads, shared_bytes=shared_bytes)
    return outputs, report


def run_host_sweep(run_cell: Callable[[int], Any], n_cells: int, *,
                   chunk_size: Optional[int] = None,
                   predicted_cost=None):
    """Host-loop counterpart of :func:`execute_sweep` for engines whose
    cells are Python event loops (the consolidation drivers): same ordering
    and reporting contract, executed one cell at a time on the host.

    Returns ``(results, SweepReport)`` with ``results`` in original cell
    order.  A host loop never idles a lane, so the active fraction is 1.
    """
    if chunk_size is None:
        chunk_size = n_cells
    chunk_size = max(1, min(int(chunk_size), max(n_cells, 1)))
    bucketed = predicted_cost is not None
    order = (np.argsort(-np.asarray(predicted_cost, np.float64),
                        kind="stable")
             if bucketed else np.arange(n_cells))
    results: list = [None] * n_cells
    for i in order:
        results[int(i)] = run_cell(int(i))
    report = SweepReport(
        n_cells=n_cells, chunk_size=chunk_size,
        n_chunks=-(-n_cells // chunk_size) if n_cells else 0,
        devices=1, bucketed=bucketed, donated=False,
        active_lane_fraction=1.0 if n_cells else None,
        active_lane_fraction_monolithic=1.0 if n_cells else None)
    return results, report
