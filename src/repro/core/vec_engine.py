"""VecEngine — the declarative SoA event-loop substrate under every vec engine.

CloudSim 7G's headline contribution is a re-engineered internal architecture
with standardized interfaces that cut code with no loss of functionality
(paper §4).  Before this module our four vectorized engines (``vec_cluster``,
``vec_workflow``, ``vec_power``, ``vec_scheduler``) each hand-rolled the same
scaffolding: a statics dataclass, masked next-event reductions with a Pallas
fallback, a single-cell ``lax.while_loop``, a vmap batch entry cached per
static shape, ``use_pallas``/precision resolution, and routing through the
sweep execution layer.  Here that scaffolding exists **once**, and a scenario
is a declarative definition:

  * a **statics** object (hashable; shape-defining, trace-specializing) with
    an optional ``use_pallas`` field the driver reads;
  * a **params pytree** whose every leaf carries the cell axis first (the
    sweep layer's calling convention), and optionally one sweep-wide
    ``shared`` operand with no cell axis (:class:`BatchPlan`);
  * a ``build(params, statics, ops) -> Loop`` function returning the loop's
    initial **state pytree**, its ``cond``/``body`` transition functions, and
    a traced metrics **finalizer** — ``ops`` is a
    :class:`repro.kernels.ops.MaskedOps` bound to the resolved Pallas switch,
    so "next event = masked min/argmin" is one call.

The driver (:func:`batched_sim` → ``vmap(run_one)``) owns the iteration
counter: ``body(state, it)`` sees the current count (RNG folding, trace
indexing), the loop result gains an ``iterations`` output automatically
(the sweep layer's divergence accounting key), and the per-statics compiled
executable is cached so the sweep executor's donating ``jit`` is reused.

Batched entry points are produced by :func:`make_batch_entry` in one call:
a ``prepare(...)`` function maps the public signature to a :class:`BatchPlan`
(params + statics + predicted cost + host-side finalizer) or short-circuits
a degenerate batch with :class:`Done`; the builder resolves ``use_pallas``
(:func:`repro.kernels.ops.resolve_use_pallas`) and ``precision``
(:func:`resolve_precision`), runs the plan under :func:`x64` through
:func:`repro.core.sweep.execute_sweep` (chunking, buffer donation, device
sharding, divergence bucketing — all bit-identical to a monolithic call),
plumbs ``with_report``, and registers the ``@scenario`` handler.

SoA conventions every engine definition follows (the contracts tests assert):

  1. dense padded arrays with boolean masks instead of resizing;
  2. the whole simulation inside one ``lax.while_loop`` under ``jit``/
     ``vmap`` (the driver's loop);
  3. next event = masked min/argmin reduction (``ops.*``), not a heap walk;
  4. stochastic processes pre-drawn as absolute schedules in ``build``;
  5. :func:`x64` so decision/number identity with the OO engines holds
     (the driver enters it around every dispatch);
  6. compile-time feature pruning via statics flags (``build`` runs at trace
     time — plain Python ``if`` drops whole subgraphs).

See ARCHITECTURE.md ("Authoring a vec scenario") for a worked end-to-end
example; ``vec_netdc`` is the smallest real definition in the tree.
"""
from __future__ import annotations

import contextlib
import functools
import itertools
from dataclasses import dataclass
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.ops import MaskedOps, resolve_use_pallas
from .backend import scenario
from .spans import span
from .sweep import (MIN_CHUNK, SweepReport, compact_sweep, execute_sweep,
                    lanes_sharding, resolve_devices)


@contextlib.contextmanager
def x64():
    """The scope every vec engine traces and dispatches in — the one place
    the repo turns x64 on, so decision/number identity with the OO engines
    holds (convention 5 above).  It also pins the non-partitionable
    threefry stream: the engines' pre-drawn stochastic schedules (and the
    golden fixtures recorded from them) are that stream's draws."""
    with jax.enable_x64(True), jax.threefry_partitionable(False):
        yield


class Loop(NamedTuple):
    """One cell's compiled event loop, as returned by an engine's ``build``.

    ``cond(state, it)`` / ``body(state, it) -> state`` / ``finalize(state,
    it) -> dict`` all run traced; ``it`` is the driver-owned int32 iteration
    counter.  ``finalize`` may return an ``iterations`` entry to override
    the driver's count (e.g. a step dispatched before the loop).

    ``trip_count`` (optional) promises ``cond(state, it) == (it <
    trip_count)`` — the loop runs a *static* number of iterations.  The
    monolithic driver then lowers to ``fori_loop`` instead of
    ``while_loop``: under ``vmap`` a while-loop body is select-masked on
    every carry leaf each iteration (lanes may disagree on ``cond``),
    which for large per-request output carries is pure overhead when all
    lanes provably run the same count.  The body sequence is identical
    either way, so outputs stay bit-exact; the compacting scheduler keeps
    the while-loop form (its lanes genuinely pause mid-stream).
    """

    init: Any
    cond: Callable[[Any, Any], Any]
    body: Callable[[Any, Any], Any]
    finalize: Callable[[Any, Any], Dict[str, Any]]
    trip_count: Optional[int] = None


@dataclass(frozen=True)
class VecEngine:
    """A scenario kind as a declarative SoA event-loop definition."""

    kind: str
    build: Callable[[Any, Any, MaskedOps], Loop]


def run_one(engine: VecEngine, params: Any, statics: Any) -> Dict[str, Any]:
    """One cell, start to finish: a ``fori_loop`` when the loop declares
    ``trip_count``, a ``lax.while_loop`` otherwise."""
    ops = MaskedOps(bool(getattr(statics, "use_pallas", False)))
    loop = engine.build(params, statics, ops)
    if loop.trip_count is not None:
        # Static trip count → fori_loop (lowers to scan): vmap batches the
        # body directly, with none of while_loop's per-leaf select masking.
        state = jax.lax.fori_loop(
            0, int(loop.trip_count),
            lambda i, s: loop.body(s, jnp.asarray(i, jnp.int32)),
            loop.init)
        it = jnp.asarray(int(loop.trip_count), jnp.int32)
    else:
        def cond(c):
            return loop.cond(c[0], c[1])

        def body(c):
            return loop.body(c[0], c[1]), c[1] + 1

        state, it = jax.lax.while_loop(cond, body,
                                       (loop.init, jnp.asarray(0, jnp.int32)))
    out = dict(loop.finalize(state, it))
    out.setdefault("iterations", it)
    return out


@functools.lru_cache(maxsize=64)
def batched_sim(engine: VecEngine, statics: Any,
                shared: bool = False) -> Callable:
    """Batched (vmap) simulator for one static shape, in the sweep layer's
    calling convention: ``fn(lane_params)``, or with ``shared``
    ``fn(lane_params, shared)``, the plan's sweep-wide operand unbatched
    (``build`` then sees the pair ``(lane, shared)``).  Cached so the
    sweep executor (which jits with buffer donation) reuses one compiled
    executable per shape.  Its ops carry the scope ``<kind>/loop`` in the
    profiler's trace."""
    def scoped(fn):
        return jax.named_scope(engine.kind)(jax.named_scope("loop")(fn))
    if shared:
        return jax.vmap(scoped(lambda lane, shared_op: run_one(
            engine, (lane, shared_op), statics)), in_axes=(0, None))
    one = functools.partial(run_one, engine, statics=statics)
    return jax.vmap(scoped(one))


# -- compacting-scheduler segment step -----------------------------------------

# Host sinks for the in-graph retire tap, keyed by the id the compiled step
# receives as a traced operand — so the jitted step itself stays cacheable
# across sweeps (the sink changes, the executable does not).
_PROGRESS_SINKS: Dict[int, Callable] = {}
_progress_ids = itertools.count(1)


def _emit_progress(sink_id, done, j) -> None:
    cb = _PROGRESS_SINKS.get(int(np.asarray(sink_id)))
    if cb is not None:
        cb(np.asarray(done), np.asarray(j))


@functools.lru_cache(maxsize=64)
def _segment_sim(engine: VecEngine, statics: Any, budget: int,
                 shared: bool = False) -> Callable:
    """vmapped segment body: resume/merge, advance ≤ ``budget`` iterations,
    report termination + finalized outputs.  With ``shared`` it takes the
    plan's sweep-wide operand as a fifth argument, unbatched.

    ``use_pallas`` routes the reductions through the next-event kernel,
    as on the monolithic path; outputs stay bit-identical to the
    monolithic run either way.  Its ops carry the scope
    ``<kind>/segment`` in the profiler's trace.
    """
    ops = MaskedOps(bool(getattr(statics, "use_pallas", False)))

    @jax.named_scope(engine.kind)
    @jax.named_scope("segment")
    def seg_one(params, state, it, fresh, *shared_op):
        cell = (params, *shared_op) if shared_op else params
        loop = engine.build(cell, statics, ops)
        # A fresh lane adopts its new cell's initial state; a resident lane
        # resumes exactly where the previous segment paused it.  The merge
        # is a leafwise where(), so resuming never re-runs any iteration —
        # the state/iteration trajectory equals the monolithic run's.
        state = jax.tree_util.tree_map(
            lambda a, b: jnp.where(fresh, a, b), loop.init, state)
        it = jnp.where(fresh, jnp.asarray(0, jnp.int32), it)

        def cond(c):
            return loop.cond(c[0], c[1]) & (c[2] < budget)

        def body(c):
            s, i, j = c
            return loop.body(s, i), i + 1, j + 1

        state, it, j = jax.lax.while_loop(
            cond, body, (state, it, jnp.asarray(0, jnp.int32)))
        done = ~loop.cond(state, it)
        out = dict(loop.finalize(state, it))
        out.setdefault("iterations", it)
        return state, it, done, j, out

    return jax.vmap(seg_one, in_axes=(0, 0, 0, 0, None) if shared else 0)


@functools.lru_cache(maxsize=64)
def segment_step(engine: VecEngine, statics: Any, budget: int,
                 devices: tuple, donate: bool = True,
                 tap: bool = False, shared: bool = False) -> Callable:
    """Compiled segment dispatcher for the compacting scheduler.

    ``step(lane_params, state, it, fresh, sink_id[, shared]) -> (state,
    it, done, j, out)`` — :func:`repro.core.sweep.compact_sweep`'s step
    contract plus a sink id for the retire tap, then, with ``shared``, the
    plan's sweep-wide operand (replicated, never donated).  Cached per
    (engine, statics, budget, placement, shared): refills re-enter the
    same executable, so recompiles happen once per shape, never per
    refill.  The in-graph retire tap is
    compiled in only when ``tap`` is set (an ordered ``io_callback``
    serializes the device stream — dead weight when no sink is listening).
    Multi-device wraps the vmap in
    ``shard_map`` over a 1-D ``lanes`` mesh (flat lane axis, multi-process-
    ready); state and iteration buffers are donated across segments so the
    resident batch owns one set of device buffers.
    """
    from jax.experimental import io_callback
    core = _segment_sim(engine, statics, budget, shared)
    donate_argnums = (1, 2) if donate else ()
    if len(devices) > 1:
        from jax.sharding import PartitionSpec
        sh = lanes_sharding(devices)
        # check_vma=False: lanes are independent; the shared operand is
        # only read.
        sharded = jax.shard_map(
            core, mesh=sh.mesh,
            in_specs=(sh.spec,) * 4 + ((PartitionSpec(),) if shared else ()),
            out_specs=sh.spec, check_vma=False)

        def stepped(lane_params, state, it, fresh, sink_id, *shared_op):
            del sink_id                # retire tap is single-device only
            return sharded(lane_params, state, it, fresh, *shared_op)
        return jax.jit(stepped, donate_argnums=donate_argnums)

    def stepped(lane_params, state, it, fresh, sink_id, *shared_op):
        state, it, done, j, out = core(lane_params, state, it, fresh,
                                       *shared_op)
        if tap:
            # In-graph retire tap: streams (done mask, per-lane segment
            # iters) to the registered host sink as the device stream
            # advances.  The payload is bool/int32 only — the io_callback
            # delivery thread does not inherit the dispatcher's
            # thread-local x64 scope, so 64-bit floats would be
            # canonicalized (silently downcast) in flight.  Result
            # payloads therefore always travel as returned arrays
            # (bit-exact); the callback carries only canonicalization-safe
            # progress signals.
            io_callback(_emit_progress, None, sink_id, done, j,
                        ordered=True)
        else:
            del sink_id
        return state, it, done, j, out
    return jax.jit(stepped, donate_argnums=donate_argnums)


def _shapes(tree, lead: int = 0):
    """Shape/dtype structs of ``tree``'s leaves, less ``lead`` leading
    axes; read from the leaves' metadata, so a device leaf is not copied."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a)[lead:], a.dtype), tree)


def state_prototype(engine: VecEngine, statics: Any, params: Any,
                    shared: Any = None):
    """Shape/dtype pytree of one cell's loop state — via ``eval_shape`` on
    one lane's shapes (and the plan's ``shared`` operand, when it has
    one), so no device computation runs.  Callers must be under the same
    x64 regime as the dispatch (``run_plan`` enters it)."""
    ops = MaskedOps(bool(getattr(statics, "use_pallas", False)))
    one = _shapes(params, lead=1)
    if shared is not None:
        one = (one, _shapes(shared))
    return jax.eval_shape(
        lambda p: engine.build(p, statics, ops).init, one)


class BatchPlan(NamedTuple):
    """What ``prepare`` hands :func:`run_plan`: data + schedule for one
    batch.

    ``params`` are the lane params: every leaf carries the cell axis
    first, and the sweep layer gathers, pads and refills them per lane.
    ``shared`` (optional) is one sweep-wide operand with no cell axis: put
    on the device once a sweep, replicated over the devices, handed to
    every executable call beside the lane params and never gathered,
    refilled, donated or sent again.  ``build`` then receives the pair
    ``(lane, shared)`` for its ``params``."""

    params: Any                               # batched pytree, cell axis first
    statics: Any                              # hashable; may carry use_pallas
    predicted_cost: Optional[Any] = None      # per-cell loop-length estimate
    finalize: Optional[Callable[[Dict[str, Any]], Any]] = None  # host-side
    shared: Any = None                        # sweep-wide, no cell axis


class Done(NamedTuple):
    """``prepare`` short-circuit: host-computed outputs, no device dispatch
    (degenerate grids — e.g. a sweep driver whose filter left no cells)."""

    outputs: Any


def empty_report(donate: bool = True) -> SweepReport:
    """The sweep report a zero-cell batch carries (no dispatch happened)."""
    return SweepReport(n_cells=0, chunk_size=0, n_chunks=0, devices=1,
                       bucketed=False, donated=donate)


def broadcast_cells(seeds, axes: Dict[str, Any]):
    """Broadcast ``seeds`` against named sweep axes → ``(seeds[B],
    {axis: values[B]}, B)`` — the batch contract every sweep-axis entry
    point shares (scalars or arrays broadcast against ``seeds``)."""
    seeds = np.atleast_1d(np.asarray(seeds, np.int64))
    arrs = {k: np.atleast_1d(np.asarray(v)) for k, v in axes.items()}
    b = int(np.broadcast_shapes(seeds.shape,
                                *(a.shape for a in arrs.values()))[0])
    return (np.broadcast_to(seeds, (b,)),
            {k: np.broadcast_to(a, (b,)) for k, a in arrs.items()}, b)


def resolve_precision(precision: str) -> bool:
    """Validate an engine's ``precision`` opt-in → ``fast`` flag.

    ``"exact"`` accumulates in f64 under :func:`x64` (bit-identical to
    the OO engines where promised); ``"fast"`` keeps the f64 stochastic
    sample but runs the loop arithmetic in f32.
    """
    if precision not in ("exact", "fast"):
        raise ValueError(
            f"precision must be 'exact' or 'fast': {precision!r}")
    return precision == "fast"


DEFAULT_COMPACT_LANES = 256     # resident batch when chunk_size is not given
DEFAULT_SEGMENT_ITERS = 64      # per-segment iteration budget default


def run_compact(engine: VecEngine, plan: BatchPlan, *, chunk_size=None,
                devices=None, donate: bool = True, segment_iters=None,
                on_chunk: Optional[Callable] = None,
                progress: Optional[Callable] = None,
                quarantine: bool = False):
    """Execute a :class:`BatchPlan` through the compacting lane scheduler.

    ``chunk_size`` is the resident lane count (device memory is O(it));
    ``segment_iters`` the per-segment iteration budget.  ``on_chunk(cells,
    raw_outputs)`` streams each retired batch; ``progress(done_mask,
    segment_iters)`` — when given — fires from *inside* the compiled step
    via ``io_callback`` as each segment's retire mask materializes.
    Callers must already be under :func:`x64` (``run_plan`` is).
    """
    params, statics = plan.params, plan.statics
    n_cells = int(np.shape(jax.tree_util.tree_leaves(params)[0])[0])
    devs = tuple(resolve_devices(devices))
    devs = devs[:n_cells] if len(devs) > n_cells else devs
    budget = int(segment_iters) if segment_iters else DEFAULT_SEGMENT_ITERS
    lanes = (int(chunk_size) if chunk_size else
             min(n_cells, max(DEFAULT_COMPACT_LANES, MIN_CHUNK * len(devs))))
    sid = 0
    if progress is not None and len(devs) == 1:
        sid = next(_progress_ids)
        _PROGRESS_SINKS[sid] = progress
    step5 = segment_step(engine, statics, budget, devs, donate,
                         tap=sid != 0, shared=plan.shared is not None)
    sid_arr = np.int32(sid)

    def step(lane_params, state, it, fresh, *shared):
        return step5(lane_params, state, it, fresh, sid_arr, *shared)

    with span("sweep.stage"):
        prototype = state_prototype(engine, statics, params, plan.shared)
    try:
        return compact_sweep(
            step, params, lanes=lanes, state_prototype=prototype,
            devices=devs, predicted_cost=plan.predicted_cost,
            on_chunk=on_chunk, donated=donate, quarantine=quarantine,
            shared=plan.shared)
    finally:
        if sid:
            jax.effects_barrier()       # drain the ordered tap before unhook
            _PROGRESS_SINKS.pop(sid, None)


def run_plan(engine: VecEngine, plan, *, chunk_size=None, devices=None,
             donate: bool = True, with_report: bool = False,
             compact: bool = False, segment_iters=None,
             on_chunk: Optional[Callable] = None,
             progress: Optional[Callable] = None,
             quarantine: bool = False):
    """Execute a :class:`BatchPlan` through the sweep layer under x64.

    ``compact=True`` routes through the compacting lane scheduler
    (:func:`run_compact`) — bit-identical outputs, O(chunk) device memory,
    streaming retires.  Otherwise chunked dispatch (:func:`execute_sweep`).
    Both split lanes over several devices by ``shard_map`` on the mesh of
    :func:`repro.core.sweep.lanes_sharding`.  ``on_chunk(cells,
    raw_outputs)`` streams finished cells on either path; the payload is
    the engine's *raw* output dict (before ``plan.finalize``), keyed by
    original cell indices.
    """
    if isinstance(plan, Done):
        out, report = plan.outputs, empty_report(donate)
    else:
        n_cells = int(np.shape(jax.tree_util.tree_leaves(plan.params)[0])[0])
        with x64():
            if compact and n_cells > 0:
                out, report = run_compact(
                    engine, plan, chunk_size=chunk_size, devices=devices,
                    donate=donate, segment_iters=segment_iters,
                    on_chunk=on_chunk, progress=progress,
                    quarantine=quarantine)
            else:
                out, report = execute_sweep(
                    batched_sim(engine, plan.statics,
                                plan.shared is not None), plan.params,
                    chunk_size=chunk_size, devices=devices, donate=donate,
                    predicted_cost=plan.predicted_cost,
                    on_chunk=on_chunk, shared=plan.shared)
        if plan.finalize is not None:
            with span("sweep.finalize"):
                out = plan.finalize(out)
    return (out, report) if with_report else out


def make_batch_entry(engine: VecEngine, prepare: Callable, *,
                     kind: Optional[str] = None, backends=("vec",),
                     name: Optional[str] = None,
                     doc: Optional[str] = None) -> Callable:
    """Build a sweep-routed batched entry point and register its scenario.

    ``prepare(*args, use_pallas=<resolved bool>, **kw)`` returns a
    :class:`BatchPlan` (or :class:`Done`).  The produced entry adds the
    uniform sweep controls (``use_pallas``, ``chunk_size``, ``devices``,
    ``donate``, ``with_report``, ``compact``, ``segment_iters``,
    ``on_chunk``, ``progress``, ``quarantine``) to ``prepare``'s own
    signature and is registered as the ``kind`` handler for ``backends``
    (pass ``backends=()`` to skip registration, e.g. when a hand-written
    handler dispatches on input shape first).
    """
    kind = kind or engine.kind

    def entry(*args, use_pallas: bool | str = False, chunk_size=None,
              devices=None, donate: bool = True, with_report: bool = False,
              compact: bool = False, segment_iters=None,
              on_chunk: Optional[Callable] = None,
              progress: Optional[Callable] = None,
              quarantine: bool = False,
              **kw):
        with span("sweep.prepare"):
            plan = prepare(*args, use_pallas=resolve_use_pallas(use_pallas),
                           **kw)
        return run_plan(engine, plan, chunk_size=chunk_size, devices=devices,
                        donate=donate, with_report=with_report,
                        compact=compact, segment_iters=segment_iters,
                        on_chunk=on_chunk, progress=progress,
                        quarantine=quarantine)

    entry.__name__ = name or f"simulate_{kind}"
    entry.__qualname__ = entry.__name__
    if doc:
        entry.__doc__ = doc
    if backends:
        scenario(kind, backends=backends)(
            lambda backend, **params: entry(**params))
    return entry
