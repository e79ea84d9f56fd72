"""Vectorized power-aware elastic datacenter — ``power_batch`` as JAX SoA.

The OO side of the paper's energy story lives in ``core.power``: power
models (linear / cubic / SPEC-table / DVFS), the unified C2 selection
policies, and :class:`~repro.core.power.ElasticDatacenterManager` — a
threshold autoscaler that powers hosts on/off against a demand trace,
integrating per-host energy and SLA-violation time.  This module is the
same scenario as a :class:`~repro.core.vec_engine.VecEngine` definition:
per-host attributes as dense ``[H]`` arrays with power models lowered to
``[H, P]`` utilization→power tables (:func:`repro.core.power.power_points`),
and the autoscaler's energy-aware host picks as masked first-occurrence
``argmin``/``argmax`` reductions (``ops.argmin``/``ops.argmax`` — the fused
Pallas next-event kernel when ``use_pallas`` is set, since "cheapest
inactive host" is exactly a masked next-event reduction with watts in place
of event times).

Exactness contract (asserted by tests and the differential suite): the
scenario is deterministic given its demand trace, and ``oo`` and ``vec``
agree **bit-exactly** on every output, including per-host energy and
integer migration counts.  The contract survives XLA:CPU codegen because
the compiled loop contains *no float multiply feeding an add/sub* — the
one pattern XLA may contract into an FMA (1-ulp drift vs CPython's
separately rounded ops; fusion clones producers, so no graph-level pin
prevents it).  Instead the loop accumulates *exact* quantities — power-
table segment-hit counts + frac sums (:func:`repro.core.power
.table_segment`), SLA-interval counts, unserved-MIPS sums — and the
shared host-side finalizer (:func:`repro.core.power.segment_energy_j`)
applies the table and the interval scaling identically for both backends.
Decision arithmetic (utilization vs thresholds) only routes multiplies
into divides, min/max, and compares — none contractible.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .backend import scenario
from .faults import FaultPlan
from .power import (_broadcast_cells, _empty_outputs, _finalize,
                    _finalize_accumulators, _power_batch_oo,
                    host_capacities, make_power_fleet, power_fault_table,
                    power_points)
from .spans import span
from .vec_engine import BatchPlan, Done, Loop, VecEngine, make_batch_entry


@dataclass(frozen=True)
class _Statics:
    """Shape-defining (compile-time) configuration of one power sweep."""
    n_hosts: int
    n_points: int
    n_intervals: int
    n_vms: int
    min_active: int
    use_pallas: bool
    # Static fault gate: when set, ``params.fail_tbl`` carries the [K, H]
    # host-crash table and the body opens with the degraded-capacity block.
    # Default off so the unfaulted compiled graph is byte-identical to the
    # pre-fault one (golden-fixture stability).
    faults: bool = False


class _Params(NamedTuple):
    """Traced per-cell inputs — every leaf carries the batch axis in the
    sweep layer's calling convention.  (The power tables and the interval
    never enter the compiled loop: energy is finalized host-side from the
    exact segment accumulators — see the module docstring.)"""
    trace: Any          # [K] aggregate per-VM utilization demand
    cap: Any            # [H] host capacity (MIPS)
    eff: Any            # [H] watts/MIPS at full load (table[:, -1] / cap)
    up_thr: Any         # [] scale-out utilization threshold
    lo_thr: Any         # [] scale-in utilization threshold
    vm_mips: Any        # [] per-VM capacity (MIPS)
    cooldown_k: Any     # [] i32 intervals to wait after a scaling action
    init_active: Any    # [] i32 hosts powered on at t=0
    fail_tbl: Any = None   # [K, H] bool host crashed during interval k
    #                        (None — an empty pytree leaf — when unfaulted)


class _Carry(NamedTuple):
    count: Any          # [H] i32 VMs placed per host
    active: Any         # [H] bool host powered on
    cooldown: Any       # [] i32 intervals until the next action may fire
    seg_count: Any      # [H, P-1] i32 power-table segment hits (active)
    seg_frac: Any       # [H, P-1] f64 Σ frac within each segment (exact)
    over_count: Any     # [H] i32 intervals spent overloaded (SLA)
    unserved: Any       # [H] f64 Σ unserved MIPS (scaled by interval later)
    migrations: Any     # [] i32 VMs that landed on a new host
    scale_out: Any      # [] i32 power-on events
    scale_in: Any       # [] i32 power-off events


def _even_counts(active, n_vms: int):
    """Even VM split over the active hosts, in host-index order: the first
    ``V mod A`` active hosts take one extra VM (mirrors the OO manager's
    ``_even_targets``)."""
    a32 = active.astype(jnp.int32)
    rank = jnp.cumsum(a32) - 1
    a = jnp.maximum(jnp.sum(a32), 1)
    base = n_vms // a
    rem = n_vms - base * a
    return jnp.where(active, base + (rank < rem).astype(jnp.int32), 0)


def table_segment(x, cast, n_points: int):
    """``(segment, frac)`` of ``x = util·(n_points-1)`` in ``[0, n_points-1]``:
    :func:`repro.core.power.table_segment`, vectorized.

    ``cast`` is ``x`` cast to int32, which may be one off ``⌊x⌋``: on the
    TPU, where f64 is a pair of f32, the cast reads the high word, so an
    ``x`` just below a knot casts to the knot.  One comparison with ``x``
    each way takes such a cast back, so ``seg = ⌊x⌋`` exactly, and
    ``frac = x - seg`` is exact for ``seg ≤ x < seg + 1`` and equal to the
    ``fmod(x, 1)`` of the OO side; both come from the one floor and cannot
    disagree.  The top endpoint folds into the last segment with
    ``frac = 1``.
    """
    seg = cast - (cast.astype(x.dtype) > x).astype(jnp.int32)
    seg = seg + ((seg + 1).astype(x.dtype) <= x).astype(jnp.int32)
    seg = jnp.minimum(seg, n_points - 2)
    frac = jnp.where(x >= n_points - 1, 1.0, x - seg.astype(x.dtype))
    return seg, frac


def _power_build(params: _Params, s: _Statics, ops) -> Loop:
    """One elastic-datacenter cell: one loop iteration per trace interval
    (the driver's counter ``it`` is the interval index ``k``).

    Each iteration slices the per-interval streams (the demand trace, and
    the crash table when faulted) at ``it`` and applies ``step``.  The
    returned ``Loop`` carries ``trip_count``, so the driver lowers it to
    ``fori_loop``.
    """
    H = s.n_hosts
    idx = jnp.arange(H)
    seg_iota = jnp.arange(s.n_points - 1)
    streams = dict(trace=params.trace)
    if s.faults:
        streams["fail_tbl"] = params.fail_tbl

    def step(c: _Carry, sl, it) -> _Carry:
        # -- host crashes (start of interval; static gate) -----------------
        # Applying the table every interval is equivalent to the OO side's
        # changed-rows-only events: at an unchanged interval the block is
        # the identity (scale-out/keep-alive never activate a failed host,
        # so ``active & ~failed == active`` between changes).  Mirrors
        # ``ElasticDatacenterManager.apply_fault_mask`` op for op.
        if s.faults:
            failed = sl["fail_tbl"]                     # [H] bool
            act = c.active & ~failed
            keep = ops.argmin(params.eff, ~failed)      # keep-alive pick
            act = jnp.where(jnp.any(act), act, act | (idx == keep))
            fchanged = jnp.any(act ^ c.active)
            cnt = jnp.where(fchanged, _even_counts(act, s.n_vms), c.count)
            fmoved = jnp.sum(jnp.maximum(cnt - c.count, 0), dtype=jnp.int32)
            avail = jnp.sum((~failed).astype(jnp.int32))
            on_mask = ~act & ~failed
        else:
            act, cnt = c.active, c.count
            avail = H
            on_mask = ~act

        # -- demand, utilization, energy, SLA (current placement) ----------
        # Multiplies here feed only divides, min/max, and compares — never
        # an add/sub, so XLA cannot FMA-contract (module docstring).
        d = sl["trace"] * params.vm_mips                # per-VM MIPS demand
        demand = cnt.astype(params.cap.dtype) * d       # [H]
        util = jnp.minimum(demand / params.cap, 1.0)
        # Exact energy accounting: which table segment, how far into it.
        # The min is the identity (util ≤ 1); it keeps the multiply from
        # feeding the subtraction in table_segment (module docstring).
        x = jnp.minimum(util * (s.n_points - 1), s.n_points - 1)
        seg, frac = table_segment(x, x.astype(jnp.int32), s.n_points)
        hot = (seg[:, None] == seg_iota) & act[:, None]        # [H, P-1]
        seg_count = c.seg_count + hot.astype(jnp.int32)
        seg_frac = c.seg_frac + jnp.where(hot, frac[:, None], 0.0)
        over = demand > params.cap
        over_count = c.over_count + over.astype(jnp.int32)
        # max(demand, cap) - cap ≡ max(demand - cap, 0): the subtraction
        # consumes a max, not the multiply — same form as the OO manager.
        unserved = c.unserved + (jnp.maximum(demand, params.cap)
                                 - params.cap)

        # -- autoscale decision (end of interval; shapes interval k+1) -----
        n_act = jnp.sum(act.astype(jnp.int32))
        can = c.cooldown == 0
        any_over = jnp.any(act & (util > params.up_thr))
        all_under = jnp.max(jnp.where(act, util, -jnp.inf)) \
            < params.lo_thr
        want_out = can & any_over & (n_act < avail)
        want_in = can & ~want_out & all_under & (n_act > s.min_active)
        # energy-aware picks: cheapest inactive host on, dearest active off
        pick_on = ops.argmin(params.eff, on_mask)
        pick_off = ops.argmax(params.eff, act)
        active1 = jnp.where(
            want_out, act | (idx == pick_on),
            jnp.where(want_in, act & (idx != pick_off), act))
        changed = want_out | want_in
        count1 = jnp.where(changed, _even_counts(active1, s.n_vms), cnt)
        moved = jnp.sum(jnp.maximum(count1 - cnt, 0), dtype=jnp.int32)
        one = jnp.asarray(1, jnp.int32)
        migrations = c.migrations + jnp.where(changed, moved, 0)
        if s.faults:
            migrations = migrations + fmoved    # i32 adds commute exactly
        return _Carry(
            count=count1,
            active=active1,
            cooldown=jnp.where(changed, params.cooldown_k,
                               jnp.maximum(c.cooldown - 1, 0)),
            seg_count=seg_count, seg_frac=seg_frac,
            over_count=over_count, unserved=unserved,
            migrations=migrations,
            scale_out=c.scale_out + jnp.where(want_out, one, 0),
            scale_in=c.scale_in + jnp.where(want_in, one, 0))

    def finalize(end: _Carry, it) -> Dict[str, Any]:
        # Exact accumulators leave the loop; energy/SLA/unserved are
        # finalized on the host by the same numpy routine the OO manager
        # uses (the plan's host-side finalizer).
        return dict(
            seg_count=end.seg_count,
            seg_frac=end.seg_frac,
            over_count=end.over_count,
            unserved_mips=end.unserved,
            migrations=end.migrations,
            scale_out_events=end.scale_out,
            scale_in_events=end.scale_in,
            final_active=jnp.sum(end.active.astype(jnp.int32)))

    active0 = idx < params.init_active
    zi = jnp.asarray(0, jnp.int32)
    init = _Carry(count=_even_counts(active0, s.n_vms), active=active0,
                  cooldown=zi,
                  seg_count=jnp.zeros((H, s.n_points - 1), jnp.int32),
                  seg_frac=jnp.zeros((H, s.n_points - 1),
                                     params.cap.dtype),
                  over_count=jnp.zeros((H,), jnp.int32),
                  unserved=jnp.zeros((H,), params.cap.dtype),
                  migrations=zi, scale_out=zi, scale_in=zi)
    def body(c: _Carry, it) -> _Carry:
        return step(c, jax.tree_util.tree_map(lambda a: a[it], streams), it)

    # trip_count: every lane runs exactly n_intervals iterations (the cond
    # is a pure counter check), so the driver lowers to fori_loop/scan —
    # identical body sequence, bit-identical outputs (Loop docstring).
    return Loop(init=init, cond=lambda c, it: it < s.n_intervals,
                body=body, finalize=finalize, trip_count=s.n_intervals)


POWER_ENGINE = VecEngine("power_batch", _power_build)


def _prepare_power(*, use_pallas: bool, seeds: Sequence[int] | np.ndarray = (0,),
                   n_hosts: int = 8, n_vms: int = 32,
                   n_samples: int = 288, interval: float = 300.0,
                   host_mips=8000.0, host_pes=1, vm_mips=1000.0,
                   up_thr=0.8, lo_thr=0.3, cooldown=3,
                   min_active: int = 1, init_active: Optional[int] = None,
                   model_mix: str = "mixed", n_points: int = 11,
                   fault_plan: Optional[FaultPlan] = None, demand=None):
    if demand is not None:
        from .power import check_demand
        demand = check_demand(demand)
        n_samples = int(demand.shape[0])
    min_active = max(int(min_active), 1)
    init_active = n_hosts if init_active is None else int(init_active)
    if not 1 <= min_active <= n_hosts:
        raise ValueError("min_active must be in [1, n_hosts]")
    if not min_active <= init_active <= n_hosts:
        raise ValueError("init_active must be in [min_active, n_hosts]")
    if n_vms < 1:
        raise ValueError("n_vms must be ≥ 1")
    if not interval > 0:
        raise ValueError("interval must be > 0")
    seeds, axes, b = _broadcast_cells(seeds, dict(
        up_thr=up_thr, lo_thr=lo_thr, cooldown=cooldown, vm_mips=vm_mips))
    pes, mips = host_capacities(n_hosts, host_mips, host_pes)
    if b and float(np.max(axes["vm_mips"])) > float(np.min(mips)):
        # Same constraint the OO reference enforces through time-shared
        # Host.suitable_for — reject up front so a vm_mips sweep axis that
        # crosses host_mips can't produce vec results with no OO semantics.
        raise ValueError(
            f"vm_mips (max {np.max(axes['vm_mips'])}) must be ≤ every "
            f"host's per-PE host_mips ({np.min(mips)}): a VM must fit a "
            f"time-shared host")
    fail_tbl = power_fault_table(fault_plan, n_hosts, n_samples, interval)
    if b == 0:
        return Done(_empty_outputs(n_hosts))

    from .power import elastic_demand_trace
    import random as _random
    with span("sweep.prepare.build"):
        if demand is not None:
            traces = np.broadcast_to(demand, (b, n_samples)).copy()
        else:
            traces = np.asarray([elastic_demand_trace(
                _random.Random(int(s)), n_samples) for s in seeds],
                np.float64)
        models = make_power_fleet(n_hosts, model_mix)
        cap = pes.astype(np.float64) * mips
        table = np.asarray([power_points(m, n_points) for m in models],
                           np.float64)
        eff = table[:, -1] / cap
    bc = lambda a: np.broadcast_to(a, (b,) + np.shape(a)).copy()
    with span("sweep.prepare.pack"):
        params = _Params(
            trace=traces,
            cap=bc(cap), eff=bc(eff),
            up_thr=axes["up_thr"].astype(np.float64),
            lo_thr=axes["lo_thr"].astype(np.float64),
            vm_mips=axes["vm_mips"].astype(np.float64),
            cooldown_k=axes["cooldown"].astype(np.int32),
            init_active=np.full(b, init_active, np.int32),
            fail_tbl=None if fail_tbl is None else bc(fail_tbl))
    statics = _Statics(int(n_hosts), int(n_points), int(n_samples),
                       int(n_vms), min_active, bool(use_pallas),
                       faults=fail_tbl is not None)
    # All lanes run exactly n_samples iterations — no divergence to bucket.
    return BatchPlan(
        params, statics,
        finalize=lambda out: _finalize(
            _finalize_accumulators(out, table, float(interval))))


simulate_power_batch = make_batch_entry(
    POWER_ENGINE, _prepare_power, name="simulate_power_batch", doc="""\
    Run a batch of elastic-datacenter cells through the sweep layer.

    ``seeds`` and the optional sweep axes (``up_thr``, ``lo_thr``,
    ``cooldown``, ``vm_mips`` — scalars or arrays broadcast against
    ``seeds``) define the batch.  ``host_mips`` (per PE) and ``host_pes``
    are one value or a cycle of host types, as ``model_mix`` is
    (:func:`repro.core.power.host_capacities`).  Each cell's demand trace
    is synthesized from its seed
    (:func:`repro.core.power.elastic_demand_trace`) and shared verbatim
    with the OO reference.  Returns a dict of per-cell
    stats — per-host ``energy_wh [B, H]`` / ``sla_s`` / ``unserved_mips_s``
    plus their datacenter totals, integer ``migrations`` /
    ``scale_out_events`` / ``scale_in_events`` / ``final_active`` — and
    with ``with_report=True`` returns ``(stats, SweepReport)``.
    A ``fault_plan`` (:class:`~repro.core.faults.FaultPlan` of ``node``
    windows) crashes hosts for the covered intervals: crashed hosts power
    off, shed their VMs (counted as migrations) and are excluded from
    scale-out until recovery — degraded-capacity autoscaling, bit-exact
    vs the ``oo``/``legacy`` backends.

    Execution goes through :mod:`repro.core.sweep` (bounded chunks with
    donated buffers, device sharding) — bit-identical to the monolithic
    dispatch, which in turn is bit-identical to the OO manager.
    """)


# -- OO reference (legacy / oo backends) ---------------------------------------
# The event-driven reference implementation lives with the OO manager in
# :mod:`repro.core.power`; registered here so loading the vec module wires
# every backend of the kind.
scenario("power_batch", backends=("legacy", "oo"))(_power_batch_oo)
