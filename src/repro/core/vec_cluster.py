"""Vectorized ML-fleet simulator — ``FleetSim``'s life-cycle as JAX SoA.

The OO :class:`repro.core.cluster.FleetSim` is a pure-Python event loop;
this module is the same life-cycle — lognormal straggler max-reduction,
pre-drawn exponential failure/repair rounds, checkpoint cadence with
rollback-on-failure, elastic width penalty, stall below ``min_nodes_frac``,
chronic-straggler eviction — as a :class:`~repro.core.vec_engine.VecEngine`
definition (dense masked node arrays; failure interruptions via ``ops.min``).

Exactness contract (asserted by tests): **deterministic** configs
(``straggler_sigma=0``, no failures) are bit-identical to the OO
``FleetSim`` (same ordered f64 additions); **stochastic** configs share the
process laws and match mean goodput within 2% over ≥64 seeds.  Documented
approximations (second-order for the validated statistics): index-ordered
active prefix instead of min-bias spare promotion; failures inside
ckpt/stall windows observed at the next boundary; a failure during the
restart window charges no second ``restart_s``; recovered nodes keep their
degrade multiplier until their next degrade event; ``elastic=False`` stall
accounting not modeled.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.ops import masked_argmax
from .backend import SimBackend, scenario
from .cluster import FleetConfig, RunStats, StepCost, fleet_fault_windows
from .faults import FaultPlan
from .vec_engine import (BatchPlan, Done, Loop, VecEngine, make_batch_entry,
                         resolve_precision, x64)

STALL_RETRY_S = 60.0          # matches FleetSim's stall-retry cadence


@dataclass(frozen=True)
class _Statics:
    """Shape-defining / trace-specializing (compile-time) configuration.

    The three feature flags prune whole subgraphs from the compiled loop
    body: ``sigma_zero`` drops the per-step RNG draw (deterministic runs),
    ``degrade`` drops the chronic-degradation schedule, ``track_stragglers``
    drops the per-step median sort + eviction bookkeeping.  ``fast`` keeps
    the pre-drawn stochastic schedules in f64 (the *same* sample as exact
    mode) but runs the loop itself in f32.
    """
    n_nodes: int
    n_spares: int
    k_fail_rounds: int
    k_degrade: int
    window: int
    use_pallas: bool
    track_stragglers: bool = True
    degrade: bool = True
    sigma_zero: bool = False
    fast: bool = False
    # Planned-outage windows from a FaultPlan (0 = no plan, pruning the
    # whole fault subgraph so the unfaulted compiled graph is unchanged).
    n_fault_windows: int = 0

    @property
    def n_total(self) -> int:
        return self.n_nodes + self.n_spares


class _Params(NamedTuple):
    """Traced per-scenario scalars — every field may carry a batch axis."""
    base_step_s: Any
    mtbf_s: Any
    repair_s: Any
    ckpt_every: Any
    ckpt_write_s: Any
    restart_s: Any
    sigma: Any
    evict_factor: Any
    degrade_s: Any
    degrade_factor: Any
    min_nodes: Any            # min_nodes_frac * n_nodes (float threshold)
    total_steps: Any
    max_wall_s: Any


class _Faults(NamedTuple):
    """Planned-outage windows (:func:`repro.core.cluster
    .fleet_fault_windows`), one row per window, batch axis in front."""
    node: Any                 # [W] i32 which node the window downs
    start: Any                # [W] f64 outage start (half-open window)
    end: Any                  # [W] f64 outage end


class _Carry(NamedTuple):
    t: Any                    # [] f64 simulation clock
    step: Any                 # [] i  unique steps completed (post-rollback)
    last_ckpt: Any            # [] i
    bias: Any                 # [n] f64 persistent per-node slowdown bias
                              #     (scalar 0 when per-node values unused)
    slow_count: Any           # [n] i  consecutive-slow-step counts (scalar
    evict_until: Any          # [n] f64 eviction outage ends   when track off)
    was_up: Any               # [n] bool schedule-up state at last observation
    was_active: Any           # [n] bool active set of the previous attempt
    watch_from: Any           # [] f64 start of an in-flight stall/restart/
                              #        ckpt window (-inf = none): failures
                              #        inside it cascade another restart
    failures: Any
    restarts: Any
    evictions: Any
    lost_steps: Any
    stall_s: Any
    ckpt_s: Any


def _fleet_build(args, s: _Statics, ops) -> Loop:
    """One fleet scenario as a loop over step attempts (the driver's ``it``
    replaces the old carried counter for per-step RNG folding)."""
    params, key, fx = args
    n = s.n_total
    kf, kd, kb, kstep, kevict = jax.random.split(key, 5)
    if s.n_fault_windows:
        # [n, W] membership mask: which windows belong to which node.
        mine = fx.node == jnp.arange(n)[:, None]

    # Pre-drawn failure renewal process: node i's k-th outage starts at
    # fail_start[i, k] and ends repair_s later (cf. FleetSim's exponential
    # NODE_FAILURE draws rescheduled after each NODE_RECOVER).
    gaps = jax.random.exponential(kf, (n, s.k_fail_rounds)) * params.mtbf_s
    fail_start = (jnp.cumsum(gaps, axis=1)
                  + jnp.arange(s.k_fail_rounds) * params.repair_s)
    # Pre-drawn chronic-degradation times (ELASTIC_RESIZE "degrade" events).
    if s.degrade:
        dgaps = jax.random.exponential(kd, (n, s.k_degrade)) * params.degrade_s
        degrade_t = jnp.cumsum(dgaps, axis=1)
    if not (s.track_stragglers or s.degrade):
        bias0 = jnp.asarray(0.0, fail_start.dtype)      # per-node path unused
    elif s.sigma_zero:
        bias0 = jnp.ones((n,), fail_start.dtype)
    else:
        bias0 = jnp.exp(jax.random.normal(kb, (n,)) * (params.sigma / 2.0))

    if s.fast:
        # "fast" precision: the pre-drawn schedules above were sampled in
        # f64 — the *same* failure/degrade/bias sample the exact path sees
        # (an f32 RNG stream is a different sample, and an unluckier draw
        # once made the f32 sweep *slower* end-to-end via extra rollback
        # redo-work) — and only the loop arithmetic drops to f32.
        def _f32(x):
            x = jnp.asarray(x)
            return x.astype(jnp.float32) \
                if jnp.issubdtype(x.dtype, jnp.floating) else x
        params = _Params(*(_f32(f) for f in params))
        fail_start = fail_start.astype(jnp.float32)
        bias0 = _f32(bias0)
        if s.degrade:
            degrade_t = degrade_t.astype(jnp.float32)
        if s.n_fault_windows:
            fx = _Faults(node=fx.node, start=_f32(fx.start),
                         end=_f32(fx.end))

    n_nodes_f = jnp.asarray(float(s.n_nodes), fail_start.dtype)
    k_last = s.k_fail_rounds - 1
    k_iota = jnp.arange(s.k_fail_rounds)

    def round_start(idx):
        """fail_start[i, idx[i]] as a one-hot contraction over the (small)
        round axis — XLA CPU executes this as fused vector passes, far
        cheaper than a batched gather."""
        return jnp.sum(jnp.where(k_iota == idx[:, None], fail_start, 0.0),
                       axis=1)

    def cond(c: _Carry, it):
        return (c.step < params.total_steps) & (c.t < params.max_wall_s)

    def body(c: _Carry, it) -> _Carry:
        # The fleet has no per-iteration stream tables: everything per-step
        # (RNG draws, schedule lookups) derives from ``it``.
        # Current renewal round = number of fully completed outages; the
        # count form needs no carried pointer and is always caught up.
        ended = jnp.sum(fail_start + params.repair_s <= c.t, axis=1,
                        dtype=jnp.int32)
        r = jnp.minimum(ended, k_last)
        cur = round_start(r)
        rdown = (cur <= c.t) & (c.t < cur + params.repair_s)
        down = rdown
        if s.n_fault_windows:
            # Planned outages fold into the same down/next-fail/cascade
            # machinery as the stochastic renewal process (half-open
            # windows, matching the FaultPlan contract).
            down = down | jnp.any(mine & (fx.start <= c.t)
                                  & (c.t < fx.end), axis=1)
        up_sched = ~down
        up = up_sched & (c.t >= c.evict_until) if s.track_stragglers \
            else up_sched
        failures = c.failures + jnp.sum(c.was_up & ~up_sched,
                                        dtype=jnp.int32)
        # Next schedule failure strictly after now (inf once exhausted).
        nxt = round_start(jnp.minimum(r + 1, k_last))
        next_fail = jnp.where(cur > c.t, cur,
                              jnp.where(rdown & (r < k_last), nxt, jnp.inf))
        if s.n_fault_windows:
            next_fail = jnp.minimum(next_fail, jnp.min(
                jnp.where(mine & (fx.start > c.t), fx.start, jnp.inf),
                axis=1))
        # Cascade check: did a then-active node fail inside the stall/
        # restart/ckpt window we just jumped over?  The OO engine processes
        # that NODE_FAILURE mid-window (gen bump): roll back to the last
        # checkpoint and pay another restart_s from the failure time.
        # (A window is shorter than repair_s, so the in-window failure is
        # each node's *current* round.)
        f_window = jnp.min(jnp.where(
            c.was_active & (cur > c.watch_from) & (cur <= c.t),
            cur, jnp.inf))
        if s.n_fault_windows:
            f_window = jnp.minimum(f_window, jnp.min(jnp.where(
                c.was_active[fx.node] & (fx.start > c.watch_from)
                & (fx.start <= c.t), fx.start, jnp.inf)))
        cascade = jnp.isfinite(c.watch_from) & (f_window < c.t)
        # Active set: index-ordered prefix of up nodes, capped at n_nodes
        # (the OO engine's explicit spare promotion; iid biases make the
        # choice statistically equivalent).
        active = up & (jnp.cumsum(up) <= s.n_nodes)
        n_active = jnp.sum(active)
        stalled = ~cascade & (n_active < params.min_nodes)

        # -- straggler sampling: sync step = slowest active participant ----
        if s.track_stragglers or s.degrade:
            # Per-node slowdowns materialized (needed for eviction
            # bookkeeping / per-node degradation multipliers).
            if s.sigma_zero:
                jitter = jnp.ones((n,), fail_start.dtype)
            else:
                jit_key = jax.random.fold_in(kstep, it)
                draws = jax.random.normal(jit_key, (n,), jnp.float32)
                jitter = jnp.exp(draws.astype(fail_start.dtype)
                                 * params.sigma)
            if s.degrade:
                deg_mult = jnp.exp(jnp.sum(degrade_t <= c.t, axis=1)
                                   * jnp.log(params.degrade_factor))
                slowdown = c.bias * deg_mult * jitter
            else:
                deg_mult = 1.0
                slowdown = c.bias * jitter
            max_slow = jnp.max(jnp.where(active, slowdown, -jnp.inf))
        elif s.sigma_zero:
            max_slow = jnp.asarray(1.0, fail_start.dtype)
        else:
            # Neither eviction nor degradation feeds per-node values back
            # into the dynamics, so only the max matters — sample it
            # directly by inverse CDF: the max of m iid exp(σ_tot·Z) is
            # exp(σ_tot·Φ⁻¹(U^(1/m))).  σ_tot folds the persistent bias
            # (σ/2) and per-step jitter (σ) components; the per-step
            # marginal distribution is exactly the OO engine's (only the
            # cross-step correlation of which node is slowest is dropped).
            # One RNG draw per step instead of n.
            from jax.scipy.special import ndtri
            u = jax.random.uniform(jax.random.fold_in(kstep, it), (),
                                   fail_start.dtype, minval=1e-12)
            sig_tot = jnp.sqrt(params.sigma ** 2 + (params.sigma / 2) ** 2)
            z = ndtri(u ** (1.0 / jnp.maximum(n_active, 1)))
            max_slow = jnp.exp(sig_tot * z)
        width = jnp.maximum(n_nodes_f / jnp.maximum(n_active, 1), 1.0)
        step_s = params.base_step_s * max_slow * width

        # -- failure interruption: earliest active-node failure in-window --
        t_int = ops.min(next_fail, active)
        interrupted = ~cascade & ~stalled & (t_int < c.t + step_s)
        completed = ~cascade & ~stalled & ~interrupted
        t_done = c.t + step_s
        step1 = c.step + 1

        # -- straggler bookkeeping + chronic eviction (completed steps) ----
        if s.track_stragglers:
            srt = jnp.sort(jnp.where(active, slowdown, jnp.inf))
            lo = jnp.maximum((n_active - 1) // 2, 0)
            hi = jnp.maximum(n_active // 2, 0)
            med = 0.5 * (srt[lo] + srt[hi])             # np.median tie rule
            slow = active & (slowdown > params.evict_factor * med)
            slow_count1 = jnp.where(active,
                                    jnp.where(slow, c.slow_count + 1, 0),
                                    c.slow_count)
            chronic = active & (slow_count1 >= s.window)
            any_chronic = jnp.any(chronic)
            worst = masked_argmax(c.bias * deg_mult, chronic)
            evict_now = completed & any_chronic
            new_bias = jnp.exp(jax.random.normal(
                jax.random.fold_in(kevict, it), ()) * (params.sigma / 2.0))
            bias1 = jnp.where(evict_now, c.bias.at[worst].set(new_bias),
                              c.bias)
            evict_until1 = jnp.where(
                evict_now,
                c.evict_until.at[worst].set(t_done + params.repair_s),
                c.evict_until)
            slow_count2 = jnp.where(evict_now, slow_count1.at[worst].set(0),
                                    slow_count1)
        else:
            evict_now = jnp.asarray(False)
            bias1, evict_until1, slow_count2 = (c.bias, c.evict_until,
                                                c.slow_count)

        # -- checkpoint cadence (completed steps) --------------------------
        ckpt_due = (step1 - c.last_ckpt) >= params.ckpt_every
        t_after = jnp.where(ckpt_due, t_done + params.ckpt_write_s, t_done)
        # A failure landing inside the checkpoint write window kills the
        # in-flight chain like the OO engine's gen bump: the step and the
        # checkpoint are already counted (last_ckpt = step1 ⇒ zero steps
        # lost) but the fleet pays restart_s from the failure time.
        ckpt_hit = completed & ckpt_due \
            & (t_int < t_done + params.ckpt_write_s)

        # -- select among {cascade, stalled, interrupted, ckpt_hit, done} --
        t_next = jnp.where(
            cascade, f_window + params.restart_s,
            jnp.where(stalled, c.t + STALL_RETRY_S,
                      jnp.where(interrupted | ckpt_hit,
                                t_int + params.restart_s, t_after)))
        step_next = jnp.where(completed, step1,
                              jnp.where(stalled, c.step, c.last_ckpt))
        last_ckpt_next = jnp.where(completed & ckpt_due, step1, c.last_ckpt)
        rollback = cascade | interrupted
        # Keep watching the new stall/restart window; a clean step clears it.
        watch_next = jnp.where(
            cascade, f_window,
            jnp.where(stalled, c.t,
                      jnp.where(interrupted | ckpt_hit, t_int, -jnp.inf)))
        return _Carry(
            t=t_next,
            step=step_next,
            last_ckpt=last_ckpt_next,
            bias=bias1,
            slow_count=jnp.where(completed, slow_count2, c.slow_count)
                       if s.track_stragglers else c.slow_count,
            evict_until=evict_until1,
            was_up=up_sched,
            was_active=jnp.where(cascade, c.was_active, active),
            watch_from=watch_next,
            failures=failures,
            restarts=c.restarts + jnp.where(rollback | ckpt_hit, 1, 0),
            evictions=c.evictions + jnp.where(evict_now, 1, 0),
            lost_steps=c.lost_steps + jnp.where(
                rollback, (c.step - c.last_ckpt).astype(
                    c.lost_steps.dtype), 0.0),
            stall_s=c.stall_s + jnp.where(
                stalled, STALL_RETRY_S,
                jnp.where(rollback | ckpt_hit, params.restart_s, 0.0)),
            ckpt_s=c.ckpt_s + jnp.where(completed & ckpt_due,
                                        params.ckpt_write_s, 0.0),
        )

    def finalize(end: _Carry, it) -> Dict[str, Any]:
        finished = end.step >= params.total_steps
        wallclock = jnp.where(finished, end.t, params.max_wall_s)
        ideal = end.step.astype(wallclock.dtype) * params.base_step_s
        return dict(
            wallclock_s=wallclock, steps_done=end.step, failures=end.failures,
            restarts=end.restarts, evictions=end.evictions,
            lost_steps=end.lost_steps, stall_s=end.stall_s, ckpt_s=end.ckpt_s,
            ideal_s=ideal,
            goodput=jnp.where(wallclock > 0, ideal / wallclock, 0.0))

    zf = jnp.asarray(0.0, fail_start.dtype)
    zi = jnp.asarray(0, jnp.int32)
    init = _Carry(
        t=zf, step=zi, last_ckpt=zi,
        bias=bias0,
        slow_count=jnp.zeros((n,), jnp.int32) if s.track_stragglers else zi,
        evict_until=(jnp.zeros((n,), fail_start.dtype)
                     if s.track_stragglers else zf),
        was_up=jnp.ones((n,), bool),
        was_active=jnp.arange(n) < s.n_nodes,
        watch_from=jnp.asarray(-jnp.inf, fail_start.dtype),
        failures=zi, restarts=zi, evictions=zi,
        lost_steps=zf, stall_s=zf, ckpt_s=zf)
    # A genuine while-loop: steps and wall clock race, so the cond is
    # data-dependent and no trip_count applies.
    return Loop(init=init, cond=cond, body=body, finalize=finalize)


FLEET_ENGINE = VecEngine("fleet_batch", _fleet_build)


def _predicted_iters(params: _Params, n_total: int) -> np.ndarray:
    """Predicted while-loop length per cell, for divergence bucketing.

    Loop iterations ≈ unique steps + failure-rollback redo work: each
    failure among the ``n_total`` nodes over the ≈ ``total_steps ×
    base_step_s`` horizon rolls the fleet back ~``ckpt_every/2`` steps.
    Only the *ordering* matters (cells are bucketed by predicted length),
    so second-order terms (stalls, checkpoint writes) are ignored."""
    steps = np.asarray(params.total_steps, np.float64)
    horizon = steps * np.asarray(params.base_step_s, np.float64)
    exp_failures = horizon * n_total / np.asarray(params.mtbf_s, np.float64)
    redo = np.asarray(params.ckpt_every, np.float64) / 2.0 + 1.0
    return steps + exp_failures * redo


def _make_params(cost: StepCost, cfg: FleetConfig, total_steps,
                 max_wallclock_s, *, mtbf_hours=None, ckpt_every=None,
                 straggler_sigma=None) -> _Params:
    """Broadcast scalars/sweep axes into a batched _Params (numpy, f64)."""
    base = cost.step_seconds() + cfg.pod_boundary_overhead_s
    mtbf_h = cfg.mtbf_hours_node if mtbf_hours is None else mtbf_hours
    every = cfg.ckpt_every_steps if ckpt_every is None else ckpt_every
    sigma = cfg.straggler_sigma if straggler_sigma is None else straggler_sigma
    fields = dict(
        base_step_s=base,
        mtbf_s=np.asarray(mtbf_h, np.float64) * 3600.0,
        repair_s=cfg.repair_hours * 3600.0,
        ckpt_every=np.asarray(every, np.int32),
        ckpt_write_s=cfg.ckpt_write_s,
        restart_s=cfg.restart_s,
        sigma=np.asarray(sigma, np.float64),
        evict_factor=cfg.straggler_evict_factor,
        degrade_s=cfg.degrade_mtbf_hours * 3600.0,
        degrade_factor=cfg.degrade_factor,
        min_nodes=cfg.min_nodes_frac * cfg.n_nodes,
        total_steps=np.asarray(total_steps, np.int32),
        max_wall_s=max_wallclock_s,
    )
    shape = np.broadcast_shapes(*(np.shape(v) for v in fields.values()))
    return _Params(**{k: np.broadcast_to(np.asarray(v, np.asarray(v).dtype),
                                         shape).astype(
                          np.int32 if k in ("ckpt_every", "total_steps")
                          else np.float64)
                      for k, v in fields.items()})


def _prepare_fleet(cost: StepCost, cfg: FleetConfig, total_steps: int = 2000,
                   *, use_pallas: bool,
                   seeds: Sequence[int] | np.ndarray = (0,),
                   mtbf_hours=None, ckpt_every=None, straggler_sigma=None,
                   max_wallclock_s: float = 30 * 86400.0,
                   k_fail_rounds: Optional[int] = None, k_degrade: int = 8,
                   precision: str = "exact",
                   fault_plan: Optional[FaultPlan] = None):
    fast = resolve_precision(precision)
    windows = fleet_fault_windows(fault_plan, cfg.n_nodes + cfg.n_spares)
    seeds = np.asarray(seeds, np.uint32)
    params = _make_params(cost, cfg, total_steps, max_wallclock_s,
                          mtbf_hours=mtbf_hours, ckpt_every=ckpt_every,
                          straggler_sigma=straggler_sigma)
    b = int(np.broadcast_shapes(seeds.shape, params.base_step_s.shape)[0]) \
        if (seeds.ndim or params.base_step_s.ndim) else 1
    seeds = np.broadcast_to(np.atleast_1d(seeds), (b,))
    params = _Params(*(np.broadcast_to(np.atleast_1d(f), (b,))
                       for f in params))
    if b == 0:
        # Degenerate grid (e.g. a sweep driver whose filter left no cells):
        # empty per-stat arrays, no dispatch.
        zf, zi = np.empty((0,), np.float64), np.empty((0,), np.int32)
        return Done(dict(
            wallclock_s=zf, steps_done=zi, failures=zi, restarts=zi,
            evictions=zi, lost_steps=zf, stall_s=zf, ckpt_s=zf,
            ideal_s=zf, goodput=zf, iterations=zi))
    if k_fail_rounds is None:
        # Horizon estimate: 10× the zero-overhead run time (goodput ≥ 0.1),
        # capped by the hard wall-clock bound; 3× margin on expected rounds.
        horizon = min(float(max_wallclock_s),
                      float(np.max(params.base_step_s))
                      * float(np.max(params.total_steps)) * 10.0 + 3600.0)
        cycle = float(np.min(params.mtbf_s) + np.min(params.repair_s))
        k_fail_rounds = int(np.clip(np.ceil(horizon / cycle * 3.0 + 3), 4, 64))
    statics = _Statics(
        cfg.n_nodes, cfg.n_spares, int(k_fail_rounds), k_degrade,
        cfg.straggler_window, bool(use_pallas),
        track_stragglers=bool(np.min(params.evict_factor) < 1e8
                              and cfg.straggler_window <= 10_000),
        degrade=bool(np.min(params.degrade_s) < 1e8 * 3600.0),
        sigma_zero=bool(np.all(params.sigma == 0.0)),
        fast=fast,
        n_fault_windows=len(windows))
    if windows:
        w = np.asarray(windows, np.float64)            # [W, 3]
        bcw = lambda a: np.broadcast_to(a, (b, len(windows))).copy()
        fx = _Faults(node=bcw(w[:, 0].astype(np.int32)),
                     start=bcw(w[:, 1]), end=bcw(w[:, 2]))
    else:
        fx = None
    with x64():
        # Keys and (for "fast") the pre-drawn schedules are built in the
        # x64 world either way, so both precisions see the same sample.
        keys = np.asarray(jax.vmap(jax.random.PRNGKey)(jnp.asarray(seeds)))
    return BatchPlan(
        (params, keys, fx), statics,
        predicted_cost=_predicted_iters(params, statics.n_total))


simulate_fleet_batch = make_batch_entry(
    FLEET_ENGINE, _prepare_fleet, name="simulate_fleet_batch", doc="""\
    Run a batch of fleet scenarios through the sweep execution layer.

    ``seeds`` and the optional sweep axes (``mtbf_hours``, ``ckpt_every``,
    ``straggler_sigma`` — scalars or arrays broadcast against ``seeds``)
    define the batch. Returns a dict of per-scenario stat arrays
    (``goodput``, ``wallclock_s``, ``steps_done``, ``failures``, ...);
    with ``with_report=True`` returns ``(stats, SweepReport)``.  Cells are
    bucketed by predicted loop length, chunked with donated buffers, and
    sharded across ``devices`` — bit-identical to the monolithic call.

    ``k_fail_rounds`` (failure-renewal rounds pre-drawn per node) defaults
    to an estimate covering the simulated horizon with ample margin (a node
    that exhausts its schedule simply stops failing); ``precision`` is
    ``"exact"`` (f64, bit-identical to the OO engine on deterministic
    configs) or ``"fast"`` (same f64 stochastic sample, f32 loop).
    A ``fault_plan`` (:class:`~repro.core.faults.FaultPlan` of per-node
    ``node`` windows) adds *planned* outages on top of the stochastic
    MTBF process — see :func:`repro.core.cluster.fleet_fault_windows`
    for the validation rules and the bit-exactness domain.
    """)


def simulate_fleet_vec(cost: StepCost, cfg: FleetConfig,
                       total_steps: int = 2000, *,
                       max_wallclock_s: float = 30 * 86400.0,
                       use_pallas: bool = False,
                       fault_plan: Optional[FaultPlan] = None) -> RunStats:
    """Single-scenario convenience wrapper returning the OO ``RunStats``."""
    out = simulate_fleet_batch(cost, cfg, total_steps, seeds=[cfg.seed],
                               max_wallclock_s=max_wallclock_s,
                               use_pallas=use_pallas, fault_plan=fault_plan)
    from dataclasses import fields
    return RunStats(**{f.name: (int if f.type == "int" else float)(
        out[f.name][0]) for f in fields(RunStats)})


# -- backend substrate handlers ------------------------------------------------

@scenario("fleet", backends=("vec",))
def _fleet_vec(backend: SimBackend, *, cost: StepCost, cfg: FleetConfig,
               total_steps: int = 2000,
               max_wallclock_s: float = 30 * 86400.0,
               use_pallas: bool = False,
               fault_plan: Optional[FaultPlan] = None) -> RunStats:
    return simulate_fleet_vec(cost, cfg, total_steps,
                              max_wallclock_s=max_wallclock_s,
                              use_pallas=use_pallas, fault_plan=fault_plan)
