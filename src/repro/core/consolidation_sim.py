"""Consolidation simulation drivers — one scenario, three engine flavours.

Engine selection goes through the :mod:`repro.core.backend` substrate
(``run_scenario("consolidation", backend=...)``); this module registers one
handler per backend instead of hand-rolling a three-way dispatch:

  * ``legacy`` (alias ``6g``) — LegacySimulation (O(n) linked-list queue,
                boxed histories, uncached recomputation, string-concat
                logging),
  * ``oo``     (alias ``7g``) — the re-engineered engine (heap queue,
                cached paths),
  * ``vec``   — beyond-paper: utilization bookkeeping + overload detection
                vectorized over all VMs/hosts as structure-of-arrays under
                JAX (the same SoA conventions as ``vec_scheduler`` /
                ``vec_cluster``; x64 so decisions stay bit-identical to the
                OO paths).

Benchmarks (Table 2 reproduction) compare run-time and allocation across
the three; tests assert identical decisions (migrations, energy).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

import numpy as np

from .backend import SimBackend, get_backend, scenario
from .engine import SimEntity, Simulation
from .engine_oo import LegacyConsolidationManager, LegacySimulation
from .events import Event, Tag
from .power import (ALGORITHMS, ConsolidationAlgo, ConsolidationManager,
                    DETECTORS, make_consolidation_scenario)


@dataclass
class ConsolidationResult:
    algo: str
    engine: str
    energy_kwh: float
    migrations: int
    events: int
    final_active_hosts: int


class _ConsolidationEntity(SimEntity):
    """Periodic CONSOLIDATE driver running a manager inside a Simulation."""

    def __init__(self, sim: Simulation, mgr: ConsolidationManager,
                 horizon: float):
        super().__init__(sim, "consolidator")
        self.mgr = mgr
        self.horizon = horizon

    def start(self) -> None:
        self.sim.schedule(0.0, Tag.CONSOLIDATE, self)

    def process_event(self, ev: Event) -> None:
        if ev.tag is Tag.CONSOLIDATE:
            t = ev.time
            self.mgr.record_step(t)
            self.mgr.consolidate(t)
            nxt = t + self.mgr.interval
            if nxt < self.horizon:
                self.sim.schedule(nxt, Tag.CONSOLIDATE, self)


class VecConsolidationManager(ConsolidationManager):
    """Structure-of-arrays utilization/detection pass under JAX.

    SoA conventions shared with ``vec_scheduler``/``vec_cluster`` (see
    ARCHITECTURE.md): per-entity attributes live as padded device arrays
    (traces ``[V, K]``, capacities ``[V]``/``[H]``), the per-step sweep is
    one fused vector pass instead of per-object traversals, and the whole
    path runs under :func:`repro.core.vec_engine.x64` so every derived float
    is the same IEEE double the OO managers compute — selection/placement
    decisions reuse the scalar routines and match the OO managers exactly
    (asserted by tests and the Table-2 benchmark).

    Host-level demand aggregation stays a scalar accumulation in canonical
    (ascending VM id) order: summation *order* is part of the bit-identity
    contract, and a segment-sum's reduction order is unspecified.
    """

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        import jax.numpy as jnp

        from .vec_engine import x64
        self._x64 = x64
        with x64():
            self._traces = jnp.asarray(
                np.stack([np.asarray(vm.trace, dtype=np.float64)
                          for vm in self.vms]), jnp.float64)     # [V, K]
            self._vm_mips = jnp.asarray(
                [vm.caps.total_mips for vm in self.vms], jnp.float64)
            self._host_mips = jnp.asarray(
                [h.caps.total_mips for h in self.hosts], jnp.float64)
        self._host_index = {h.id: i for i, h in enumerate(self.hosts)}
        self._vm_index = {vm.id: i for i, vm in enumerate(self.vms)}
        self._vm_util_now = np.zeros(len(self.vms))
        self._sweep_k = -1                     # trace index of cached sweep
        self._sweep_util = self._sweep_demand = None

    def _sweep(self, t: float):
        """One SoA pass per trace interval: every VM's utilization and MIPS
        demand, cached so the detect/select/place loop's many ``host_util``
        calls within one interval reuse a single device sweep + sync."""
        k = min(int(t / self.interval), self._traces.shape[1] - 1)
        if k != self._sweep_k:
            with self._x64():
                util = self._traces[:, k]                        # [V] one sweep
                demand_vec = util * self._vm_mips                # [V] one sweep
            self._sweep_k = k
            self._sweep_util = np.asarray(util)                  # one host sync
            self._sweep_demand = np.asarray(demand_vec)
        return self._sweep_util, self._sweep_demand

    def record_step(self, t: float) -> None:
        self.now = t
        util, demand_vec = self._sweep(t)
        self._vm_util_now = util
        for vm, u in zip(self.vms, util):                        # histories
            vm.util_history.append(float(u))
        # Per-host aggregation in canonical (ascending vm id) order with
        # scalar accumulation — bit-identical to the OO managers' sums while
        # the per-VM sweep above stays vectorized.
        for h in self.hosts:
            demand = 0.0
            for vm in sorted(h.guests, key=lambda g: g.id):
                demand += float(demand_vec[self._vm_index[vm.id]])
            u = min(demand / h.caps.total_mips, 1.0) if h.caps.total_mips else 0.0
            h.record_utilization(u, self.interval)

    def host_util(self, h, t: float) -> float:
        _, demand_vec = self._sweep(t)
        demand = 0.0
        for vm in sorted(h.guests, key=lambda g: g.id):
            demand += float(demand_vec[self._vm_index[vm.id]])
        cap = h.caps.total_mips
        return min(demand / cap, 1.0) if cap else 0.0


_MANAGERS = {"legacy": LegacyConsolidationManager,
             "oo": ConsolidationManager,
             "vec": VecConsolidationManager}


@scenario("consolidation", backends=("legacy", "oo", "vec"))
def _consolidation_scenario(backend: SimBackend, *, algo: str = "ThrMu",
                            n_hosts: int = 50, n_vms: int = 100, seed: int = 1,
                            n_samples: int = 288, interval: float = 300.0
                            ) -> ConsolidationResult:
    hosts, vms = make_consolidation_scenario(n_hosts, n_vms, seed=seed,
                                             n_samples=n_samples,
                                             interval=interval)
    mgr = _MANAGERS[backend.name](hosts, vms, ConsolidationAlgo.by_name(algo),
                                  interval=interval, seed=seed)
    sim = backend.make_simulation()
    horizon = n_samples * interval
    _ConsolidationEntity(sim, mgr, horizon)
    sim.run()
    return ConsolidationResult(
        algo=algo, engine=backend.name, energy_kwh=mgr.total_energy_kwh(),
        migrations=mgr.migrations, events=sim.events_processed,
        final_active_hosts=sum(1 for h in hosts if h.active))


@scenario("consolidation_batch", backends=("legacy", "oo", "vec"))
def _consolidation_batch(backend: SimBackend, *, algos=("ThrMu",),
                         seeds=(1,), n_hosts: int = 50, n_vms: int = 100,
                         n_samples: int = 288, interval: float = 300.0,
                         chunk_size: Optional[int] = None,
                         with_report: bool = False):
    """Batched consolidation sweep (``algos`` × ``seeds`` broadcast) through
    the sweep layer's host path.

    The consolidation drivers are Python event loops (the vec flavour
    vectorizes the per-step utilization sweep, not the loop), so cells run
    on :func:`repro.core.sweep.run_host_sweep` — same ordering/report
    contract as the compiled engines, executed cell-at-a-time.  Cells are
    bucketed by predicted cost (∝ hosts × VMs × samples, uniform here
    unless the caller broadcasts differing sizes).  Returns a list of
    :class:`ConsolidationResult` in cell order; ``with_report=True``
    returns ``(results, SweepReport)``.
    """
    from .sweep import run_host_sweep
    algos = np.atleast_1d(np.asarray(algos, dtype=object))
    seeds = np.atleast_1d(np.asarray(seeds))
    b = int(np.broadcast_shapes(algos.shape, seeds.shape)[0])
    algos = np.broadcast_to(algos, (b,))
    seeds = np.broadcast_to(seeds, (b,))

    def run_cell(i: int) -> ConsolidationResult:
        return _consolidation_scenario(
            backend, algo=str(algos[i]), n_hosts=n_hosts, n_vms=n_vms,
            seed=int(seeds[i]), n_samples=n_samples, interval=interval)

    results, report = run_host_sweep(
        run_cell, b, chunk_size=chunk_size,
        predicted_cost=np.full(b, float(n_hosts) * n_vms * n_samples))
    return (results, report) if with_report else results


def run_consolidation(engine: str = "7g", algo: str = "ThrMu", *,
                      n_hosts: int = 50, n_vms: int = 100, seed: int = 1,
                      n_samples: int = 288, interval: float = 300.0
                      ) -> ConsolidationResult:
    """Back-compat wrapper over the backend substrate (``6g``/``7g``
    aliases accepted)."""
    return get_backend(engine).run_scenario(
        "consolidation", algo=algo, n_hosts=n_hosts, n_vms=n_vms, seed=seed,
        n_samples=n_samples, interval=interval)
