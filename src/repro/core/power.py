"""Power-aware consolidation — the workloads behind the paper's Table 2.

Implements the five algorithms evaluated in the paper (Dvfs, MadMmt, ThrMu,
IqrRs, LrrMc), i.e. Beloglazov & Buyya's overload-detection × VM-selection
grid, on top of the 7G **unified selection interface** (C2): VM-selection
(migration) and host-selection (placement) are both `SelectionPolicy`
instances — the deduplication the paper performs on ≤6G's disjoint policy
families.

Host CPU-utilization history is kept in a ``deque`` (paper §4.4 item 4:
append + last-k access pattern → linked list, not array list).
"""
from __future__ import annotations

import math
import random
from collections import deque

import numpy as np
from dataclasses import dataclass, field
from typing import Callable, Deque, Dict, List, Optional, Sequence, Tuple

from .engine import SimEntity
from .entities import Cloudlet, CoreAttributes, GuestEntity, Host, HostEntity, Vm
from .events import Tag
from .faults import FaultPlan
from .scheduler import CloudletSchedulerTimeShared
from .selection import (MaximumScore, MinimumScore, RandomSelection,
                        SelectionPolicy, least_power_efficient,
                        most_power_efficient)

HISTORY_LEN = 30          # samples of history used by adaptive detectors
SAFETY_LR = 1.2           # Beloglazov's safety parameter for LR/LRR
S_IQR = 1.5
S_MAD = 2.5
THR_STATIC = 0.8


# --------------------------------------------------------------------------
# Power model + power-aware entities (PowerHostEntity/PowerGuestEntity ifaces)
# --------------------------------------------------------------------------

def interp_table(points: Sequence[float], util: float) -> float:
    """Piecewise-linear power lookup over evenly spaced utilization points.

    CloudSim's ``PowerModelSpecPower`` semantics: ``points[k]`` is the power
    at utilization ``k/(len-1)`` and intermediate utilizations interpolate
    linearly between the two enclosing measurements.

    (The elastic scenario's engines never call this inside their hot
    loops: they accumulate the exact :func:`table_segment` decomposition
    and finalize through :func:`segment_energy_j`, which reproduces this
    interpolation bit-for-bit — asserted by tests.)
    """
    u = min(max(util, 0.0), 1.0)
    n = len(points)
    x = u * (n - 1)
    k = min(int(x), n - 2)
    frac = x - k
    return points[k] + (points[k + 1] - points[k]) * frac


@dataclass
class PowerModelLinear:
    """P(u) = idle + (max-idle)·u — the standard CloudSim linear model."""
    idle_w: float = 86.0
    max_w: float = 117.0

    def power(self, util: float) -> float:
        u = min(max(util, 0.0), 1.0)
        return self.idle_w + (self.max_w - self.idle_w) * u


@dataclass
class PowerModelCubic:
    """P(u) = idle + (max-idle)·u³ — CloudSim's ``PowerModelCubic``
    (dynamic power ∝ V²f with both scaling with load)."""
    idle_w: float = 93.7
    max_w: float = 135.0

    def power(self, util: float) -> float:
        u = min(max(util, 0.0), 1.0)
        return self.idle_w + (self.max_w - self.idle_w) * u * u * u


@dataclass(frozen=True)
class PowerModelSpecTable:
    """SPECpower-style measured table: power at 0%, 10%, …, 100% load,
    linearly interpolated in between (``PowerModelSpecPower`` semantics)."""
    points: Tuple[float, ...]

    def __post_init__(self):
        object.__setattr__(self, "points",
                           tuple(float(p) for p in self.points))
        if len(self.points) < 2:
            raise ValueError("SPEC table needs ≥ 2 measurement points")

    def power(self, util: float) -> float:
        return interp_table(self.points, util)


# The two SPECpower_ssj2008 tables every CloudSim power example ships
# (Beloglazov & Buyya's evaluation hosts).
SPEC_HP_ML110_G4 = (86.0, 89.4, 92.6, 96.0, 99.5, 102.0, 106.0, 108.0,
                    112.0, 114.0, 117.0)
SPEC_HP_ML110_G5 = (93.7, 97.0, 101.0, 105.0, 110.0, 116.0, 121.0, 125.0,
                    129.0, 133.0, 135.0)


@dataclass(frozen=True)
class PowerModelDvfs:
    """Discrete-step DVFS: the host clocks at the lowest frequency step
    ``f ≥ u`` and dynamic power scales as ``f²·u`` (∝ V²f at proportional
    voltage).  Monotone non-decreasing in utilization: linear within a
    step, an upward jump at each step boundary.
    """
    idle_w: float = 86.0
    max_w: float = 117.0
    steps: Tuple[float, ...] = (0.4, 0.6, 0.8, 1.0)

    def __post_init__(self):
        object.__setattr__(self, "steps",
                          tuple(float(f) for f in self.steps))
        if not self.steps or tuple(sorted(self.steps)) != self.steps \
                or self.steps[-1] != 1.0:
            raise ValueError("DVFS steps must ascend and end at 1.0")

    def frequency(self, util: float) -> float:
        u = min(max(util, 0.0), 1.0)
        for f in self.steps:
            if f >= u:
                return f
        return self.steps[-1]

    def power(self, util: float) -> float:
        u = min(max(util, 0.0), 1.0)
        f = self.frequency(u)
        return self.idle_w + (self.max_w - self.idle_w) * (f * f) * u


def power_points(model, n_points: int = 11) -> List[float]:
    """Sample any power model onto an evenly spaced utilization table.

    The elastic-datacenter scenario evaluates *all* host power through
    :func:`interp_table` over these samples (its vec engine needs one
    uniform SoA representation); the models' own ``power()`` stays the
    ground truth for the consolidation workloads and the unit tests.
    """
    if n_points < 2:
        raise ValueError("n_points must be ≥ 2")
    return [model.power(k / (n_points - 1)) for k in range(n_points)]


def table_segment(util: float, n_points: int) -> Tuple[int, float]:
    """(segment index, fractional position) of a utilization in a table.

    The exact-summation decomposition behind the elastic scenario's energy
    accounting: interpolated power is ``t[s] + (t[s+1]-t[s])·frac``, so an
    engine only needs to *count* segment hits and *sum* fracs — both exact
    accumulations — and :func:`segment_energy_j` applies the table once at
    the end.  ``frac`` comes from ``fmod`` (exact in IEEE-754, and equal to
    the ``x - s`` the direct interpolation uses, since ``s = ⌊x⌋``); the
    top endpoint folds into the last segment with ``frac = 1``.
    """
    x = util * (n_points - 1)
    s = min(int(x), n_points - 2)
    frac = 1.0 if x >= n_points - 1 else math.fmod(x, 1.0)
    return s, frac


def segment_energy_j(tables: "np.ndarray", seg_count: "np.ndarray",
                     seg_frac: "np.ndarray", interval) -> "np.ndarray":
    """Per-host energy (J) from segment-hit counts and frac sums.

    ``tables [..., H, P]``, ``seg_count``/``seg_frac [..., H, P-1]`` →
    ``[..., H]`` joules.  Σ_k interval·(t[s_k] + Δt[s_k]·frac_k)
    regrouped by segment:  interval · Σ_s (count_s·t[s] + Δt[s]·Σfrac_s).

    This host-side numpy routine is shared verbatim by the OO manager and
    the vec engine — the one place the power table is multiplied in.  The
    compiled vec loop deliberately contains **no** float multiply feeding
    an add: XLA:CPU's fusion clones producers into consumers and may then
    contract ``a + b·c`` into an FMA (observed as 1-ulp energy drift on
    wide batches that no graph-level pin — optimization_barrier, bitcast,
    select, roll — survives, since fusion re-derives the product from the
    cloned multiply).  Pure counts and frac sums are exact accumulations,
    immune by construction.
    """
    tables = np.asarray(tables, np.float64)
    lo, hi = tables[..., :-1], tables[..., 1:]
    watts = seg_count * lo + (hi - lo) * seg_frac
    return watts.sum(axis=-1) * np.asarray(interval)[..., None]


class PowerHost(Host):
    """Host with power model + utilization history (PowerHostEntity)."""

    def __init__(self, *a, power_model: Optional[PowerModelLinear] = None, **kw):
        super().__init__(*a, **kw)
        self.power_model = power_model or PowerModelLinear()
        self.util_history: Deque[float] = deque(maxlen=HISTORY_LEN)
        self.energy_j = 0.0

    def record_utilization(self, util: float, dt: float) -> None:
        self.util_history.append(util)
        if self.active:
            self.energy_j += self.power_model.power(util) * dt


class TraceVm(Vm):
    """VM whose CPU demand follows a utilization trace (PowerGuestEntity).

    ``trace[k]`` is the fraction of the VM's MIPS demanded during sample
    interval k (PlanetLab-style: 288 samples × 300 s = 24 h).
    """

    def __init__(self, trace: Sequence[float], interval: float = 300.0, **kw):
        kw.setdefault("name", "tvm")
        super().__init__(CloudletSchedulerTimeShared(), **kw)
        self.trace = list(trace)
        self.interval = interval
        self.util_history: Deque[float] = deque(maxlen=HISTORY_LEN)

    def utilization(self, t: float) -> float:
        if not self.trace:
            return 0.0
        k = min(int(t / self.interval), len(self.trace) - 1)
        return self.trace[k]

    def demand_mips(self, t: float) -> float:
        return self.utilization(t) * self.caps.total_mips


# --------------------------------------------------------------------------
# Overload detection
# --------------------------------------------------------------------------

def _median(xs: Sequence[float]) -> float:
    s = sorted(xs)
    n = len(s)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


def detect_thr(history: Sequence[float], util: float) -> bool:
    return util > THR_STATIC


def detect_iqr(history: Sequence[float], util: float) -> bool:
    if len(history) < 10:
        return detect_thr(history, util)
    s = sorted(history)
    n = len(s)
    q1, q3 = s[n // 4], s[(3 * n) // 4]
    thr = max(1.0 - S_IQR * (q3 - q1), 0.0)
    return util > thr


def detect_mad(history: Sequence[float], util: float) -> bool:
    if len(history) < 10:
        return detect_thr(history, util)
    med = _median(history)
    mad = _median([abs(x - med) for x in history])
    thr = max(1.0 - S_MAD * mad, 0.0)
    return util > thr


def _lr_predict(history: Sequence[float], robust: bool) -> float:
    """(Robust) local regression 1-step-ahead prediction (Loess-style)."""
    h = list(history)[-10:]
    n = len(h)
    if n < 3:
        return h[-1] if h else 0.0
    xs = list(range(n))
    w = [1.0] * n
    a = b = 0.0
    for it in range(3 if robust else 1):
        sw = sum(w)
        mx = sum(wi * xi for wi, xi in zip(w, xs)) / sw
        my = sum(wi * yi for wi, yi in zip(w, h)) / sw
        sxx = sum(wi * (xi - mx) ** 2 for wi, xi in zip(w, xs))
        if sxx < 1e-12:
            return h[-1]
        b = sum(wi * (xi - mx) * (yi - my) for wi, xi, yi in zip(w, xs, h)) / sxx
        a = my - b * mx
        if robust:
            resid = [abs(yi - (a + b * xi)) for xi, yi in zip(xs, h)]
            s = _median(resid) or 1e-9
            w = [(1 - min(r / (6 * s), 1.0) ** 2) ** 2 for r in resid]  # bisquare
    return a + b * n            # extrapolate one step


def detect_lr(history: Sequence[float], util: float, *, robust: bool = False) -> bool:
    if len(history) < 10:
        return detect_thr(history, util)
    return SAFETY_LR * _lr_predict(history, robust) >= 1.0


def detect_lrr(history: Sequence[float], util: float) -> bool:
    return detect_lr(history, util, robust=True)


DETECTORS: Dict[str, Callable[[Sequence[float], float], bool]] = {
    "thr": detect_thr, "iqr": detect_iqr, "mad": detect_mad,
    "lr": detect_lr, "lrr": detect_lrr,
}


# --------------------------------------------------------------------------
# VM selection (migration) — unified SelectionPolicy instances (C2)
# --------------------------------------------------------------------------

def make_vm_selector(kind: str, now_fn: Callable[[], float],
                     seed: int = 7) -> SelectionPolicy:
    if kind == "mmt":       # minimum migration time = min RAM
        return MinimumScore(lambda vm: vm.caps.ram)
    if kind == "mu":        # minimum utilization
        return MinimumScore(lambda vm: vm.utilization(now_fn()))
    if kind == "rs":
        return RandomSelection(seed)
    if kind == "mc":        # maximum correlation: proxy = max variance share
        def score(vm):
            h = list(vm.util_history)
            if len(h) < 2:
                return 0.0
            m = sum(h) / len(h)
            return sum((x - m) ** 2 for x in h) / len(h)
        return MaximumScore(score)
    raise ValueError(kind)


@dataclass
class ConsolidationAlgo:
    """One Table-2 row: a detector + a VM selector (or pure DVFS)."""
    name: str
    detector: Optional[str]            # None => Dvfs (no consolidation)
    vm_selector: Optional[str]

    @staticmethod
    def by_name(name: str) -> "ConsolidationAlgo":
        table = {
            "Dvfs":   ConsolidationAlgo("Dvfs", None, None),
            "MadMmt": ConsolidationAlgo("MadMmt", "mad", "mmt"),
            "ThrMu":  ConsolidationAlgo("ThrMu", "thr", "mu"),
            "IqrRs":  ConsolidationAlgo("IqrRs", "iqr", "rs"),
            "LrrMc":  ConsolidationAlgo("LrrMc", "lrr", "mc"),
        }
        return table[name]


ALGORITHMS = ["Dvfs", "MadMmt", "ThrMu", "IqrRs", "LrrMc"]


# --------------------------------------------------------------------------
# The consolidation manager (time-stepped, like the power package's examples)
# --------------------------------------------------------------------------

class ConsolidationManager:
    """Runs the detect→select→place loop each scheduling interval.

    Decision logic is engine-agnostic: the OO engines (6G/7G flavours) and
    the vectorized engine all call into the same routine so their *decisions*
    are identical and only mechanics differ (benchmark fairness).
    """

    def __init__(self, hosts: List[PowerHost], vms: List[TraceVm],
                 algo: ConsolidationAlgo, *, interval: float = 300.0, seed: int = 7):
        self.hosts = hosts
        self.vms = vms
        self.algo = algo
        self.interval = interval
        self.now = 0.0
        self.migrations = 0
        self._vm_selector = (make_vm_selector(algo.vm_selector, lambda: self.now, seed)
                             if algo.vm_selector else None)

    # -- utilization bookkeeping ------------------------------------------------
    # NOTE: demand is accumulated over guests in ascending-id order with a
    # fixed association so that every engine flavour (6g/7g/vec) produces
    # bit-identical utilizations — decision identity across engines is a
    # benchmark-fairness requirement (and is asserted in tests).
    def host_util(self, h: PowerHost, t: float) -> float:
        if not h.caps.total_mips:
            return 0.0
        demand = 0.0
        for vm in sorted(h.guests, key=lambda g: g.id):
            demand += vm.utilization(t) * vm.caps.total_mips  # type: ignore[attr-defined]
        return min(demand / h.caps.total_mips, 1.0)

    def record_step(self, t: float) -> None:
        self.now = t
        for vm in self.vms:
            vm.util_history.append(vm.utilization(t))
        for h in self.hosts:
            h.record_utilization(self.host_util(h, t), self.interval)

    # -- the consolidation pass ----------------------------------------------------
    def consolidate(self, t: float) -> int:
        if self.algo.detector is None:
            return 0
        detector = DETECTORS[self.algo.detector]
        migrating: List[TraceVm] = []
        # 1) drain overloaded hosts until no longer overloaded
        for h in self.hosts:
            if not h.active or not h.guests:
                continue
            util = self.host_util(h, t)
            hist = list(h.util_history)
            guests = list(h.guests)
            while guests and detector(hist, util):
                vm = self._vm_selector.select(guests)
                if vm is None:
                    break
                guests.remove(vm)
                migrating.append(vm)
                util -= vm.demand_mips(t) / h.caps.total_mips
        # 2) drain the least-utilized (underloaded) active host
        active = [h for h in self.hosts if h.active and h.guests]
        if len(active) > 1:
            under = MinimumScore(lambda h: self.host_util(h, t)).select(
                [h for h in active
                 if not detect_thr(list(h.util_history), self.host_util(h, t))])
            if under is not None:
                migrating.extend(under.guests)  # try to fully drain it
        # 3) place migrating VMs: power-aware best-fit (minimum power delta)
        done = 0
        for vm in migrating:
            src = vm.host
            candidates = [h for h in self.hosts
                          if h is not src and h.active and h.suitable_for(vm)
                          and not detector(list(h.util_history),
                                           self.host_util(h, t)
                                           + vm.demand_mips(t) / h.caps.total_mips)]
            dst = MinimumScore(
                lambda h: h.power_model.power(self.host_util(h, t)
                                              + vm.demand_mips(t) / h.caps.total_mips)
                          - h.power_model.power(self.host_util(h, t))
            ).select(candidates)
            if dst is None:
                continue
            src.deallocate(vm)
            dst.try_allocate(vm)
            done += 1
        # 4) power off fully drained hosts
        for h in self.hosts:
            if h.active and not h.guests:
                h.active = False
        self.migrations += done
        return done

    # -- summary ---------------------------------------------------------------
    def total_energy_kwh(self) -> float:
        return sum(h.energy_j for h in self.hosts) / 3.6e6


# --------------------------------------------------------------------------
# Workload synthesis (PlanetLab-like traces; the real package ships samples)
# --------------------------------------------------------------------------

def planetlab_like_trace(rng: random.Random, n_samples: int = 288) -> List[float]:
    """Random-walk + diurnal CPU trace in [0,1], PlanetLab-flavoured."""
    base = rng.uniform(0.05, 0.5)
    amp = rng.uniform(0.05, 0.4)
    phase = rng.uniform(0, 2 * math.pi)
    x, out = rng.uniform(0, 0.3), []
    for k in range(n_samples):
        diurnal = amp * 0.5 * (1 + math.sin(2 * math.pi * k / n_samples + phase))
        x = min(max(x + rng.gauss(0, 0.05), 0.0), 1.0)
        out.append(min(max(0.7 * (base + diurnal) + 0.3 * x, 0.0), 1.0))
    return out


# --------------------------------------------------------------------------
# Power-aware elastic datacenter (the ``power_batch`` scenario's OO side)
# --------------------------------------------------------------------------

MODEL_MIXES = ("mixed", "linear", "cubic", "spec", "dvfs")


def make_power_fleet(n_hosts: int, mix: str = "mixed") -> List[object]:
    """One power model per host.  ``mixed`` cycles through all four model
    families in two efficiency tiers (G4-class efficient, G5-class not),
    so energy-aware host selection has a real gradient to exploit."""
    mixed = [
        PowerModelLinear(86.0, 117.0),
        PowerModelCubic(93.7, 135.0),
        PowerModelSpecTable(SPEC_HP_ML110_G4),
        PowerModelDvfs(93.7, 135.0),
        PowerModelSpecTable(SPEC_HP_ML110_G5),
        PowerModelDvfs(86.0, 117.0),
    ]
    families = {
        "mixed": mixed,
        "linear": [PowerModelLinear(86.0, 117.0),
                   PowerModelLinear(93.7, 135.0)],
        "cubic": [PowerModelCubic(86.0, 117.0),
                  PowerModelCubic(93.7, 135.0)],
        "spec": [PowerModelSpecTable(SPEC_HP_ML110_G4),
                 PowerModelSpecTable(SPEC_HP_ML110_G5)],
        "dvfs": [PowerModelDvfs(86.0, 117.0),
                 PowerModelDvfs(93.7, 135.0)],
    }
    try:
        cycle = families[mix]
    except KeyError:
        raise ValueError(f"unknown model mix {mix!r}; "
                         f"known: {MODEL_MIXES}") from None
    return [cycle[i % len(cycle)] for i in range(n_hosts)]


def host_capacities(n_hosts: int, host_mips, host_pes=1
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """``(pes [H] int, per-PE MIPS [H] float64)`` of the elastic fleet.

    Each of ``host_mips`` and ``host_pes`` is one value for every host or
    a sequence of host types cycled over the hosts by index, as CloudSim's
    power examples assign ``hostType = i % HOST_TYPES`` (the same cycle
    :func:`make_power_fleet` gives the power models)."""
    mips = np.asarray(host_mips, np.float64).ravel()
    pes = np.asarray(host_pes).ravel()
    if mips.size == 0 or pes.size == 0:
        raise ValueError("host_mips and host_pes need at least one value")
    if pes.dtype.kind not in "iu" or np.any(pes < 1):
        raise ValueError(f"host_pes must be whole numbers ≥ 1, not {host_pes}")
    return (np.resize(pes.astype(np.int64), n_hosts),
            np.resize(mips, n_hosts))


def elastic_demand_trace(rng: random.Random, n_samples: int) -> List[float]:
    """Aggregate per-VM utilization trace in [0, 1]: triangle-wave diurnal
    swing + bounded random walk.

    Deliberately libm-free (``rng.uniform`` + arithmetic only, no
    ``sin``/``gauss``): the trace is the sole stochastic input of the
    elastic scenario, and keeping it free of platform-dependent
    transcendental rounding keeps the committed golden fixtures bit-stable
    across machines.
    """
    walk = rng.uniform(0.2, 0.8)
    out = []
    for k in range(n_samples):
        phase = k / n_samples
        diurnal = 1.0 - 2.0 * abs(phase - 0.5)          # 0 → 1 → 0 triangle
        walk = min(max(walk + rng.uniform(-0.08, 0.08), 0.0), 1.0)
        out.append(min(max(0.1 + 0.6 * diurnal + 0.3 * (walk - 0.5),
                           0.02), 1.0))
    return out


def power_fault_table(fault_plan: Optional[FaultPlan], n_hosts: int,
                      n_samples: int, interval: float) -> Optional[np.ndarray]:
    """``[K, H]`` bool: host ``h`` failed during interval ``k`` — the one
    compiled fault view both power backends consume.

    The scenario is time-stepped, so windows resolve at the interval
    decision times ``k·interval`` under the plan's half-open rule (a
    window starting exactly at ``k·interval`` is visible to interval
    ``k``).  The OO path replays rows of this table as priority ``-1``
    events at the changed intervals; the vec loop indexes it directly —
    same table, same rule, bit-exact either way.
    """
    if fault_plan is None:
        return None
    for kind in ("link", "region", "transient"):
        if fault_plan.has(kind):
            raise ValueError(
                f"power_batch supports only 'node' fault windows "
                f"(host crashes), got a {kind!r} event")
    fault_plan.check_targets("node", n_hosts, "host")
    times = np.arange(n_samples, dtype=np.float64) * float(interval)
    tbl = fault_plan.down_mask("node", times, n_hosts)
    dead = np.all(tbl, axis=1)
    if dead.any():
        k = int(np.argmax(dead))
        raise ValueError(
            f"power_batch: fault plan fails all {n_hosts} hosts during "
            f"interval {k} (t={k * float(interval)}) — at least one host "
            f"must survive")
    return tbl


class ElasticDatacenterManager:
    """Threshold autoscaler over a fleet of :class:`PowerHost`\\ s — the OO
    reference for the ``power_batch`` scenario (the decision/accounting
    loop ``vec_power`` compiles into one ``lax.while_loop``).

    Per interval k: every VM demands ``trace[k] · vm_mips``; VMs are spread
    evenly (by count, in host-index order) over the active hosts; per-host
    energy integrates the host's power table at its utilization; SLA
    violation time accrues on every overloaded host.  At the interval's
    end, when the cooldown has expired, one scaling action may fire:

      * scale-out — some active host runs above ``up_thr`` and a host is
        off: power on the *most efficient* inactive host (min watts/MIPS at
        full load, the C2 ``MinimumScore`` policy; ties → lowest index);
      * scale-in — every active host runs below ``lo_thr`` and more than
        ``min_active`` hosts are on: drain the *least efficient* active
        host (``MaximumScore``) and power it off.

    Either action rebalances to the even split and counts each VM that
    lands on a new host as one migration.

    Bit-exactness contract (asserted by tests + the differential suite):
    every float here is computed by the same IEEE-754 ops, in the same
    order, as ``vec_power._simulate_one`` — utilization from a single
    ``count · demand`` product (never a VM-by-VM sum), energy/SLA/unserved
    tracked as *exact* accumulations (segment-hit counts, frac sums,
    interval counts — see :func:`table_segment`) with every float multiply
    deferred to the shared host-side finalizers (:func:`segment_energy_j`),
    and per-host accumulators summed to scalars only via ``np.sum`` on the
    host side.
    """

    def __init__(self, hosts: List[PowerHost], vms: List[Vm],
                 trace: Sequence[float], *, vm_mips: float,
                 up_thr: float = 0.8, lo_thr: float = 0.3,
                 cooldown_k: int = 3, min_active: int = 1,
                 init_active: Optional[int] = None,
                 interval: float = 300.0, n_points: int = 11):
        self.hosts = hosts
        self.vms = vms
        self.trace = [float(u) for u in trace]
        self.vm_mips = float(vm_mips)
        self.up_thr = float(up_thr)
        self.lo_thr = float(lo_thr)
        self.cooldown_k = int(cooldown_k)
        self.min_active = max(int(min_active), 1)
        self.interval = float(interval)
        H = len(hosts)
        if not 1 <= self.min_active <= H:
            raise ValueError("min_active must be in [1, n_hosts]")
        init_active = H if init_active is None else int(init_active)
        if not self.min_active <= init_active <= H:
            raise ValueError("init_active must be in [min_active, n_hosts]")
        min_host_mips = min(h.caps.mips for h in hosts)
        if self.vm_mips > min_host_mips:
            raise ValueError(
                f"vm_mips ({self.vm_mips}) must be ≤ every host's per-PE "
                f"MIPS ({min_host_mips}): a VM must fit a time-shared host")
        # SoA mirrors of the fleet (shared bit-for-bit with the vec engine).
        self.caps = np.asarray([h.caps.total_mips for h in hosts], np.float64)
        self.tables = np.asarray([power_points(h.power_model, n_points)
                                  for h in hosts], np.float64)
        self.eff = self.tables[:, -1] / self.caps      # watts/MIPS, full load
        self._pick_on = most_power_efficient(lambda i: self.eff[i])
        self._pick_off = least_power_efficient(lambda i: self.eff[i])
        # exact accumulators (floats multiplied only in result())
        self.n_points = int(n_points)
        self.seg_count = np.zeros((H, n_points - 1), np.int32)
        self.seg_frac = np.zeros((H, n_points - 1), np.float64)
        self.over_count = np.zeros(H, np.int32)
        self.unserved_mips = np.zeros(H, np.float64)
        self.migrations = 0
        self.scale_out_events = 0
        self.scale_in_events = 0
        self.cooldown = 0
        self.failed = np.zeros(H, bool)    # live host-crash mask (faults)
        self.events: List[Tuple[int, str, int]] = []   # (k, action, host)
        # initial placement: first ``init_active`` hosts on, even VM split
        for i, h in enumerate(hosts):
            h.active = i < init_active
        self._rebalance()

    # -- placement ---------------------------------------------------------
    def _even_targets(self) -> List[int]:
        """Even VM split over active hosts, in host-index order: the first
        ``V mod A`` active hosts take the extra VM."""
        targets = [0] * len(self.hosts)
        active = [i for i, h in enumerate(self.hosts) if h.active]
        base = len(self.vms) // len(active)
        rem = len(self.vms) - base * len(active)
        for rank, i in enumerate(active):
            targets[i] = base + (1 if rank < rem else 0)
        return targets

    def _rebalance(self) -> int:
        """Move VMs (host-index order, excess hosts pop from the tail) until
        every host holds its even-split target; returns VMs that moved."""
        targets = self._even_targets()
        pool: List[Vm] = [vm for vm in self.vms if vm.host is None]
        for i, h in enumerate(self.hosts):
            while len(h.guests) > targets[i]:
                vm = h.guests[-1]
                h.deallocate(vm)
                pool.append(vm)
        moved = 0
        for i, h in enumerate(self.hosts):
            while len(h.guests) < targets[i]:
                vm = pool.pop()
                if not h.try_allocate(vm):
                    raise RuntimeError(f"rebalance failed on host {i}")
                moved += 1
        assert not pool, "rebalance lost VMs"
        return moved

    # -- fault handling ----------------------------------------------------
    def apply_fault_mask(self, failed: Sequence[bool]) -> None:
        """Adopt one row of :func:`power_fault_table` (degraded-capacity
        operation).  Newly failed hosts power off and shed their VMs; if
        no active host would remain, the most efficient surviving host is
        kept alive; one rebalance absorbs the displaced VMs (counted as
        migrations).  Cooldown is deliberately untouched — a crash is not
        a scaling action.  The vec loop applies the identical rule from
        the same table, so faulted runs stay bit-exact."""
        self.failed = np.asarray(failed, bool).copy()
        before = [h.active for h in self.hosts]
        for h, f in zip(self.hosts, self.failed):
            if f and h.active:
                h.active = False
        if not any(h.active for h in self.hosts):
            i = self._pick_on.select(
                [i for i in range(len(self.hosts)) if not self.failed[i]])
            self.hosts[i].active = True
        if [h.active for h in self.hosts] != before:
            self.migrations += self._rebalance()

    # -- one interval ------------------------------------------------------
    def step(self, k: int) -> None:
        H = len(self.hosts)
        d = self.trace[k] * self.vm_mips               # per-VM MIPS demand
        utils = [0.0] * H
        for i, h in enumerate(self.hosts):
            demand = len(h.guests) * d
            cap = float(self.caps[i])
            util = min(demand / cap, 1.0)
            utils[i] = util
            if h.active:
                s, frac = table_segment(util, self.n_points)
                self.seg_count[i, s] += 1
                self.seg_frac[i, s] += frac
            if demand > cap:
                self.over_count[i] += 1
            # max(demand, cap) - cap ≡ max(demand - cap, 0) — written so no
            # multiply feeds the subtraction (the vec engine's FMA-immunity
            # form; see segment_energy_j).
            self.unserved_mips[i] += max(demand, cap) - cap
        # -- autoscale decision (end of interval; affects interval k+1) ----
        active_idx = [i for i, h in enumerate(self.hosts) if h.active]
        n_act = len(active_idx)
        avail = H - int(self.failed.sum())    # degraded capacity under faults
        can = self.cooldown == 0
        any_over = any(utils[i] > self.up_thr for i in active_idx)
        all_under = max(utils[i] for i in active_idx) < self.lo_thr
        want_out = can and any_over and n_act < avail
        want_in = (can and not want_out and all_under
                   and n_act > self.min_active)
        if want_out:
            i = self._pick_on.select(
                [i for i in range(H)
                 if not self.hosts[i].active and not self.failed[i]])
            self.hosts[i].active = True
            self.scale_out_events += 1
            self.events.append((k, "out", i))
        elif want_in:
            i = self._pick_off.select(active_idx)
            self.hosts[i].active = False
            self.scale_in_events += 1
            self.events.append((k, "in", i))
        if want_out or want_in:
            self.migrations += self._rebalance()
            self.cooldown = self.cooldown_k
        else:
            self.cooldown = max(self.cooldown - 1, 0)

    # -- summary -----------------------------------------------------------
    def result(self) -> Dict[str, object]:
        energy_j = segment_energy_j(self.tables, self.seg_count,
                                    self.seg_frac, self.interval)
        return dict(
            energy_wh=energy_j / 3600.0,
            sla_s=self.over_count * np.float64(self.interval),
            unserved_mips_s=self.unserved_mips * np.float64(self.interval),
            migrations=np.int32(self.migrations),
            scale_out_events=np.int32(self.scale_out_events),
            scale_in_events=np.int32(self.scale_in_events),
            final_active=np.int32(sum(1 for h in self.hosts if h.active)),
            iterations=np.int32(len(self.trace)))


def check_demand(demand) -> np.ndarray:
    """Validate an injected demand curve (a trace-replay
    :func:`repro.core.trace.demand_curve` product or a hand-built array):
    1-D, finite, in [0, 1].  Returns the canonical f64 array whose values
    both backends consume verbatim (bit-exactness)."""
    d = np.asarray(demand, np.float64)
    if d.ndim != 1 or d.shape[0] < 1:
        raise ValueError(f"power_batch: demand must be a non-empty 1-D "
                         f"utilization curve, got shape {d.shape}")
    if not np.all(np.isfinite(d)) or float(d.min()) < 0.0 \
            or float(d.max()) > 1.0:
        raise ValueError("power_batch: demand values must be finite "
                         "utilizations in [0, 1]")
    return d


def make_elastic_scenario(n_hosts: int, n_vms: int, *, seed: int,
                          n_samples: int, host_mips, vm_mips: float,
                          model_mix: str = "mixed", demand=None,
                          host_pes=1
                          ) -> Tuple[List[PowerHost], List[Vm], List[float]]:
    """Hosts (capacities and power models cycled by host type, see
    :func:`host_capacities`), identical VMs, and the cell's demand trace —
    shared verbatim by the OO and vec backends.  An injected ``demand``
    curve (trace replay) supersedes the seeded one."""
    models = make_power_fleet(n_hosts, model_mix)
    pes, mips = host_capacities(n_hosts, host_mips, host_pes)
    hosts = [PowerHost(num_pes=int(n), mips=float(m), ram=1e12, bw=1e15,
                       guest_scheduler="time", power_model=model)
             for n, m, model in zip(pes, mips, models)]
    vms = [Vm(CloudletSchedulerTimeShared(), num_pes=1, mips=vm_mips,
              ram=1.0, bw=1.0) for _ in range(n_vms)]
    trace = ([float(x) for x in demand] if demand is not None
             else elastic_demand_trace(random.Random(seed), n_samples))
    return hosts, vms, trace


def make_consolidation_scenario(n_hosts: int = 50, n_vms: int = 100, *,
                                seed: int = 1, n_samples: int = 288,
                                interval: float = 300.0
                                ) -> Tuple[List[PowerHost], List[TraceVm]]:
    rng = random.Random(seed)
    hosts = [PowerHost(num_pes=2, mips=2660.0 if i % 2 else 1860.0,
                       ram=8192.0, bw=1e9, guest_scheduler="time",
                       power_model=PowerModelLinear(86.0 if i % 2 else 93.7,
                                                    117.0 if i % 2 else 135.0))
             for i in range(n_hosts)]
    vm_types = [(1, 2500.0, 870.0), (1, 2000.0, 1740.0),
                (1, 1000.0, 1740.0), (1, 500.0, 613.0)]
    vms = []
    for i in range(n_vms):
        pes, mips, ram = vm_types[i % len(vm_types)]
        vms.append(TraceVm(planetlab_like_trace(rng, n_samples), interval,
                           num_pes=pes, mips=mips, ram=ram, bw=1e8))
    # initial placement: round-robin first-fit
    hi = 0
    for vm in vms:
        placed = False
        for k in range(len(hosts)):
            h = hosts[(hi + k) % len(hosts)]
            if h.try_allocate(vm):
                hi = (hi + k + 1) % len(hosts)
                placed = True
                break
        if not placed:
            raise RuntimeError("scenario over-packed: increase hosts")
    return hosts, vms


# -- power_batch: shared accounting + the OO (legacy/oo) reference -------------

def _finalize(out: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Datacenter-level totals from the per-host accumulators.

    Shared by the oo and vec handlers so the scalar reductions are the same
    ``np.sum`` (pairwise) over bit-identical per-host arrays — keeping the
    totals in the bit-exactness contract too.
    """
    out = dict(out)
    out["energy_total_wh"] = np.sum(out["energy_wh"], axis=-1)
    out["sla_total_s"] = np.sum(out["sla_s"], axis=-1)
    out["unserved_total_mips_s"] = np.sum(out["unserved_mips_s"], axis=-1)
    return out


def _broadcast_cells(seeds, axes: Dict):
    """Broadcast ``seeds`` against the sweep axes → (seeds[B], axes[B], B)
    (the substrate's shared batch contract)."""
    from .vec_engine import broadcast_cells
    return broadcast_cells(seeds, axes)


def _empty_outputs(n_hosts: int):
    zf = np.empty((0, n_hosts), np.float64)
    zi = np.empty((0,), np.int32)
    return _finalize(dict(
        energy_wh=zf, sla_s=zf, unserved_mips_s=zf, migrations=zi,
        scale_out_events=zi, scale_in_events=zi, final_active=zi,
        iterations=zi))


def _finalize_accumulators(out: Dict[str, np.ndarray], tables: np.ndarray,
                           interval) -> Dict[str, np.ndarray]:
    """Exact loop accumulators → public per-host metrics (host-side numpy;
    op-for-op what ``ElasticDatacenterManager.result`` computes)."""
    interval = np.float64(interval)
    out = dict(out)
    energy_j = segment_energy_j(tables, out.pop("seg_count"),
                                out.pop("seg_frac"), interval)
    out["energy_wh"] = energy_j / 3600.0
    out["sla_s"] = out.pop("over_count") * interval
    out["unserved_mips_s"] = out.pop("unserved_mips") * interval
    return out


class _AutoscaleEntity(SimEntity):
    """Periodic AUTOSCALE driver running the elastic manager inside a
    Simulation (the legacy/oo engine flavours differ only in queue
    mechanics — decisions and accounting live in the manager)."""

    def __init__(self, sim, mgr: "ElasticDatacenterManager",
                 n_intervals: int):
        super().__init__(sim, "autoscaler")
        self.mgr = mgr
        self.n_intervals = n_intervals
        self._k = 0

    def start(self) -> None:
        if self.n_intervals > 0:
            self.sim.schedule(0.0, Tag.AUTOSCALE, self)

    def process_event(self, ev) -> None:
        if ev.tag is Tag.AUTOSCALE:
            self.mgr.step(self._k)
            self._k += 1
            if self._k < self.n_intervals:
                # k·interval, not ev.time + interval: the absolute form lands
                # on exactly the timestamps _HostFaultEntity schedules at, so
                # a priority -1 crash event at k·interval always sorts ahead
                # of interval k's AUTOSCALE.
                self.sim.schedule(self._k * self.mgr.interval, Tag.AUTOSCALE,
                                  self)


class _HostFaultEntity(SimEntity):
    """Replays the changed rows of a :func:`power_fault_table` as priority
    ``-1`` events, so the manager adopts interval ``k``'s crash mask before
    that interval's AUTOSCALE step runs.  Scheduling only *changed* rows is
    equivalent to applying every row: at an unchanged interval
    ``apply_fault_mask`` is the identity (no newly-failed active host, no
    empty active set), which is also why the vec loop may apply the table
    unconditionally each interval and still agree bit-for-bit."""

    def __init__(self, sim, mgr: "ElasticDatacenterManager",
                 fail_tbl: np.ndarray):
        super().__init__(sim, "host-faults")
        self.mgr = mgr
        self.fail_tbl = fail_tbl

    def start(self) -> None:
        prev = np.zeros(self.fail_tbl.shape[1], bool)
        for k, row in enumerate(self.fail_tbl):
            if np.any(row != prev):
                self.sim.schedule(k * self.mgr.interval, Tag.NODE_FAILURE,
                                  self, data=k, priority=-1)
            prev = row

    def process_event(self, ev) -> None:
        if ev.tag is Tag.NODE_FAILURE:
            self.mgr.apply_fault_mask(self.fail_tbl[ev.data])


def _run_elastic_cell(backend, *, seed: int, n_hosts: int,
                      n_vms: int, n_samples: int, interval: float,
                      host_mips, host_pes, vm_mips: float, up_thr: float,
                      lo_thr: float, cooldown: int, min_active: int,
                      init_active, model_mix: str, n_points: int,
                      fail_tbl: Optional[np.ndarray] = None,
                      demand=None) -> Dict:
    hosts, vms, trace = make_elastic_scenario(
        n_hosts, n_vms, seed=seed, n_samples=n_samples,
        host_mips=host_mips, host_pes=host_pes, vm_mips=vm_mips,
        model_mix=model_mix, demand=demand)
    mgr = ElasticDatacenterManager(
        hosts, vms, trace, vm_mips=vm_mips, up_thr=up_thr, lo_thr=lo_thr,
        cooldown_k=cooldown, min_active=min_active, init_active=init_active,
        interval=interval, n_points=n_points)
    sim = backend.make_simulation()
    _AutoscaleEntity(sim, mgr, n_samples)
    if fail_tbl is not None:
        _HostFaultEntity(sim, mgr, fail_tbl)
    sim.run()
    return mgr.result()


def _power_batch_oo(backend, *, seeds=(0,), n_hosts: int = 8,
                    n_vms: int = 32, n_samples: int = 288,
                    interval: float = 300.0, host_mips=8000.0, host_pes=1,
                    vm_mips=1000.0, up_thr=0.8, lo_thr=0.3, cooldown=3,
                    min_active: int = 1, init_active=None,
                    model_mix: str = "mixed", n_points: int = 11,
                    fault_plan: Optional[FaultPlan] = None, demand=None,
                    chunk_size=None, with_report: bool = False, **_ignored):
    """Reference semantics for the power sweep: run the OO elastic manager
    (event-driven, one cell at a time) over every scenario point — what the
    vec path replaces with one compiled vmap call.  Cells route through the
    sweep layer's host path so ``run_sweep`` sees a populated report.
    (Registered for legacy/oo in :mod:`repro.core.vec_power`.)"""
    from .sweep import run_host_sweep
    from .vec_engine import empty_report
    if demand is not None:
        demand = check_demand(demand)
        n_samples = int(demand.shape[0])
    fail_tbl = power_fault_table(fault_plan, n_hosts, n_samples, interval)
    seeds, axes, b = _broadcast_cells(seeds, dict(
        up_thr=up_thr, lo_thr=lo_thr, cooldown=cooldown, vm_mips=vm_mips))
    if b == 0:
        out, report = _empty_outputs(n_hosts), empty_report(donate=False)
        return (out, report) if with_report else out

    def run_cell(i: int) -> Dict:
        return _run_elastic_cell(
            backend, seed=int(seeds[i]), n_hosts=n_hosts, n_vms=n_vms,
            n_samples=n_samples, interval=interval, host_mips=host_mips,
            host_pes=host_pes, vm_mips=float(axes["vm_mips"][i]),
            up_thr=float(axes["up_thr"][i]), lo_thr=float(axes["lo_thr"][i]),
            cooldown=int(axes["cooldown"][i]), min_active=min_active,
            init_active=init_active, model_mix=model_mix, n_points=n_points,
            fail_tbl=fail_tbl, demand=demand)

    rows, report = run_host_sweep(run_cell, b, chunk_size=chunk_size)
    out = _finalize({k: np.stack([np.asarray(r[k]) for r in rows])
                     for k in rows[0]})
    return (out, report) if with_report else out
