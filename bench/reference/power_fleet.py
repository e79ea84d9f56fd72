"""Plain reference for ``power_batch`` over a fleet of host types: the
threshold autoscaler of an elastic, power-aware datacenter, stated once
more in numpy over a batch of cells.  It imports nothing of the program
under test: the demand traces, the power tables and the accounting are all
rebuilt here from the cells' seeds and thresholds.

Hosts come in types cycled by index, as CloudSim's power examples assign
``hostType = i % HOST_TYPES``: ``host_mips`` (per PE), ``host_pes`` and the
power models of ``model_mix`` each take host ``h`` from entry
``h mod len``.  A host's capacity is ``host_pes * host_mips``.

Semantics, per interval ``k`` of ``n_samples`` (CloudSim's power examples
with a threshold autoscaler in place of VM consolidation):

* every VM demands ``trace[k] * vm_mips``; the VMs are spread evenly, by
  count and in host order, over the active hosts (the first ``V mod A``
  active hosts take one more);
* each host's utilization is ``min(count * demand / cap, 1)``; an active
  host's power comes from its 11-point table by linear interpolation;
  a host whose demand exceeds its capacity adds one interval of SLA
  violation and its excess MIPS;
* at the interval's end, once the cooldown is over, one action may fire:
  scale out (an active host above ``up_thr`` and a host still off: power
  on the off host with the least watts per MIPS, first on ties) or scale
  in (every active host below ``lo_thr`` and more than ``min_active`` on:
  power off the active host with the most watts per MIPS, first on ties);
  either rebalances the even split, counting each VM that lands on a new
  host as one migration, and restarts the cooldown.

Energy is kept as per-segment hit counts and sums of the position within
the segment, and turned into joules once at the end, so that the f64
reference is exact and does not depend on the order of additions.
``dtype=np.float32`` computes the same in single precision: the control
that a correct check has to reject.
"""
from __future__ import annotations

import random
from typing import Dict

import numpy as np

# SPECpower_ssj2008 tables of the two hosts in CloudSim's power examples
# (HP ProLiant ML110 G4 and G5): watts at 0 %, 10 %, ..., 100 % load.
SPEC_G4 = (86.0, 89.4, 92.6, 96.0, 99.5, 102.0, 106.0, 108.0, 112.0, 114.0,
           117.0)
SPEC_G5 = (93.7, 97.0, 101.0, 105.0, 110.0, 116.0, 121.0, 125.0, 129.0,
           133.0, 135.0)
# Inputs that take one value per cell; the rest are the deployment's.
CELL_KEYS = ("seeds", "up_thr", "lo_thr", "cooldown", "vm_mips")


def _interp(points, util: float) -> float:
    u = min(max(util, 0.0), 1.0)
    n = len(points)
    x = u * (n - 1)
    k = min(int(x), n - 2)
    return points[k] + (points[k + 1] - points[k]) * (x - k)


def _spec(points):
    return lambda u: _interp(points, u)


# model_mix "spec": CloudSim's own pair (HOST_POWER in its power examples'
# Constants), cycled over the hosts G4, G5, G4, ...
SPEC = (_spec(SPEC_G4), _spec(SPEC_G5))


def power_tables(n_hosts: int, model_mix: str, n_points: int) -> np.ndarray:
    """``[H, n_points]`` watts at evenly spaced utilizations, per host."""
    if model_mix != "spec":
        raise ValueError(f"reference implements model_mix='spec' only, "
                         f"not {model_mix!r}")
    models = SPEC
    return np.asarray(
        [[models[h % len(models)](k / (n_points - 1))
          for k in range(n_points)] for h in range(n_hosts)], np.float64)


def capacities(n_hosts: int, host_mips, host_pes) -> np.ndarray:
    """``[H]`` MIPS per host: PEs times per-PE MIPS of its type."""
    mips = np.resize(np.asarray(host_mips, np.float64).ravel(), n_hosts)
    pes = np.resize(np.asarray(host_pes, np.int64).ravel(), n_hosts)
    return pes * mips


def demand_trace(seed: int, n_samples: int) -> np.ndarray:
    """Per-VM utilization in [0, 1]: a triangle-wave day plus a bounded
    random walk, drawn from ``random.Random(seed)``."""
    rng = random.Random(int(seed))
    walk = rng.uniform(0.2, 0.8)
    out = []
    for k in range(n_samples):
        diurnal = 1.0 - 2.0 * abs(k / n_samples - 0.5)
        walk = min(max(walk + rng.uniform(-0.08, 0.08), 0.0), 1.0)
        out.append(min(max(0.1 + 0.6 * diurnal + 0.3 * (walk - 0.5), 0.02),
                       1.0))
    return np.asarray(out, np.float64)


def _even_counts(active: np.ndarray, n_vms: int) -> np.ndarray:
    a = active.astype(np.int64)
    rank = np.cumsum(a, axis=1) - 1
    n_act = np.maximum(a.sum(axis=1, keepdims=True), 1)
    base = n_vms // n_act
    rem = n_vms - base * n_act
    return np.where(active, base + (rank < rem), 0)


def simulate(cells: Dict[str, np.ndarray], *, n_hosts: int, n_vms: int,
             n_samples: int, interval: float, host_mips, host_pes=1,
             model_mix: str = "spec", n_points: int = 11,
             min_active: int = 1, init_active=None,
             dtype=np.float64) -> Dict[str, np.ndarray]:
    """Every output of ``power_batch`` for a batch of cells.

    ``cells`` holds per-cell ``seeds``, ``up_thr``, ``lo_thr``, ``cooldown``
    and ``vm_mips`` arrays of one length."""
    f = np.dtype(dtype).type
    seeds = np.asarray(cells["seeds"])
    c = len(seeds)
    H, P = int(n_hosts), int(n_points)
    min_active = max(int(min_active), 1)
    init_active = H if init_active is None else int(init_active)
    trace = np.stack([demand_trace(s, n_samples) for s in seeds]).astype(f)
    vm_mips = np.asarray(cells["vm_mips"], np.float64).astype(f)[:, None]
    up = np.asarray(cells["up_thr"], np.float64).astype(f)[:, None]
    lo = np.asarray(cells["lo_thr"], np.float64).astype(f)[:, None]
    cool_k = np.asarray(cells["cooldown"], np.int64)
    tables64 = power_tables(H, model_mix, P)
    tables = tables64.astype(f)
    cap = capacities(H, host_mips, host_pes).astype(f)
    eff = tables[:, -1] / cap

    rows = np.arange(c)[:, None]
    hosts = np.arange(H)[None, :]
    active = np.broadcast_to(hosts < init_active, (c, H)).copy()
    count = _even_counts(active, n_vms)
    cooldown = np.zeros(c, np.int64)
    seg_count = np.zeros((c, H, P - 1), np.int64)
    seg_frac = np.zeros((c, H, P - 1), f)
    over_count = np.zeros((c, H), np.int64)
    unserved = np.zeros((c, H), f)
    migrations = np.zeros(c, np.int64)
    scale_out = np.zeros(c, np.int64)
    scale_in = np.zeros(c, np.int64)
    for k in range(n_samples):
        d = trace[:, k:k + 1] * vm_mips
        demand = count.astype(f) * d
        util = np.minimum(demand / cap, f(1.0))
        x = util * f(P - 1)
        seg = np.minimum(x.astype(np.int64), P - 2)
        frac = np.where(x >= P - 1, f(1.0), np.fmod(x, f(1.0)))
        r, h = np.nonzero(active)
        seg_count[r, h, seg[r, h]] += 1
        seg_frac[r, h, seg[r, h]] += frac[r, h]
        over_count += demand > cap
        unserved += np.maximum(demand, cap) - cap

        n_act = active.sum(axis=1)
        can = cooldown == 0
        any_over = np.any(active & (util > up), axis=1)
        all_under = np.max(np.where(active, util, -np.inf), axis=1) < lo[:, 0]
        want_out = can & any_over & (n_act < H)
        want_in = can & ~want_out & all_under & (n_act > min_active)
        pick_on = np.argmin(np.where(active, np.inf, eff), axis=1)
        pick_off = np.argmax(np.where(active, eff, -np.inf), axis=1)
        new_active = active.copy()
        new_active[rows[want_out, 0], pick_on[want_out]] = True
        new_active[rows[want_in, 0], pick_off[want_in]] = False
        changed = want_out | want_in
        new_count = np.where(changed[:, None],
                             _even_counts(new_active, n_vms), count)
        migrations += np.where(
            changed, np.maximum(new_count - count, 0).sum(axis=1), 0)
        scale_out += want_out
        scale_in += want_in
        cooldown = np.where(changed, cool_k, np.maximum(cooldown - 1, 0))
        active, count = new_active, new_count

    lo_t, hi_t = tables[:, :-1], tables[:, 1:]
    watts = (seg_count.astype(f) * lo_t + (hi_t - lo_t) * seg_frac).sum(axis=-1)
    energy_wh = watts * f(interval) / f(3600.0)
    sla_s = over_count.astype(f) * f(interval)
    unserved_mips_s = unserved * f(interval)
    return dict(
        energy_wh=energy_wh, sla_s=sla_s, unserved_mips_s=unserved_mips_s,
        energy_total_wh=energy_wh.sum(axis=-1),
        sla_total_s=sla_s.sum(axis=-1),
        unserved_total_mips_s=unserved_mips_s.sum(axis=-1),
        migrations=migrations, scale_out_events=scale_out,
        scale_in_events=scale_in, final_active=active.sum(axis=1),
        iterations=np.full(c, n_samples, np.int64))


def events(cells: Dict[str, np.ndarray], *, n_samples: int,
           **_) -> np.ndarray:
    """Loop iterations each cell runs: one per interval."""
    return np.full(len(cells["seeds"]), int(n_samples), np.int64)
