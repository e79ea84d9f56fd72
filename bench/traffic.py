"""The one generator of sweep inputs: a traffic mix is a JSON file of
parameters, and this turns it, with the configuration, ``--seed`` and the
sweep's index, into one sweep's per-cell arrays.

A mix stands for one caller of a policy search (the cross-entropy method
of ``repro.core.search``): every sweep scores a fresh population of
``members`` candidate policies, each replicated over the same
``seeds_per_member`` scenario seeds.  Keys of a mix:

* ``members``, ``seeds_per_member``: the sweep has their product of cells;
* ``member_axes``: ``{axis: {"uniform": [lo, hi]}}`` draws one value per
  member; ``{axis: {"placement_keys": [lo, hi]}}`` draws one key per
  machine and decodes them into an ``llmserve_batch`` placement (sort
  machines by key, descending and stable, deal them stage-major);
* ``repair``: ``{"less": [a, b], "set": {...}}``: members whose ``a`` is
  not below ``b`` take the ``set`` values, as an objective repairs
  inverted thresholds;
* ``sweep``: ``SweepConfig`` fields for the sweep;
* ``check_lanes``: lanes of each sweep kept for the comparison with the
  plain reference.

Every seed draws the same sizes; only the values differ.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np


def rng_for(seed: int, *index: int) -> np.random.Generator:
    """A generator keyed by ``--seed`` (any whole number) and indices."""
    return np.random.default_rng([int(seed) % 2 ** 64, *index])


def placement_from_keys(keys: np.ndarray, n_pipelines: int,
                        n_stages: int) -> np.ndarray:
    """``[P_pop, M]`` machine keys → ``[P_pop, n_pipelines, n_stages]``
    machine ids: the highest keys, dealt stage-major."""
    order = np.argsort(-keys, axis=-1, kind="stable")[:, :n_pipelines * n_stages]
    return np.transpose(order.reshape(-1, n_stages, n_pipelines), (0, 2, 1))


def n_cells(traffic: Dict[str, Any]) -> int:
    return int(traffic["members"]) * int(traffic["seeds_per_member"])


def sweep_cells(traffic: Dict[str, Any], params: Dict[str, Any], seed: int,
                index: int) -> Dict[str, np.ndarray]:
    """Per-cell arrays of sweep ``index`` (``seeds`` and every axis)."""
    rng = rng_for(seed, index)
    m, k = int(traffic["members"]), int(traffic["seeds_per_member"])
    cells = {"seeds": np.tile(rng.integers(0, 2 ** 31, k), m)}
    member = {}
    for axis in sorted(traffic.get("member_axes", {})):
        spec = traffic["member_axes"][axis]
        if "uniform" in spec:
            lo, hi = spec["uniform"]
            member[axis] = rng.uniform(lo, hi, m)
        elif "placement_keys" in spec:
            lo, hi = spec["placement_keys"]
            stages = int(params["n_stages"])
            keys = rng.uniform(lo, hi, (m, int(params["n_machines"])))
            member[axis] = placement_from_keys(
                keys, int(params["n_machines"]) // stages, stages)
        else:
            raise ValueError(f"member axis {axis!r}: unknown draw {spec}")
    rep = traffic.get("repair")
    if rep:
        a, b = rep["less"]
        bad = ~(member[a] < member[b])
        for axis, v in rep["set"].items():
            member[axis] = np.where(bad, v, member[axis])
    for axis, v in member.items():
        cells[axis] = np.repeat(v, k, axis=0)
    return cells
