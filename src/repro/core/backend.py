"""SimBackend — the standardized engine-selection substrate.

CloudSim 7G's core contribution is a re-engineered internal architecture with
*standardized interfaces* so multiple extensions run in one simulated
environment (paper §4).  This module is that interface for the repo's three
engine flavours, which previously each had hand-rolled three-way dispatch
(``consolidation_sim``'s ``_MANAGERS``/``_SIMS`` dicts, ``cluster``'s
OO-only path, per-benchmark engine loops):

  ``legacy``  ≤6G mechanics — O(n) linked-list queue, boxed histories,
              uncached recomputation (benchmark baseline; alias ``6g``).
  ``oo``      the 7G re-engineered object kernel — heap queue, cached
              paths (the reference semantics; alias ``7g``).
  ``vec``     beyond-paper structure-of-arrays engines — JAX ``jit``/``vmap``
              batched paths (``vec_scheduler``, ``vec_cluster``,
              consolidation-vec) with optional Pallas next-event fusion.

Two registries:

  * **backends** — ``get_backend(name)`` → :class:`SimBackend` (accepts the
    ``6g``/``7g`` aliases everywhere a backend name is taken);
  * **scenarios** — scenario kinds (``"consolidation"``, ``"fleet"``,
    ``"fleet_batch"``, ``"case_study"``, ``"cloudlet_batch"``,
    ``"workflow_batch"``, ``"consolidation_batch"``, ``"power_batch"``,
    ``"netdc_batch"``) registered by their home modules via the
    :func:`scenario` decorator, keyed per backend.

The single entry point is ``run_scenario(kind, backend=..., **params)`` (or
``SimBackend.run_scenario``): modules and benchmarks select engines through
it instead of dispatching by hand.  A backend without an implementation for
a scenario raises :class:`ScenarioUnsupported` (e.g. ``"fleet"`` has no
``legacy`` batched path beyond the loop fallback; every paper scenario —
including the §6 network case study since ``vec_workflow`` — now has a
vectorized implementation).

Batched scenario kinds execute through the **sweep layer**
(:mod:`repro.core.sweep`): chunked dispatch with donated buffers, device
sharding, and divergence bucketing, all bit-identical to a monolithic run.
:func:`run_sweep` is the sweep-aware entry point — identical to
:func:`run_scenario` but returning ``(result, SweepReport)`` so callers see
how the sweep was scheduled (devices, chunk size, active-lane fraction);
the same sweep controls (``chunk_size=``, ``devices=``) pass through
``run_scenario`` as ordinary scenario params.

Scenario-provider modules are imported lazily on first dispatch so that
importing :mod:`repro.core` stays light and free of cycles.
"""
from __future__ import annotations

import difflib
import importlib
import warnings
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterable, List, Mapping, Tuple

import numpy as np

from .engine import Simulation
from .engine_oo import LegacySimulation
from .spans import report_stats, span, sweep_span, tracing


class BackendError(LookupError):
    """Unknown backend or scenario kind."""


class ScenarioUnsupported(BackendError):
    """The scenario kind exists but the chosen backend has no path for it."""


@dataclass(frozen=True)
class SimBackend:
    """One engine flavour: how to build its kernel and run scenarios on it.

    ``simulation_cls`` builds the discrete-event kernel for OO-style
    scenarios; vectorized scenarios may never instantiate it (their "engine"
    is a compiled ``lax.while_loop``) — it is still provided so mixed
    scenarios can drive residual event-loop parts.
    """

    name: str
    simulation_cls: type
    description: str
    vectorized: bool = False

    def make_simulation(self) -> Simulation:
        return self.simulation_cls()

    def run_scenario(self, kind: str, **params: Any) -> Any:
        """Run one scenario kind on this backend — the substrate's single
        entry point."""
        return _scenario_handler(kind, self.name)(self, **params)


# -- backend registry ---------------------------------------------------------

_BACKENDS: Dict[str, SimBackend] = {}
_ALIASES: Dict[str, str] = {"6g": "legacy", "7g": "oo", "jax": "vec"}


def register_backend(backend: SimBackend) -> SimBackend:
    _BACKENDS[backend.name] = backend
    return backend


def canonical_name(name: str) -> str:
    return _ALIASES.get(name.lower(), name.lower())


def get_backend(name: str) -> SimBackend:
    try:
        return _BACKENDS[canonical_name(name)]
    except KeyError:
        raise BackendError(
            f"unknown backend {name!r}; available: {available_backends()} "
            f"(aliases: {_ALIASES})") from None


def available_backends() -> List[str]:
    return sorted(_BACKENDS)


register_backend(SimBackend(
    "legacy", LegacySimulation,
    "CloudSim ≤6G mechanics: O(n) linked-list queue, boxed histories, "
    "uncached recomputation (benchmark baseline)"))
register_backend(SimBackend(
    "oo", Simulation,
    "CloudSim 7G re-engineered object kernel: heap queue, cached paths "
    "(reference semantics)"))
register_backend(SimBackend(
    "vec", Simulation,
    "Structure-of-arrays JAX engines under jit/vmap (batched fast path; "
    "optional Pallas next-event fusion)", vectorized=True))


# -- scenario registry --------------------------------------------------------

# kind -> backend name -> handler(backend, **params)
_SCENARIOS: Dict[str, Dict[str, Callable[..., Any]]] = {}

# Modules that register scenario handlers on import (lazy, cycle-free).
# OO reference implementations live with their OO engines (cluster,
# scheduler, workflow, power, netdc); each vec module is a VecEngine
# definition (see repro.core.vec_engine) registering the "vec" handlers.
_SCENARIO_MODULES: Tuple[str, ...] = (
    "repro.core.consolidation_sim",
    "repro.core.cluster",
    "repro.core.vec_cluster",
    "repro.core.case_study",
    "repro.core.vec_scheduler",
    "repro.core.vec_workflow",
    "repro.core.vec_power",
    "repro.core.netdc",
    "repro.core.vec_netdc",
    "repro.core.llmserve",
    "repro.core.vec_llmserve",
    "repro.core.storage",
    "repro.core.vec_storage",
)
_loaded = False


def scenario(kind: str, backends: Iterable[str] = ("*",)):
    """Decorator: register ``fn(backend, **params)`` as the implementation of
    ``kind`` for the given backends (``"*"`` = any backend)."""
    names = tuple(backends)

    def deco(fn: Callable[..., Any]) -> Callable[..., Any]:
        table = _SCENARIOS.setdefault(kind, {})
        for b in names:
            table[b if b == "*" else canonical_name(b)] = fn
        return fn
    return deco


def _load_scenarios() -> None:
    global _loaded
    if _loaded:
        return
    for mod in _SCENARIO_MODULES:
        importlib.import_module(mod)
    # Only once every module imported: a failed import must raise again on
    # the next call, not leave a half-filled registry that reports
    # "unknown scenario kind".
    _loaded = True


def scenario_kinds() -> List[str]:
    _load_scenarios()
    return sorted(_SCENARIOS)


def supporting_backends(kind: str) -> List[str]:
    """Registered backend names that implement ``kind`` (``"*"`` handlers
    expanded to every backend)."""
    _load_scenarios()
    table = _SCENARIOS.get(kind, {})
    if "*" in table:
        return available_backends()
    return sorted(b for b in table if b in _BACKENDS)


def _supported_msg(kind: str) -> str:
    """`supported backends: ... (aliases: ...)` — the uniform tail every
    kind/backend rejection carries, so the caller always learns where the
    scenario IS available and which registered aliases reach it."""
    supported = supporting_backends(kind)
    aliases = ", ".join(f"{a!r}→{c!r}" for a, c in sorted(_ALIASES.items())
                        if c in supported)
    return (f"supported backends: "
            f"{', '.join(repr(b) for b in supported) or 'none'}"
            + (f" (aliases: {aliases})" if aliases else ""))


def _scenario_handler(kind: str, backend_name: str) -> Callable[..., Any]:
    _load_scenarios()
    table = _SCENARIOS.get(kind)
    if table is None:
        raise BackendError(
            f"unknown scenario kind {kind!r}; known: {scenario_kinds()}")
    handler = table.get(backend_name, table.get("*"))
    if handler is None:
        raise ScenarioUnsupported(
            f"scenario {kind!r} is not implemented on backend "
            f"{backend_name!r}; {_supported_msg(kind)}")
    return handler


def run_scenario(kind: str, *, backend: str = "oo", **params: Any) -> Any:
    """Module-level convenience: ``get_backend(backend).run_scenario(...)``."""
    return get_backend(backend).run_scenario(kind, **params)


class ScenarioResult(tuple):
    """The uniform result every batched kind returns from :func:`run_sweep`.

    Behaves as the historical ``(outputs, report)`` 2-tuple — existing
    ``out, rep = run_sweep(...)`` call sites unpack unchanged — while
    exposing the typed contract: ``.outputs`` (the per-cell output dict),
    ``.report`` (the :class:`~repro.core.sweep.SweepReport` schedule
    record), ``.report_fields()`` (the uniform BENCH/consumer slice), and
    ``.summary()`` (a scalar digest of every numeric output).
    """

    def __new__(cls, outputs: Any, report: Any, *, kind: str = "",
                backend: str = "") -> "ScenarioResult":
        self = tuple.__new__(cls, (outputs, report))
        self.kind = kind
        self.backend = backend
        return self

    @property
    def outputs(self) -> Any:
        return self[0]

    @property
    def report(self) -> Any:
        return self[1]

    def report_fields(self) -> Dict[str, Any]:
        """The uniform ``SweepReport`` slice (devices, chunking, compaction
        counters, observed active-lane fraction) — what BENCH JSONs record."""
        return self.report.report_fields()

    def summary(self) -> Dict[str, Any]:
        """Scalar digest: the finite-mean of every numeric output array,
        plus the run's identity (kind, backend, cell count)."""
        s: Dict[str, Any] = {"kind": self.kind, "backend": self.backend,
                             "n_cells": self.report.n_cells}
        out = self.outputs
        items = sorted(out.items()) if isinstance(out, Mapping) else ()
        for k, v in items:
            a = np.asarray(v)
            if a.dtype.kind not in "bifu" or a.size == 0:
                continue
            finite = a[np.isfinite(a.astype(np.float64))]
            s[k] = float(finite.mean()) if finite.size else None
        return s

    def __repr__(self) -> str:  # the tuple repr hides the typed contract
        return (f"ScenarioResult(kind={self.kind!r}, "
                f"backend={self.backend!r}, n_cells={self.report.n_cells})")


# -- scenario-parameter validation (run_sweep entry) --------------------------

# Parameters that must be strictly positive wherever given — rates,
# capacities, MTBFs.  A zero or negative entry produces silent nonsense
# (division by zero, instant-failure storms) only *after* a sweep compiles
# and dispatches; rejecting at entry names the axis and index instead.
_POSITIVE_PARAMS = frozenset({
    "mean_gap_s", "link_bw", "dc_mips", "host_mips", "host_pes", "vm_mips",
    "guest_mips", "mtbf_hours", "mtbf_hours_node", "degrade_mtbf_hours",
    "interval", "total_steps", "n_samples",
})
# Parameters that must be >= 0 (delays, penalties, weights).
_NONNEGATIVE_PARAMS = frozenset({
    "hop_latency_s", "slo_ttft_s", "kv_penalty_s", "payload_mb",
    "locality_weight", "up_thr", "lo_thr", "cooldown", "offline_frac",
    "demand", "placement_weight", "repair_bias_s",
})
# float params where +inf is a legitimate sentinel (NaN never is).
_INF_OK = frozenset({"timeout_s", "budget_s"})


def validate_scenario_params(kind: str, params: Mapping[str, Any]) -> None:
    """Reject non-finite or sign-invalid scenario parameter arrays before
    anything compiles, naming the offending key and index.

    Best-effort by construction: non-numeric parameters (config
    dataclasses, fault plans, callables, strings) pass through untouched;
    every float array is NaN-checked (and inf-checked unless the key
    legitimately uses ``inf`` as a sentinel), and keys in the
    positive/non-negative registries get their sign constraint enforced.
    """
    for key, val in params.items():
        try:
            arr = np.asarray(val)
        except Exception:
            continue
        if arr.dtype.kind == "f":
            bad = np.isnan(arr) if key in _INF_OK else ~np.isfinite(arr)
            if bad.any():
                idx = np.unravel_index(int(np.argmax(bad)), arr.shape)
                loc = "".join(f"[{i}]" for i in idx)
                raise ValueError(
                    f"run_sweep({kind!r}): params[{key!r}]{loc} = "
                    f"{arr[idx]} — scenario parameters must be finite")
        if arr.dtype.kind not in "fiu" or arr.size == 0:
            continue
        if key in _POSITIVE_PARAMS:
            bad = ~(arr > 0)
        elif key in _NONNEGATIVE_PARAMS:
            bad = ~(arr >= 0)
        else:
            continue
        if bad.any():
            idx = np.unravel_index(int(np.argmax(bad)), arr.shape)
            loc = "".join(f"[{i}]" for i in idx)
            bound = ("> 0 (a positive rate/capacity/MTBF)"
                     if key in _POSITIVE_PARAMS else ">= 0")
            raise ValueError(
                f"run_sweep({kind!r}): params[{key!r}]{loc} = {arr[idx]} "
                f"— must be {bound}")


# One-time deprecation notice for loose sweep-control kwargs (the pre-
# SweepConfig calling convention); tests reset it to observe the warning.
_warned_legacy_controls = False


def run_sweep(kind: str, params: Mapping[str, Any] | None = None, *,
              backend: str = "vec", config: Any = None,
              **kwargs: Any) -> ScenarioResult:
    """Sweep-aware batch entry point — run a *batched* scenario kind and
    return a :class:`ScenarioResult` (an ``(outputs, SweepReport)`` pair
    with the typed accessors).

    The typed calling convention separates scenario parameters from sweep
    scheduling::

        run_sweep("netdc_batch", dict(seeds=range(64), n_dcs=8),
                  config=SweepConfig(compact=True, chunk_size=32))

    ``params`` holds only scenario parameters (a sweep-control key inside
    it is rejected, pointing at ``config=``); ``config`` is a
    :class:`~repro.core.sweep.SweepConfig` whose non-default fields are
    forwarded as the uniform control kwargs every batched handler accepts.

    The pre-config convention — controls mixed into ``**kwargs``
    (``run_sweep(kind, chunk_size=8, seeds=...)``) — still works via a
    shim: control-named kwargs are folded into a ``SweepConfig`` with a
    one-time ``DeprecationWarning``, near-miss typos of control names are
    rejected with a did-you-mean, and the rest pass through as scenario
    params.  A kind/backend pair with no sweep path raises (``TypeError``
    from the handler's signature, or :class:`ScenarioUnsupported` if a
    permissive handler swallowed ``with_report``) — never a bare result
    the caller would mis-unpack.
    """
    from .sweep import SweepReport
    with sweep_span() as sweep:
        scenario_params, config = _sweep_args(kind, params, config, kwargs)
        with span("sweep.validate"):
            validate_scenario_params(kind, scenario_params)
        res = get_backend(backend).run_scenario(
            kind, with_report=True, **scenario_params, **config.to_kwargs())
        if not (isinstance(res, tuple) and len(res) == 2
                and isinstance(res[1], SweepReport)):
            raise ScenarioUnsupported(
                f"scenario {kind!r} has no sweep-aware path on backend "
                f"{backend!r} (handler returned no SweepReport); "
                f"{_supported_msg(kind)}")
        if tracing():
            sweep.set_metadata(**report_stats(res[1]))
    return ScenarioResult(res[0], res[1], kind=kind,
                          backend=canonical_name(backend))


def _sweep_args(kind: str, params: Mapping[str, Any] | None, config: Any,
                kwargs: Dict[str, Any]):
    """``run_sweep``'s arguments as ``(scenario_params, SweepConfig)``: the
    typed convention checked, the legacy one folded in."""
    global _warned_legacy_controls
    from .sweep import SweepConfig
    if config is not None and not isinstance(config, SweepConfig):
        raise TypeError(
            f"config must be a SweepConfig, got {type(config).__name__}; "
            f"scenario parameters go in the params dict")
    control_names = SweepConfig.field_names()
    if params is not None:
        if not isinstance(params, Mapping):
            raise TypeError(
                f"params must be a mapping of scenario parameters, got "
                f"{type(params).__name__}")
        misplaced = sorted(set(params) & set(control_names))
        if misplaced:
            raise TypeError(
                f"sweep control(s) {misplaced} belong in "
                f"config=SweepConfig(...), not in the params dict")
        if kwargs:
            hints = []
            for k in sorted(kwargs):
                close = difflib.get_close_matches(
                    k, list(control_names) + list(params), n=1, cutoff=0.6)
                hints.append(f"{k!r}" + (f" (did you mean {close[0]!r}?)"
                                         if close else ""))
            raise TypeError(
                f"run_sweep got unexpected keyword(s) {', '.join(hints)}; "
                f"with a params dict, scenario parameters go inside it and "
                f"sweep controls in config=SweepConfig(...)")
        scenario_params = dict(params)
    else:
        controls = {k: kwargs.pop(k) for k in list(kwargs)
                    if k in control_names}
        for k in kwargs:
            close = difflib.get_close_matches(k, control_names, n=1,
                                              cutoff=0.8)
            if close:
                raise TypeError(
                    f"run_sweep got unexpected keyword {k!r} — did you "
                    f"mean the SweepConfig field {close[0]!r}?")
        if controls:
            if config is not None:
                raise TypeError(
                    f"pass sweep controls either via config=SweepConfig(...)"
                    f" or as legacy kwargs, not both ({sorted(controls)} "
                    f"given alongside config=)")
            if not _warned_legacy_controls:
                _warned_legacy_controls = True
                warnings.warn(
                    "passing sweep controls as loose run_sweep kwargs "
                    f"({sorted(controls)}) is deprecated — use "
                    "run_sweep(kind, params, config=SweepConfig(...))",
                    DeprecationWarning, stacklevel=3)
            config = SweepConfig.from_kwargs(**controls)
        scenario_params = kwargs
    if config is None:
        config = SweepConfig()
    return scenario_params, config
