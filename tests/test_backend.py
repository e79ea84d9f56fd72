"""SimBackend substrate: registry, aliases, scenario dispatch, and
cross-backend decision identity on the consolidation workload."""
import numpy as np
import pytest

from repro.core.backend import (BackendError, ScenarioUnsupported, SimBackend,
                                available_backends, get_backend, run_scenario,
                                scenario_kinds)
from repro.core.engine import Simulation
from repro.core.engine_oo import LegacySimulation


def test_registry_and_aliases():
    assert set(available_backends()) >= {"legacy", "oo", "vec"}
    assert get_backend("oo").simulation_cls is Simulation
    assert get_backend("legacy").simulation_cls is LegacySimulation
    # paper-era aliases resolve to the canonical backends
    assert get_backend("6g") is get_backend("legacy")
    assert get_backend("7g") is get_backend("oo")
    assert get_backend("VEC") is get_backend("vec")
    assert get_backend("vec").vectorized


def test_unknown_backend_raises():
    with pytest.raises(BackendError):
        get_backend("quantum")


def test_unknown_scenario_raises():
    with pytest.raises(BackendError):
        run_scenario("time-travel", backend="oo")


def test_failed_scenario_import_raises_on_every_call(monkeypatch):
    """A scenario module that fails to import must raise its ImportError on
    every call, not once and then "unknown scenario kind" forever after."""
    from repro.core import backend
    monkeypatch.setattr(backend, "_loaded", False)
    monkeypatch.setattr(backend, "_SCENARIO_MODULES",
                        backend._SCENARIO_MODULES
                        + ("repro.core._no_such_scenarios",))
    for _ in range(2):
        with pytest.raises(ModuleNotFoundError, match="_no_such_scenarios"):
            run_scenario("power_batch", backend="oo", seeds=[0])
    assert backend._loaded is False


def test_scenario_kinds_registered():
    kinds = scenario_kinds()
    for k in ("consolidation", "fleet", "fleet_batch", "case_study",
              "cloudlet_batch", "workflow_batch", "power_batch",
              "netdc_batch"):
        assert k in kinds, kinds


def test_case_study_runs_on_vec_backend():
    """ISSUE 2: the last ScenarioUnsupported gap is closed — the §6 case
    study runs on the vectorized backend with OO-identical results."""
    r = run_scenario("case_study", backend="vec")
    r_oo = run_scenario("case_study", backend="oo")
    assert r.makespans == r_oo.makespans


def test_scenario_unsupported_still_raised_for_partial_kinds():
    """Every built-in kind now has all three implementations; the substrate
    still errors cleanly for a kind registered on a subset of backends."""
    from repro.core.backend import _SCENARIOS, scenario
    try:
        @scenario("_oo_only_probe", backends=("oo",))
        def _probe(backend, **kw):
            return "ran"
        assert run_scenario("_oo_only_probe", backend="oo") == "ran"
        with pytest.raises(ScenarioUnsupported):
            run_scenario("_oo_only_probe", backend="vec")
    finally:
        _SCENARIOS.pop("_oo_only_probe", None)


def test_scenario_unsupported_names_supporting_backends():
    """ISSUE 5 satellite: the error tells the user which backends *do*
    implement the kind — including the aliases that reach them — instead
    of leaving them to grep the registry."""
    from repro.core.backend import _SCENARIOS, scenario, supporting_backends
    try:
        @scenario("_named_probe", backends=("oo", "legacy"))
        def _probe(backend, **kw):
            return "ran"
        assert supporting_backends("_named_probe") == ["legacy", "oo"]
        with pytest.raises(ScenarioUnsupported,
                           match=r"not implemented on backend 'vec'; "
                                 r"supported backends: 'legacy', 'oo' "
                                 r"\(aliases: '6g'→'legacy', '7g'→'oo'\)"):
            run_scenario("_named_probe", backend="vec")
    finally:
        _SCENARIOS.pop("_named_probe", None)


def test_supporting_backends_expands_wildcard():
    from repro.core.backend import (_SCENARIOS, available_backends, scenario,
                                    supporting_backends)
    try:
        @scenario("_any_probe")                       # backends=("*",)
        def _probe(backend, **kw):
            return "ran"
        assert supporting_backends("_any_probe") == available_backends()
    finally:
        _SCENARIOS.pop("_any_probe", None)


def test_case_study_runs_on_both_kernels():
    from repro.core.case_study import run_case_study
    r_oo = run_case_study(backend="oo", activations=1)
    r_legacy = run_case_study(backend="legacy", activations=1)
    assert r_oo.makespans == r_legacy.makespans     # same semantics, any kernel


def test_consolidation_decisions_identical_across_backends():
    """The substrate's core guarantee: one scenario, three engines, same
    decisions (migrations, energy, final packing)."""
    results = {b: run_scenario("consolidation", backend=b, algo="ThrMu",
                               n_hosts=20, n_vms=40, n_samples=24)
               for b in ("legacy", "oo", "vec")}
    base = results["oo"]
    for b, r in results.items():
        assert r.migrations == base.migrations, b
        assert r.energy_kwh == pytest.approx(base.energy_kwh, rel=1e-12), b
        assert r.final_active_hosts == base.final_active_hosts, b
        assert r.engine == b


def test_consolidation_backcompat_engine_names():
    from repro.core.consolidation_sim import run_consolidation
    r6 = run_consolidation("6g", "Dvfs", n_hosts=8, n_vms=16, n_samples=12)
    r7 = run_consolidation("7g", "Dvfs", n_hosts=8, n_vms=16, n_samples=12)
    assert r6.engine == "legacy" and r7.engine == "oo"
    assert r6.energy_kwh == pytest.approx(r7.energy_kwh, rel=1e-12)


def test_fleet_scenario_on_all_backends():
    from repro.core.cluster import FleetConfig, StepCost
    cost = StepCost(compute_s=1.0, memory_s=0.4, collective_s=0.3,
                    overlap_collective=0.5)
    cfg = FleetConfig(n_nodes=16, n_spares=2, straggler_sigma=0.0,
                      mtbf_hours_node=1e9, degrade_mtbf_hours=1e9,
                      ckpt_every_steps=25, seed=0)
    stats = {b: run_scenario("fleet", backend=b, cost=cost, cfg=cfg,
                             total_steps=100) for b in ("legacy", "oo", "vec")}
    # deterministic config ⇒ all three backends agree exactly
    assert stats["legacy"].wallclock_s == stats["oo"].wallclock_s \
        == stats["vec"].wallclock_s
    assert stats["vec"].steps_done == 100


def test_backend_run_scenario_entrypoint():
    b = get_backend("vec")
    out = b.run_scenario("cloudlet_batch",
                         length=np.array([[100.0]]), pes=np.array([[1.0]]),
                         submit=np.array([[0.0]]),
                         guest_mips=np.array([100.0]),
                         guest_pes=np.array([1.0]))
    assert np.asarray(out)[0, 0] == pytest.approx(1.0)
