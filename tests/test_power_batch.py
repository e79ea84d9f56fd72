"""``power_batch`` scenario tests — the ISSUE-4 acceptance surface.

The power-aware elastic datacenter runs on all three backends with
bit-exact agreement, routes through the sweep layer (``run_sweep`` returns
a populated :class:`SweepReport`, chunking never changes a bit), and shows
the physics the paper centers on: autoscaling saves energy vs a static
fleet, and the scale-out threshold trades energy against SLA violation.
"""
import numpy as np
import pytest

from repro.core.backend import run_scenario, run_sweep, scenario_kinds
from repro.core.sweep import SweepConfig, SweepReport

CFG = dict(seeds=[0, 1, 2], n_hosts=8, n_vms=32, n_samples=48,
           up_thr=0.8, lo_thr=0.3, cooldown=2)


def _assert_all_equal(a, b, ctx):
    assert sorted(a) == sorted(b), ctx
    for k in a:
        assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), \
            f"{ctx}: {k} differs"


def test_power_batch_registered_on_all_backends():
    assert "power_batch" in scenario_kinds()
    for b in ("legacy", "oo", "vec"):
        out = run_scenario("power_batch", backend=b, seeds=[0], n_hosts=4,
                           n_vms=8, n_samples=4)
        assert out["iterations"][0] == 4


def test_three_backends_bit_exact():
    oo = run_scenario("power_batch", backend="oo", **CFG)
    vec = run_scenario("power_batch", backend="vec", **CFG)
    legacy = run_scenario("power_batch", backend="legacy", **CFG)
    _assert_all_equal(oo, vec, "oo vs vec")
    _assert_all_equal(oo, legacy, "oo vs legacy")
    assert oo["energy_wh"].shape == (3, 8)
    assert (oo["energy_total_wh"] > 0).all()


# CloudSim's power-example hosts: ML110 G4 and G5 by index, 2 PEs each.
ML110 = dict(host_mips=[1860.0, 2660.0], host_pes=2, vm_mips=1500.0,
             model_mix="spec")


def test_host_types_bit_exact_across_backends():
    cfg = dict(CFG, n_vms=13, **ML110)
    oo = run_scenario("power_batch", backend="oo", **cfg)
    vec = run_scenario("power_batch", backend="vec", **cfg)
    _assert_all_equal(oo, vec, "oo vs vec, host types")
    one_type = run_scenario("power_batch", backend="vec",
                            **dict(cfg, host_mips=2660.0))
    # the G4 hosts' smaller capacity raises their utilization, and so the
    # energy the autoscaler's decisions leave behind
    assert not np.array_equal(vec["energy_wh"], one_type["energy_wh"])


def test_host_capacities_cycle_by_index():
    from repro.core.power import host_capacities
    pes, mips = host_capacities(5, [1860.0, 2660.0], 2)
    assert pes.tolist() == [2] * 5
    assert mips.tolist() == [1860.0, 2660.0, 1860.0, 2660.0, 1860.0]
    pes, mips = host_capacities(3, 8000.0, [1, 4])
    assert pes.tolist() == [1, 4, 1] and mips.tolist() == [8000.0] * 3
    for bad in (0, 1.5, []):
        with pytest.raises(ValueError, match="host_pes|at least one"):
            host_capacities(3, 8000.0, bad)


def test_run_sweep_report_populated_both_backends():
    for backend in ("vec", "oo"):
        out, rep = run_sweep("power_batch", backend=backend, **CFG)
        assert isinstance(rep, SweepReport)
        assert rep.n_cells == 3 and rep.devices >= 1
        assert out["energy_total_wh"].shape == (3,)
    # vec lanes all run exactly n_samples iterations: no divergence to pay
    out, rep = run_sweep("power_batch", backend="vec", **CFG)
    assert (out["iterations"] == CFG["n_samples"]).all()
    assert rep.active_lane_fraction == 1.0


def test_chunked_and_sharded_fallback_bit_identical():
    mono = run_scenario("power_batch", backend="vec", **CFG)
    chunked, rep = run_sweep("power_batch", CFG, backend="vec",
                             config=SweepConfig(chunk_size=2))
    assert rep.n_chunks == 2 and rep.chunk_size == 2
    _assert_all_equal(mono, chunked, "chunked vs monolithic")
    sharded, rep1 = run_sweep("power_batch", CFG, backend="vec",
                              config=SweepConfig(devices=1, chunk_size=1))
    assert rep1.devices == 1
    _assert_all_equal(mono, sharded, "sharded-fallback vs monolithic")


def test_pallas_picks_match_jnp_picks():
    """The energy-aware host selection through the fused next-event kernel
    (interpret mode on CPU via "force") picks identical hosts."""
    plain = run_scenario("power_batch", backend="vec", seeds=[0], n_hosts=6,
                         n_vms=12, n_samples=8, cooldown=0)
    forced = run_scenario("power_batch", backend="vec", seeds=[0], n_hosts=6,
                          n_vms=12, n_samples=8, cooldown=0,
                          use_pallas="force")
    _assert_all_equal(plain, forced, "pallas vs jnp")


def test_empty_batch():
    out, rep = run_sweep("power_batch", backend="vec",
                         seeds=np.array([], np.int64), n_hosts=4, n_vms=8,
                         n_samples=4)
    assert rep.n_cells == 0
    assert out["energy_wh"].shape == (0, 4)
    assert out["migrations"].shape == (0,)


def test_threshold_sweep_broadcasts_against_seeds():
    out = run_scenario("power_batch", backend="vec", seeds=0,
                       up_thr=np.array([0.7, 0.8, 0.9]), n_hosts=4,
                       n_vms=8, n_samples=8)
    assert out["energy_total_wh"].shape == (3,)


def test_autoscaling_saves_energy_vs_static_fleet():
    """The paper's core energy claim, on our scenario: threshold scaling
    beats an always-on fleet on energy; the static fleet never violates."""
    kw = dict(seeds=np.arange(4), n_hosts=8, n_vms=48, n_samples=96,
              cooldown=8)
    elastic = run_scenario("power_batch", backend="vec", up_thr=0.7,
                           lo_thr=0.3, init_active=1, **kw)
    static = run_scenario("power_batch", backend="vec", up_thr=2.0,
                          lo_thr=-1.0, **kw)
    assert (static["scale_out_events"] == 0).all()
    assert (static["scale_in_events"] == 0).all()
    assert (static["sla_total_s"] == 0).all()
    assert elastic["energy_total_wh"].mean() < static["energy_total_wh"].mean()
    assert (elastic["scale_out_events"] > 0).all()


def test_up_threshold_trades_energy_for_sla():
    """Lazier scale-out (higher up_thr) burns less energy but violates the
    SLA longer — the trade-off the 256-lane example sweep visualizes."""
    kw = dict(seeds=np.arange(8), n_hosts=8, n_vms=48, n_samples=96,
              lo_thr=0.3, cooldown=8, init_active=1)
    eager = run_scenario("power_batch", backend="vec", up_thr=0.7, **kw)
    lazy = run_scenario("power_batch", backend="vec", up_thr=0.95, **kw)
    assert lazy["energy_total_wh"].mean() < eager["energy_total_wh"].mean()
    assert lazy["sla_total_s"].mean() > eager["sla_total_s"].mean()
    assert eager["sla_total_s"].mean() > 0    # even eager scaling pays some


def test_model_mix_changes_energy_not_decisions_shape():
    for mix in ("linear", "cubic", "spec", "dvfs"):
        out = run_scenario("power_batch", backend="vec", seeds=[0],
                           n_hosts=4, n_vms=8, n_samples=8, model_mix=mix)
        assert out["energy_total_wh"][0] > 0


def test_validation_errors():
    with pytest.raises(ValueError, match="min_active"):
        run_scenario("power_batch", backend="vec", seeds=[0], n_hosts=4,
                     n_vms=8, n_samples=4, min_active=9)
    with pytest.raises(ValueError, match="init_active"):
        run_scenario("power_batch", backend="vec", seeds=[0], n_hosts=4,
                     n_vms=8, n_samples=4, init_active=0)
    with pytest.raises(ValueError, match="n_vms"):
        run_scenario("power_batch", backend="vec", seeds=[0], n_hosts=4,
                     n_vms=0, n_samples=4)
    with pytest.raises(ValueError, match="interval"):
        run_scenario("power_batch", backend="vec", seeds=[0], n_hosts=4,
                     n_vms=8, n_samples=4, interval=0.0)
    with pytest.raises(ValueError, match="model mix"):
        run_scenario("power_batch", backend="vec", seeds=[0], n_hosts=4,
                     n_vms=8, n_samples=4, model_mix="fusion")
    # a VM that can't fit a time-shared host is rejected up front on BOTH
    # backends (the OO allocation path would otherwise fail mid-run while
    # vec silently produced reference-less numbers)
    for backend in ("vec", "oo"):
        with pytest.raises(ValueError, match="vm_mips"):
            run_scenario("power_batch", backend=backend, seeds=[0],
                         n_hosts=4, n_vms=8, n_samples=4,
                         host_mips=8000.0, vm_mips=[4000.0, 9000.0])
        # per PE, on every host type: 2000 fits a G5 PE, not a G4 one
        with pytest.raises(ValueError, match="vm_mips"):
            run_scenario("power_batch", backend=backend, seeds=[0],
                         n_hosts=4, n_vms=8, n_samples=4,
                         **dict(ML110, vm_mips=2000.0))


def test_unknown_backend_errors_cleanly():
    from repro.core.backend import BackendError
    with pytest.raises(BackendError):
        run_scenario("power_batch", backend="quantum")
