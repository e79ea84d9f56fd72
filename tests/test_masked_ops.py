"""Direct unit tests for the canonical masked reductions (ISSUE 5 satellite:
the one implementation in ``repro.kernels.ops`` that replaced the three
private copies in vec_cluster / vec_power / vec_workflow).

Contracts: last-axis reduction, ``(inf, 0)`` on all-masked input,
first-occurrence tie-breaking, and bit-exact jnp-vs-Pallas agreement.
"""
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.vec_engine import x64
from repro.kernels.ops import (MaskedOps, masked_argmax, masked_argmin,
                               masked_min, pallas_native,
                               reset_pallas_warning, resolve_use_pallas)


def test_masked_min_basic_and_mask():
    with x64():
        v = jnp.asarray([3.0, 1.0, 2.0, 0.5])
        assert float(masked_min(v)) == 0.5
        m = jnp.asarray([True, True, True, False])
        assert float(masked_min(v, m)) == 1.0
        assert int(masked_argmin(v, m)) == 1
        assert int(masked_argmax(v, m)) == 0


def test_all_masked_returns_inf_and_index_zero():
    """An all-masked row behaves exactly like jnp.min/argmin over all-inf:
    (inf, 0) — the engines rely on this for 'no candidate events left'."""
    with x64():
        v = jnp.asarray([5.0, 7.0, 9.0])
        m = jnp.zeros(3, bool)
        assert np.isinf(float(masked_min(v, m)))
        assert int(masked_argmin(v, m)) == 0
        assert int(masked_argmax(v, m)) == 0


def test_first_occurrence_tie_breaking():
    with x64():
        v = jnp.asarray([4.0, 2.0, 2.0, 4.0])
        assert int(masked_argmin(v)) == 1
        assert int(masked_argmax(v)) == 0
        # masked ties: the first *eligible* occurrence wins
        m = jnp.asarray([True, False, True, True])
        assert int(masked_argmin(v, m)) == 2
        assert int(masked_argmax(v, m)) == 0
        assert int(masked_argmax(v, jnp.asarray([False, True, True, True]))) \
            == 3


def test_last_axis_reduction_with_leading_dims():
    with x64():
        v = jnp.asarray([[3.0, 1.0], [2.0, 5.0]])
        assert np.array_equal(np.asarray(masked_min(v)), [1.0, 2.0])
        assert np.array_equal(np.asarray(masked_argmin(v)), [1, 0])
        assert np.array_equal(np.asarray(masked_argmax(v)), [0, 1])


@pytest.mark.parametrize("op", [masked_min, masked_argmin, masked_argmax])
def test_jnp_vs_pallas_agree_bitwise(op):
    """The Pallas (interpret-mode) path must agree bit-for-bit with the jnp
    path — value *and* tie-broken index — over randomized masked inputs
    (duplicates injected to exercise the tie rule)."""
    rng = np.random.default_rng(42)
    with x64():
        for trial in range(5):
            n = int(rng.integers(2, 40))
            v = rng.choice([0.25, 1.5, 3.0, 7.25], size=n)  # forced ties
            m = rng.random(n) < 0.7
            a = np.asarray(op(jnp.asarray(v), jnp.asarray(m)))
            b = np.asarray(op(jnp.asarray(v), jnp.asarray(m),
                              use_pallas=True))
            assert np.array_equal(a, b), f"trial {trial}: {a} != {b}"


def test_maskedops_binds_the_switch():
    with x64():
        v = jnp.asarray([2.0, 1.0, 1.0])
        for up in (False, True):
            ops = MaskedOps(use_pallas=up)
            assert float(ops.min(v)) == 1.0
            assert int(ops.argmin(v)) == 1
            assert int(ops.argmax(v)) == 0


def test_resolve_use_pallas_cpu_fallback():
    """On CPU, True falls back to the jnp path (one-time warning);
    'force' stays on; False stays off."""
    assert resolve_use_pallas(False) is False
    assert resolve_use_pallas("force") is True
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        resolved = resolve_use_pallas(True)
    import jax as _jax
    assert resolved is (_jax.default_backend() in ("tpu", "gpu"))


@pytest.mark.skipif(pallas_native(),
                    reason="fallback warning only fires off-TPU/GPU")
def test_pallas_fallback_warning_once_per_backend_and_reset():
    """The fallback warning fires once per *backend* (not once per
    process) and ``reset_pallas_warning`` re-arms it — so a CPU warning
    in a long session can't suppress a later distinct-backend warning."""
    reset_pallas_warning()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        assert resolve_use_pallas(True) is False
        assert resolve_use_pallas(True) is False    # suppressed repeat
        assert len(caught) == 1
        assert issubclass(caught[0].category, RuntimeWarning)
        reset_pallas_warning()                      # re-armed
        assert resolve_use_pallas(True) is False
        assert len(caught) == 2
    # Per-backend memory: a different default backend warns independently
    # even though this backend already did.
    import repro.kernels.ops as ops_mod
    reset_pallas_warning()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        assert resolve_use_pallas(True) is False    # warns for real backend
        real = ops_mod.jax.default_backend
        try:
            ops_mod.jax.default_backend = lambda: "other_cpu"
            assert resolve_use_pallas(True) is False    # warns again
            assert resolve_use_pallas(True) is False    # but only once
        finally:
            ops_mod.jax.default_backend = real
        assert len(caught) == 2
    reset_pallas_warning()


# -- a native backend: no silent fallback -----------------------------------------
#
# Where Pallas lowers natively, use_pallas=True never falls back to the jnp
# path or interpret mode: a route that cannot lower raises before anything
# runs.  The CPU stands in for the chip by answering "tpu" to the backend
# query the routing reads; nothing here reaches a device kernel.

@pytest.fixture
def native(monkeypatch):
    import repro.kernels.ops as ops_mod
    monkeypatch.setattr(ops_mod.jax, "default_backend", lambda: "tpu")
    assert pallas_native()


def test_native_rejects_force(native):
    with pytest.raises(ValueError, match="interpret mode"):
        resolve_use_pallas("force")
    assert resolve_use_pallas(True) is True


def test_native_f64_reduction_raises(native):
    """An x64 exact engine's reductions are f64: the TPU kernel lowers
    float32 only, so the request is refused, not rerouted."""
    from repro.core.vec_netdc import simulate_netdc_batch
    with pytest.raises(NotImplementedError, match="float32 only"):
        simulate_netdc_batch(seeds=[0, 1, 2], n_dcs=5, n_jobs=7,
                             use_pallas=True)


def test_native_fused_step_raises(native):
    """Power's host picks reduce f64 watts: the native next-event kernel
    refuses them when the loop is traced and tells the caller to run
    use_pallas=False."""
    from repro.core.vec_power import simulate_power_batch
    with pytest.raises(NotImplementedError, match="use_pallas=False"):
        simulate_power_batch(seeds=[0, 1, 2], n_hosts=5, n_vms=9,
                             n_samples=7, use_pallas=True)


# -- the next-event route inside the engines under use_pallas="force" ---------
#
# "force" routes every masked reduction of fleet and power through the
# interpret-mode next-event kernel, on the monolithic loop and in the
# compacting scheduler's segments, and every output must be bit-identical
# to the plain jnp path, so golden fixtures cannot churn.

def _assert_outputs_equal(a, b):
    assert set(a) == set(b)
    for k in sorted(a):
        x, y = np.asarray(a[k]), np.asarray(b[k])
        assert x.dtype == y.dtype, f"{k}: dtype {x.dtype} vs {y.dtype}"
        assert np.array_equal(x, y), f"{k}: kernel route drifted"


# Compacting: 2 resident lanes and short segments, so lanes pause, resume
# and (fleet) refill with the kernel route inside the segment step.
_SCHEDULES = {"monolithic": {},
              "compact": dict(compact=True, chunk_size=2, segment_iters=5)}


@pytest.mark.parametrize("schedule", sorted(_SCHEDULES))
def test_differential_fleet_force_parity(schedule):
    """Fleet (while-loop engine): stochastic config with stragglers,
    eviction, degradation and failures on."""
    from repro.core.cluster import FleetConfig, StepCost
    from repro.core.vec_cluster import simulate_fleet_batch
    cost = StepCost(compute_s=1.0, memory_s=0.4, collective_s=0.3,
                    overlap_collective=0.5)
    cfg = FleetConfig(n_nodes=4, n_spares=1, straggler_sigma=0.25,
                      mtbf_hours_node=4.0)
    kw = dict(seeds=[0, 1, 2], max_wallclock_s=20_000.0)
    a = simulate_fleet_batch(cost, cfg, 40, use_pallas=False, **kw)
    b = simulate_fleet_batch(cost, cfg, 40, use_pallas="force",
                             **_SCHEDULES[schedule], **kw)
    _assert_outputs_equal(a, b)


@pytest.mark.parametrize("schedule", sorted(_SCHEDULES))
def test_differential_power_force_parity(schedule):
    """Power (static-trip-count engine), clean and faulted (the crash
    table adds the keep-alive reduction)."""
    from repro.core.faults import FaultEvent, FaultPlan
    from repro.core.vec_power import simulate_power_batch
    kw = dict(seeds=[0, 1], n_hosts=4, n_vms=8, n_samples=16)
    sched = _SCHEDULES[schedule]
    a = simulate_power_batch(use_pallas=False, **kw)
    b = simulate_power_batch(use_pallas="force", **sched, **kw)
    _assert_outputs_equal(a, b)
    plan = FaultPlan([FaultEvent("node", 600.0, 1800.0, target=1)])
    a = simulate_power_batch(use_pallas=False, fault_plan=plan, **kw)
    b = simulate_power_batch(use_pallas="force", fault_plan=plan, **sched,
                             **kw)
    _assert_outputs_equal(a, b)


@pytest.mark.parametrize("schedule", sorted(_SCHEDULES))
def test_power_force_matches_oo_bit_exact(schedule):
    """Transitivity check the differential suite relies on: the kernel
    route equals vec-plain, which equals the OO reference — so it must
    equal OO directly too (the strongest end-to-end statement)."""
    from repro.core.backend import run_scenario
    from repro.core.vec_power import simulate_power_batch
    kw = dict(seeds=[3], n_hosts=4, n_vms=8, n_samples=16)
    oo = run_scenario("power_batch", backend="oo", **kw)
    forced = simulate_power_batch(use_pallas="force", **_SCHEDULES[schedule],
                                  **kw)
    for k in ("energy_wh", "migrations", "sla_s", "final_active"):
        assert np.array_equal(np.asarray(oo[k]), np.asarray(forced[k])), k
