"""Fused step-kernel tests (ISSUE 10 tentpole): whole ``cond/body``
iterations as single Pallas kernels, bit-exact vs the jnp path.

Covers the kernel mechanics directly (``fused_step_body`` /
``fused_scan`` vs the canonical ``body_from_step`` jnp path — f32 and
f64, all-masked / single-slot / tie edge cases, mirroring the
``test_masked_ops`` contracts) and the two wired engines end to end: a
differential cell running fleet + power with ``use_pallas="force"``
asserts every output bit-identical to the plain path — the CPU-only CI
lane that exercises the kernels in interpret mode (a native backend
refuses these fused routes; see ``test_tpu_compile.py`` for what lowers).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.vec_engine import StepSpec, body_from_step, x64
from repro.kernels.ops import masked_argmin, masked_min
from repro.kernels.step import closure_convert_all, fused_scan, fused_step_body


# -- kernel mechanics: per-step fused body -------------------------------------

def _toy_spec(dtype, mask_mode: str) -> StepSpec:
    """A step with everything the engine bodies throw at the kernel:
    closed-over consts (incl. a non-differentiable PRNG key), RNG folding
    on ``it``, masked next-event reductions, scatter updates, and scalar
    + vector + bool + int state leaves."""
    key = jax.random.PRNGKey(7)                      # uint32 const
    shift = jnp.asarray([0.5, -0.25, 0.5, 0.0, 0.125], dtype)

    def step(state, sl, it):
        del sl
        t, vals, picks, flag = state
        n = vals.shape[0]
        if mask_mode == "all_masked":
            mask = jnp.zeros((n,), bool)
        elif mask_mode == "single_slot":
            mask = jnp.arange(n) == 2
        else:                                        # "ties"
            mask = jnp.ones((n,), bool)
        vmin = masked_min(vals, mask)
        imin = masked_argmin(vals, mask)
        draw = jax.random.normal(jax.random.fold_in(key, it),
                                 (n,)).astype(dtype)
        vals = jnp.where(mask, vals + shift, vals).at[imin].add(
            jnp.asarray(1.0, dtype) + 0.125 * draw[imin])
        t = t + jnp.where(jnp.isfinite(vmin), vmin,
                          jnp.asarray(0.0, dtype))
        return (t, vals, picks + imin.astype(jnp.int32),
                flag | (imin == 0))

    return StepSpec(step=step)


def _toy_init(dtype):
    # Duplicated minima force the first-occurrence tie rule through the
    # kernel on every iteration.
    return (jnp.asarray(0.0, dtype),
            jnp.asarray([2.0, 0.5, 0.5, 3.0, 0.5], dtype),
            jnp.asarray(0, jnp.int32),
            jnp.asarray(False))


@pytest.mark.parametrize("dtype", ["float32", "float64"])
@pytest.mark.parametrize("mask_mode", ["ties", "all_masked", "single_slot"])
def test_fused_step_body_bitwise(dtype, mask_mode):
    """One whole iteration as one pallas_call (interpret) must equal the
    jnp body bit-for-bit across dtypes and masked-reduction edge cases."""
    with x64():
        spec = _toy_spec(jnp.dtype(dtype), mask_mode)
        init = _toy_init(jnp.dtype(dtype))

        def run(body):
            def w_body(c):
                return body(c[0], c[1]), c[1] + 1
            return jax.lax.while_loop(lambda c: c[1] < 6, w_body,
                                      (init, jnp.asarray(0, jnp.int32)))[0]

        a = jax.jit(lambda: run(body_from_step(spec)))()
        b = jax.jit(lambda: run(fused_step_body(spec, interpret=True)))()
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)):
            assert x.dtype == y.dtype
            assert np.array_equal(np.asarray(x), np.asarray(y))


# -- kernel mechanics: whole-loop scan kernel ----------------------------------

def _scan_spec(dtype):
    eff = jnp.asarray([1.5, 0.75, 1.0, 1.0], dtype)   # const w/ ties

    def step(state, sl, it):
        count, total, last = state
        demand = sl["trace"] * eff + sl["tbl"]
        pick = masked_argmin(demand, count > 0)
        count = count.at[pick].add(1)
        return (count, total + jnp.sum(demand), last + it)

    return step


@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_fused_scan_bitwise_vs_fori(dtype):
    """The whole static-trip-count loop as ONE pallas_call (VMEM scratch
    carry + per-step blocked streams) must equal lax.fori_loop over the
    same step bit-for-bit — including under jit(vmap(...)), the driver's
    actual dispatch shape."""
    with x64():
        dt = jnp.dtype(dtype)
        T = 9
        rng = np.random.default_rng(3)
        traces = jnp.asarray(rng.random((3, T, 4)), dt)    # [B, T, 4]
        # [B, T]: per-lane [T] stream whose per-step slice is 0-d — the
        # scalar-stream padding path.
        tbls = jnp.asarray(rng.random((3, T)), dt)

        def run(trace, tbl, fused):
            streams = dict(trace=trace, tbl=tbl)
            spec = StepSpec(step=_scan_spec(dt), streams=streams)
            init = (jnp.full((4,), 2, jnp.int32), jnp.asarray(0.0, dt),
                    jnp.asarray(0, jnp.int32))
            if fused:
                return fused_scan(spec, init, T, interpret=True)
            body = body_from_step(spec)
            return jax.lax.fori_loop(
                0, T, lambda i, s: body(s, jnp.asarray(i, jnp.int32)),
                init)

        a = jax.jit(jax.vmap(lambda tr, tb: run(tr, tb, False)))(traces,
                                                                 tbls)
        b = jax.jit(jax.vmap(lambda tr, tb: run(tr, tb, True)))(traces,
                                                                tbls)
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)):
            assert np.array_equal(np.asarray(x), np.asarray(y))


def test_fused_scan_trip_zero_and_short_stream():
    with x64():
        spec = StepSpec(step=lambda s, sl, it: s,
                        streams=dict(x=jnp.zeros((4,))))
        init = (jnp.zeros((2,)),)
        out = fused_scan(spec, init, 0, interpret=True)
        assert np.array_equal(np.asarray(out[0]), np.zeros(2))
        with pytest.raises(ValueError, match="shorter than trip_count"):
            fused_scan(spec, init, 9, interpret=True)


def test_closure_convert_all_hoists_nondifferentiable_consts():
    """The raison d'être vs jax.closure_convert: *every* captured const —
    including a uint32 PRNG key — becomes an explicit argument, and the
    converted function replays the computation exactly."""
    key = jax.random.PRNGKey(11)

    def f(x):
        return x + jax.random.normal(key, x.shape)

    x = jnp.ones((3,))
    conv, consts = closure_convert_all(f, x)
    assert any(np.asarray(c).dtype == np.uint32 for c in consts)
    assert np.array_equal(np.asarray(conv(x, *consts)), np.asarray(f(x)))


# -- differential cell: fleet + power engines under use_pallas="force" ---------
#
# The CPU-only CI kernel-parity lane: "force" routes the whole body of
# both wired engines through the fused kernels (interpret mode here,
# native lowering on TPU/GPU — same call site), and every output must be
# bit-identical to the plain jnp path, so golden fixtures cannot churn.

def _assert_outputs_equal(a, b):
    assert set(a) == set(b)
    for k in sorted(a):
        x, y = np.asarray(a[k]), np.asarray(b[k])
        assert x.dtype == y.dtype, f"{k}: dtype {x.dtype} vs {y.dtype}"
        assert np.array_equal(x, y), f"{k}: fused path drifted"


def test_differential_fleet_force_parity():
    """Fleet (while-loop engine → per-iteration fused body): stochastic
    config with stragglers, eviction, degradation and failures on."""
    from repro.core.cluster import FleetConfig, StepCost
    from repro.core.vec_cluster import simulate_fleet_batch
    cost = StepCost(compute_s=1.0, memory_s=0.4, collective_s=0.3,
                    overlap_collective=0.5)
    cfg = FleetConfig(n_nodes=4, n_spares=1, straggler_sigma=0.25,
                      mtbf_hours_node=4.0)
    kw = dict(seeds=[0, 1], max_wallclock_s=20_000.0)
    a = simulate_fleet_batch(cost, cfg, 40, use_pallas=False, **kw)
    b = simulate_fleet_batch(cost, cfg, 40, use_pallas="force", **kw)
    _assert_outputs_equal(a, b)


def test_differential_power_force_parity():
    """Power (static-trip-count engine → whole-loop scan kernel), clean
    and faulted (adds the fail_tbl stream to the kernel's block inputs)."""
    from repro.core.faults import FaultEvent, FaultPlan
    from repro.core.vec_power import simulate_power_batch
    kw = dict(seeds=[0, 1], n_hosts=4, n_vms=8, n_samples=16)
    a = simulate_power_batch(use_pallas=False, **kw)
    b = simulate_power_batch(use_pallas="force", **kw)
    _assert_outputs_equal(a, b)
    plan = FaultPlan([FaultEvent("node", 600.0, 1800.0, target=1)])
    a = simulate_power_batch(use_pallas=False, fault_plan=plan, **kw)
    b = simulate_power_batch(use_pallas="force", fault_plan=plan, **kw)
    _assert_outputs_equal(a, b)


def test_power_force_matches_oo_bit_exact():
    """Transitivity check the differential suite relies on: the fused
    path equals vec-plain, which equals the OO reference — so fused must
    equal OO directly too (the strongest end-to-end statement)."""
    from repro.core.backend import run_scenario
    from repro.core.vec_power import simulate_power_batch
    kw = dict(seeds=[3], n_hosts=4, n_vms=8, n_samples=16)
    oo = run_scenario("power_batch", backend="oo", **kw)
    forced = simulate_power_batch(use_pallas="force", **kw)
    for k in ("energy_wh", "migrations", "sla_s", "final_active"):
        assert np.array_equal(np.asarray(oo[k]), np.asarray(forced[k])), k
