"""Sweep execution layer (repro.core.sweep) — exactness and policy tests.

The layer's contract is strict: chunking, divergence bucketing, buffer
donation, and device sharding are *schedules* over independent vmap lanes
and must not change one output bit relative to the monolithic dispatch.
Covered here for all batched entry points (``fleet_batch``,
``workflow_batch``, ``cloudlet_batch`` cells, ``consolidation_batch``),
plus the chunking policy, the divergence report, the Pallas CPU
auto-fallback, and the f32 fast path's shared-sample guarantee.
"""
import subprocess
import sys
import warnings

import numpy as np
import pytest

from repro.core.backend import run_scenario, run_sweep
from repro.core.cluster import FleetConfig, StepCost
from repro.core.sweep import (SweepConfig, SweepReport, auto_chunk_size,
                              run_host_sweep)
from repro.core.vec_cluster import simulate_fleet_batch

COST = StepCost(compute_s=1.2, memory_s=0.5, collective_s=0.4,
                overlap_collective=0.6)

# Divergent little grid: the mtbf axis spreads predicted loop lengths, so
# the auto policy buckets; small enough to compile in seconds.
FLEET_CFG = FleetConfig(n_nodes=8, n_spares=2, straggler_sigma=0.08,
                        repair_hours=0.5, degrade_mtbf_hours=1e9,
                        straggler_evict_factor=1e9)
B = 32
MTBF = np.repeat([200.0, 20.0, 2.0, 0.5], B // 4)
CKPT = np.tile([10, 50], B // 2)
SEEDS = np.arange(B)


def _fleet(**kw):
    return simulate_fleet_batch(COST, FLEET_CFG, 60, seeds=SEEDS,
                                mtbf_hours=MTBF, ckpt_every=CKPT, **kw)


# -- bit-identity: chunked / bucketed / sharded-fallback vs monolithic --------

@pytest.mark.parametrize("precision", ["exact", "fast"])
def test_fleet_chunked_bit_identical(precision):
    mono = _fleet(precision=precision, chunk_size=B)
    chunked, rep = _fleet(precision=precision, chunk_size=10,  # uneven: pads
                          with_report=True)
    assert rep.n_chunks == 4 and rep.chunk_size == 10 and rep.bucketed
    for k in mono:
        assert np.array_equal(mono[k], chunked[k]), k


def test_fleet_auto_policy_bit_identical_and_bucketed():
    mono = _fleet(chunk_size=B)
    auto, rep = _fleet(with_report=True)
    assert rep.bucketed and rep.n_chunks > 1      # mtbf spread ⇒ buckets
    for k in mono:
        assert np.array_equal(mono[k], auto[k]), k


def test_fleet_single_device_sharded_fallback_bit_identical():
    mono = _fleet(chunk_size=B)
    sharded, rep = _fleet(devices=1, chunk_size=16, with_report=True)
    assert rep.devices == 1
    for k in mono:
        assert np.array_equal(mono[k], sharded[k]), k


def test_fleet_donation_off_bit_identical():
    mono = _fleet(chunk_size=B)
    undonated = _fleet(chunk_size=16, donate=False)
    for k in mono:
        assert np.array_equal(mono[k], undonated[k]), k


def test_fleet_multi_device_sharded_bit_identical():
    """The default multi-device executor (``shard_map`` over the lane mesh)
    on 2 forced host devices reproduces the 1-device bits.  Needs a fresh
    process: XLA device count is fixed at backend init."""
    mono = _fleet(chunk_size=B)
    code = f"""
import numpy as np
from repro.core.vec_cluster import simulate_fleet_batch
from repro.core.cluster import FleetConfig, StepCost
import jax
assert jax.device_count() == 2, jax.devices()
out, rep = simulate_fleet_batch(
    StepCost(compute_s=1.2, memory_s=0.5, collective_s=0.4,
             overlap_collective=0.6),
    FleetConfig(n_nodes=8, n_spares=2, straggler_sigma=0.08,
                repair_hours=0.5, degrade_mtbf_hours=1e9,
                straggler_evict_factor=1e9),
    60, seeds=np.arange({B}),
    mtbf_hours=np.repeat([200.0, 20.0, 2.0, 0.5], {B // 4}),
    ckpt_every=np.tile([10, 50], {B // 2}),
    chunk_size=16, with_report=True)
assert rep.devices == 2 and rep.sharding == "shard_map", rep
print(out["wallclock_s"].tobytes().hex())
print(out["goodput"].tobytes().hex())
"""
    import os
    env = dict(os.environ,
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=2"),
               PYTHONPATH=os.pathsep.join(sys.path), JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    wall_hex, good_hex = proc.stdout.split()
    assert wall_hex == mono["wallclock_s"].tobytes().hex()
    assert good_hex == mono["goodput"].tobytes().hex()


def test_workflow_chunked_bit_identical():
    diamond = dict(nodes=[1000.0, 2000.0, 1500.0, 1000.0],
                   edges=[(0, 1), (0, 2), (1, 3), (2, 3)],
                   guest_of=[0, 1, 2, 0], guest_mips=[1000.0] * 3,
                   payload=list(np.linspace(0.0, 2e6, 12)),
                   activations=3, arrival_rate=0.5)
    mono = run_scenario("workflow_batch", backend="vec", **diamond)
    chunked, rep = run_scenario("workflow_batch", backend="vec",
                                chunk_size=5, with_report=True, **diamond)
    assert rep.n_chunks == 3
    for k in mono:
        assert np.array_equal(mono[k], chunked[k]), k


def test_cloudlet_cells_chunked_bit_identical():
    rng = np.random.default_rng(7)
    Bc, G, C = 10, 3, 4
    kw = dict(
        length=rng.uniform(100, 4000, (Bc, G, C))
        * (rng.random((Bc, G, C)) < 0.8),
        pes=np.ones((Bc, G, C)),
        submit=rng.uniform(0, 10, (Bc, G, C)),
        guest_mips=rng.uniform(500, 1500, (Bc, G)),
        guest_pes=np.full((Bc, G), 2.0))
    mono = run_scenario("cloudlet_batch", backend="vec", **kw)
    chunked, rep = run_sweep("cloudlet_batch", kw,
                             config=SweepConfig(chunk_size=3))
    assert rep.n_chunks == 4
    assert np.array_equal(mono, chunked)
    # and the cells contract matches the OO engine per cell (inf-safe)
    oo = run_scenario("cloudlet_batch", backend="oo", **kw)
    assert np.array_equal(np.isfinite(mono), np.isfinite(oo))
    m = np.isfinite(mono)
    np.testing.assert_allclose(mono[m], oo[m], rtol=1e-12)


def test_empty_batch_returns_empty_outputs():
    out, rep = simulate_fleet_batch(COST, FLEET_CFG, 60,
                                    seeds=np.array([], np.uint32),
                                    with_report=True)
    assert rep.n_cells == 0 and rep.n_chunks == 0
    assert out["goodput"].shape == (0,)
    assert out["iterations"].shape == (0,)


def test_run_sweep_rejects_sweepless_paths():
    """A kind/backend pair with no sweep path must raise, never hand back a
    bare result the caller would mis-unpack as (result, report)."""
    from repro.core.backend import ScenarioUnsupported
    rng = np.random.default_rng(0)
    kw = dict(length=rng.uniform(100, 500, (2, 2, 3)),
              pes=np.ones((2, 2, 3)), submit=np.zeros((2, 2, 3)),
              guest_mips=np.full((2, 2), 1000.0),
              guest_pes=np.ones((2, 2)))
    with pytest.raises((TypeError, ScenarioUnsupported)):
        run_sweep("cloudlet_batch", backend="oo", **kw)
    with pytest.raises((TypeError, ScenarioUnsupported)):
        run_sweep("consolidation", backend="oo", algo="ThrMu", n_hosts=4,
                  n_vms=8, n_samples=4)


def test_consolidation_batch_host_sweep_matches_loop():
    from repro.core.consolidation_sim import run_consolidation
    res, rep = run_sweep("consolidation_batch", seeds=[1, 2], n_hosts=8,
                         n_vms=16, n_samples=12)
    assert isinstance(rep, SweepReport) and rep.devices == 1
    assert rep.active_lane_fraction == 1.0
    for seed, r in zip([1, 2], res):
        single = run_consolidation("vec", seed=seed, n_hosts=8, n_vms=16,
                                   n_samples=12)
        assert (r.migrations, r.energy_kwh) == (single.migrations,
                                                single.energy_kwh)


# -- divergence accounting + policy -------------------------------------------

def test_report_divergence_accounting():
    out, rep = _fleet(chunk_size=8, with_report=True)
    assert rep.n_cells == B and rep.devices >= 1
    assert rep.lane_iterations.shape == (B,)
    assert (rep.lane_iterations == out["iterations"]).all()
    assert 0.0 < rep.active_lane_fraction <= 1.0
    assert 0.0 < rep.active_lane_fraction_monolithic <= 1.0
    # bucketed chunks can only improve (or match) lane occupancy
    assert rep.active_lane_fraction >= rep.active_lane_fraction_monolithic


def test_auto_chunk_size_policy():
    # no prediction / uniform prediction / tiny grids: monolithic
    assert auto_chunk_size(256, None, 1) == 256
    assert auto_chunk_size(256, np.full(256, 7.0), 1) == 256
    assert auto_chunk_size(24, np.r_[np.full(12, 1.0), np.full(12, 9.0)],
                           1) == 24
    # divergent large grid: ~8 chunks, floored at MIN_CHUNK lanes/device
    # and aligned to a device multiple; the split is *balanced* so the last
    # chunk is never nearly all padding (5 × 54 covers 256 with 14 pad
    # lanes total, vs 48-lane chunks leaving a 16-real/32-pad tail).
    assert auto_chunk_size(256, np.linspace(1, 10, 256), 1) == 32
    assert auto_chunk_size(256, np.linspace(1, 10, 256), 3) == 54


def test_auto_chunk_size_degenerate_cases():
    """Grids smaller than the device fleet and all-equal predictions must
    never produce a chunk bigger than the grid (pure pad waste)."""
    divergent = np.linspace(1, 10, 8)
    # fewer cells than devices: clamp, run monolithic
    assert auto_chunk_size(8, divergent, 16) == 8
    assert auto_chunk_size(1, np.array([5.0]), 4) == 1
    assert auto_chunk_size(0, None, 4) == 0
    # all-equal cost never chunks, whatever the device count
    for nd in (1, 3, 16, 1000):
        assert auto_chunk_size(256, np.full(256, 7.0), nd) == 256
    # zero/negative predictions: no spread information, monolithic
    assert auto_chunk_size(256, np.zeros(256), 1) == 256
    # the balanced chunk never exceeds the grid
    for n in (33, 64, 100, 256, 1000):
        for nd in (1, 2, 3, 7):
            c = auto_chunk_size(n, np.linspace(1, 10, n), nd)
            assert 1 <= c <= n, (n, nd, c)
            if c < n:
                assert c % min(nd, n) == 0, (n, nd, c)


def test_run_host_sweep_orders_and_restores():
    calls = []

    def cell(i):
        calls.append(i)
        return i * 10

    res, rep = run_host_sweep(cell, 4, predicted_cost=[1.0, 4.0, 2.0, 3.0])
    assert res == [0, 10, 20, 30]             # original order restored
    assert calls == [1, 3, 2, 0]              # executed longest-first
    assert rep.bucketed and rep.devices == 1


# -- fast-path repairs --------------------------------------------------------

def test_fast_precision_shares_failure_sample():
    """precision="fast" must see the *same* pre-drawn failure schedules as
    exact mode (an independent f32 RNG stream is a different — and once
    measurably unluckier — scenario sample)."""
    exact = _fleet(precision="exact", chunk_size=B)
    fast = _fleet(precision="fast", chunk_size=B)
    assert exact["failures"].sum() > 0        # the grid actually fails
    assert np.array_equal(exact["failures"], fast["failures"])
    assert np.array_equal(exact["restarts"], fast["restarts"])
    # Per-step jitter draws stay dtype-local, so lanes drift at f32 scale —
    # but with the schedules shared the drift is percent-level even on this
    # failure-saturated grid, not a different scenario.
    good = np.abs(fast["goodput"] - exact["goodput"])
    assert good.max() < 0.05 and good.mean() < 5e-3


def test_pallas_cpu_auto_fallback_warns_once_and_matches():
    import jax
    from repro.kernels import ops
    if ops.pallas_native():                   # on TPU/GPU there is no fallback
        pytest.skip("Pallas lowers natively here")
    plain = _fleet(chunk_size=B)
    ops.reset_pallas_warning()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        first = _fleet(chunk_size=B, use_pallas=True)
        second = _fleet(chunk_size=B, use_pallas=True)
    msgs = [w for w in rec if issubclass(w.category, RuntimeWarning)
            and "use_pallas" in str(w.message)]
    assert len(msgs) == 1                     # one-time warning
    for k in plain:                           # fallback IS the plain path
        assert np.array_equal(plain[k], first[k]), k
        assert np.array_equal(plain[k], second[k]), k
    assert jax.default_backend() == "cpu"


def test_resolve_use_pallas_force():
    from repro.kernels.ops import resolve_use_pallas
    assert resolve_use_pallas(False) is False
    assert resolve_use_pallas("force") is True


# -- edge cases: chunk clamping, single lane, degenerate bucketing, errors ----

def test_chunk_size_larger_than_lane_count_clamps():
    """chunk_size beyond the grid is clamped to one full (monolithic)
    chunk — same bits, sane report."""
    mono = _fleet(chunk_size=B)
    over, rep = _fleet(chunk_size=10 * B, with_report=True)
    assert rep.n_chunks == 1 and rep.chunk_size == B
    for k in mono:
        assert np.array_equal(mono[k], over[k]), k


def test_single_lane_sweep():
    out, rep = simulate_fleet_batch(COST, FLEET_CFG, 60, seeds=[3],
                                    mtbf_hours=20.0, with_report=True)
    assert rep.n_cells == 1 and rep.n_chunks == 1 and rep.chunk_size == 1
    assert out["goodput"].shape == (1,)
    assert rep.active_lane_fraction == 1.0          # one lane never idles


def test_identical_lanes_bucketing_degenerate():
    """All lanes predicted identical: the auto policy stays monolithic
    (bucketing can't help), and every lane's result is the same bits."""
    from repro.core.sweep import execute_sweep

    def fn(params):
        (x,) = params
        import jax.numpy as jnp
        return {"y": x * 2.0, "iterations": jnp.ones(x.shape[0],
                                                     jnp.int32) * 5}

    x = np.full(48, 7.0)
    out, rep = execute_sweep(fn, (x,), predicted_cost=np.full(48, 3.0))
    assert not rep.bucketed and rep.n_chunks == 1 and rep.chunk_size == 48
    assert (out["y"] == 14.0).all()
    assert rep.active_lane_fraction == 1.0          # uniform iterations
    # An *explicit* chunk_size with a predicted_cost reports bucketed=True
    # even over identical lanes — the sort ran, it just reorders nothing —
    # and the outputs stay bit-identical to the monolithic dispatch.
    chunked, rep2 = execute_sweep(fn, (x,), chunk_size=7,
                                  predicted_cost=np.full(48, 3.0))
    assert rep2.bucketed and rep2.n_chunks == 7     # ordering is a no-op
    assert np.array_equal(out["iterations"], chunked["iterations"])
    assert np.array_equal(out["y"], chunked["y"])


def _aliasing_fn(params):
    """Output of the input's shape and dtype, so a donated input buffer
    can be reused for it."""
    (x,) = params
    import jax.numpy as jnp
    return {"y": x * 3 + 1, "iterations": jnp.ones(x.shape[0], jnp.int32)}


@pytest.mark.parametrize("chunk_size,identity", [(48, True), (20, False)])
def test_chunk_params_gathered_only_when_cells_move(chunk_size, identity,
                                                    monkeypatch):
    """One chunk of every cell in order hands the caller's arrays to the
    dispatch as they are; chunks that cut, pad or reorder the cells get a
    gathered copy.  One params upload per chunk either way, and a donated
    dispatch leaves the caller's params byte for byte as they were."""
    from repro.core import sweep
    x = np.arange(48 * 16, dtype=np.int32).reshape(48, 16)
    before = x.tobytes()
    handed, real = [], sweep._dispatch

    def spy(executor, chunk_params, *a):
        handed.append(chunk_params[0])
        return real(executor, chunk_params, *a)
    monkeypatch.setattr(sweep, "_dispatch", spy)
    out, rep = sweep.execute_sweep(_aliasing_fn, (x,), chunk_size=chunk_size,
                                   donate=True)
    assert x.tobytes() == before
    assert np.array_equal(out["y"], x * 3 + 1)
    assert rep.param_uploads == rep.n_chunks == len(handed)
    assert rep.h2d_bytes == rep.n_chunks * rep.chunk_size * 16 * 4
    assert [np.shares_memory(h, x) for h in handed] == \
        [identity] * rep.n_chunks


def test_run_sweep_rejection_messages():
    """Unregistered kind/backend pairs reject with an actionable message —
    naming the kind, the backend, and where the scenario IS available."""
    from repro.core.backend import (BackendError, ScenarioUnsupported,
                                    _SCENARIOS, run_scenario, scenario)
    with pytest.raises(BackendError, match="unknown scenario kind"):
        run_sweep("warp_drive", backend="vec")
    with pytest.raises(BackendError, match="unknown backend"):
        run_sweep("fleet_batch", backend="quantum")
    try:
        @scenario("_sweep_probe", backends=("oo",))
        def _probe(backend, **kw):
            return "bare result"                     # no SweepReport
        with pytest.raises(ScenarioUnsupported,
                           match=r"_sweep_probe.*not implemented on backend "
                                 r"'vec'.*supported backends: 'oo' "
                                 r"\(aliases: '7g'→'oo'\)"):
            run_sweep("_sweep_probe", backend="vec")
        # a handler that swallows with_report but returns no report must
        # also be rejected — never a bare result the caller mis-unpacks
        with pytest.raises(ScenarioUnsupported,
                           match="no sweep-aware path"):
            run_sweep("_sweep_probe", backend="oo")
        assert run_scenario("_sweep_probe", backend="oo",
                            with_report=False) == "bare result"
    finally:
        _SCENARIOS.pop("_sweep_probe", None)


def test_auto_chunk_size_ignores_zero_cost_lanes():
    """A few zero-predicted-cost lanes (an empty trace slice, a zero-job
    cell) carry no divergence information and must not silently disable
    chunking for the whole sweep."""
    pred = np.linspace(1, 10, 256)
    pred[0] = 0.0
    assert auto_chunk_size(256, pred, 1) == 32   # chunking still engages
    pred[1] = -2.0                               # defensive: negatives too
    assert auto_chunk_size(256, pred, 1) == 32
    # positive lanes that do NOT diverge stay monolithic despite the zeros
    flat = np.full(256, 7.0)
    flat[:8] = 0.0
    assert auto_chunk_size(256, flat, 1) == 256
