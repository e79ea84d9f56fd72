"""Compacting lane scheduler — bit-identity, refill edges, streaming, sharding.

The compacting path (``compact_sweep`` + ``vec_engine.segment_step``) must
extend the sweep layer's strict exactness contract: retiring and refilling
lanes mid-flight is a *schedule* over independent vmap lanes and may not
change one output bit relative to the monolithic dispatch.  Covered here:
every refill edge case the host scheduler has (queue drains mid-chunk, all
lanes finishing on the same step, single-lane grids, refill under LPT
bucketing), the streaming ``on_chunk``/``progress`` consumer APIs, the
report's refill/retire/peak-lane accounting, and 2-device ``shard_map``
parity in a subprocess (mirroring the chunked-path test in
``test_sweep.py``).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.backend import run_sweep
from repro.core.sweep import SweepConfig
from repro.core.cluster import FleetConfig, StepCost
from repro.core.vec_cluster import simulate_fleet_batch

COST = StepCost(compute_s=1.2, memory_s=0.5, collective_s=0.4,
                overlap_collective=0.6)
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


@pytest.fixture(scope="module")
def mono():
    return _fleet(chunk_size=B)


# -- bit-identity --------------------------------------------------------------

@pytest.mark.parametrize("lanes,budget", [(8, 7), (16, 64), (B, 5), (5, 13)])
def test_fleet_compact_bit_identical(mono, lanes, budget):
    """Across resident-batch sizes and segment budgets — including budgets
    that never let a lane finish in one segment and lane counts that don't
    divide the grid — the bits match the monolithic dispatch."""
    out, rep = _fleet(compact=True, chunk_size=lanes, segment_iters=budget,
                      with_report=True)
    assert rep.compacted and rep.chunk_size == lanes
    for k in mono:
        assert np.array_equal(mono[k], out[k]), k


def test_compact_defaults_bit_identical(mono):
    out, rep = _fleet(compact=True, with_report=True)
    assert rep.compacted
    for k in mono:
        assert np.array_equal(mono[k], out[k]), k


def test_compact_donation_off_bit_identical(mono):
    out = _fleet(compact=True, chunk_size=8, segment_iters=7, donate=False)
    for k in mono:
        assert np.array_equal(mono[k], out[k]), k


# -- refill edge cases ---------------------------------------------------------

def test_refill_queue_drains_mid_chunk(mono):
    """More retires per segment than queued work near the end: freed slots
    must go dormant without disturbing resident lanes."""
    # 32 cells into 12 lanes: the queue (20 deep after the initial fill)
    # drains while retires keep coming.
    out, rep = _fleet(compact=True, chunk_size=12, segment_iters=7,
                      with_report=True)
    assert rep.refills == B - 12 and rep.retires == B
    assert rep.peak_lanes == 12
    for k in mono:
        assert np.array_equal(mono[k], out[k]), k


def test_refill_all_lanes_finish_same_step():
    """A deterministic equal-length grid with budget ≥ loop length: every
    lane retires on segment 1, the whole batch refills at once, and the
    observed active fraction is exactly 1."""
    cfg = FleetConfig(n_nodes=8, n_spares=2, straggler_sigma=0.0,
                      mtbf_hours_node=1e9, degrade_mtbf_hours=1e9,
                      straggler_evict_factor=1e9)
    kw = dict(seeds=np.arange(16), ckpt_every=10)
    ref = simulate_fleet_batch(COST, cfg, 40, **kw)
    out, rep = simulate_fleet_batch(COST, cfg, 40, compact=True,
                                    chunk_size=4, segment_iters=64,
                                    with_report=True, **kw)
    assert rep.segments == 4 and rep.refills == 12
    assert rep.active_lane_fraction == 1.0
    for k in ref:
        assert np.array_equal(ref[k], out[k]), k


def test_single_lane_compact_sweep():
    out, rep = simulate_fleet_batch(COST, FLEET_CFG, 60, seeds=[3],
                                    mtbf_hours=20.0, compact=True,
                                    with_report=True)
    ref = simulate_fleet_batch(COST, FLEET_CFG, 60, seeds=[3],
                               mtbf_hours=20.0)
    assert rep.n_cells == 1 and rep.chunk_size == 1 and rep.peak_lanes == 1
    assert rep.refills == 0 and rep.retires == 1
    for k in ref:
        assert np.array_equal(ref[k], out[k]), k


def test_refill_under_divergence_bucketing(mono):
    """With predicted_cost present the queue is LPT-ordered (longest first).
    The outputs still land in original cell order, bit-identical."""
    out, rep = _fleet(compact=True, chunk_size=8, segment_iters=7,
                      with_report=True)
    assert rep.bucketed            # fleet predicts per-cell cost ⇒ LPT queue
    assert rep.refills == B - 8 and rep.segments > 1
    for k in mono:
        assert np.array_equal(mono[k], out[k]), k


def test_compact_lanes_exceeding_grid_clamp(mono):
    out, rep = _fleet(compact=True, chunk_size=10 * B, with_report=True)
    assert rep.chunk_size == B and rep.refills == 0
    for k in mono:
        assert np.array_equal(mono[k], out[k]), k


# -- streaming consumers -------------------------------------------------------

def test_on_chunk_streams_every_cell_once(mono):
    seen = []
    out, rep = _fleet(compact=True, chunk_size=8, segment_iters=7,
                      on_chunk=lambda cells, raw: seen.append((cells, raw)),
                      with_report=True)
    streamed = np.concatenate([c for c, _ in seen])
    assert sorted(streamed.tolist()) == list(range(B))   # each cell once
    # chunk payloads are the raw engine outputs, bit-identical per cell
    for cells, raw in seen:
        assert np.array_equal(raw["goodput"], out["goodput"][cells])
        assert np.array_equal(raw["wallclock_s"], mono["wallclock_s"][cells])
    assert len(seen) <= rep.segments


def test_on_chunk_streams_on_chunked_path_too(mono):
    seen = []
    out = _fleet(chunk_size=8,
                 on_chunk=lambda cells, raw: seen.append((cells, raw)))
    assert len(seen) == 4
    streamed = np.concatenate([c for c, _ in seen])
    assert sorted(streamed.tolist()) == list(range(B))
    for cells, raw in seen:
        assert np.array_equal(raw["goodput"], mono["goodput"][cells])


def test_progress_tap_fires_per_segment():
    """The in-graph io_callback retire tap reports one (done mask, segment
    iters) pair per compiled segment, with canonicalization-safe dtypes."""
    events = []
    _, rep = _fleet(compact=True, chunk_size=8, segment_iters=7,
                    progress=lambda done, j: events.append((done, j)),
                    with_report=True)
    assert len(events) == rep.segments
    for done, j in events:
        assert done.dtype == np.bool_ and done.shape == (8,)
        assert j.dtype == np.int32 and j.max() <= 7


# -- report accounting ---------------------------------------------------------

def test_compact_report_accounting(mono):
    out, rep = _fleet(compact=True, chunk_size=8, segment_iters=7,
                      with_report=True)
    assert rep.compacted and rep.n_cells == B
    assert rep.retires == B and rep.refills == B - 8
    assert rep.n_chunks == rep.segments > 1
    assert rep.peak_lanes == 8 and rep.devices == 1 and rep.sharding is None
    assert np.array_equal(rep.lane_iterations, mono["iterations"])
    assert 0.0 < rep.active_lane_fraction <= 1.0
    assert rep.active_lane_fraction_observed == rep.active_lane_fraction
    # compaction keeps the batch dense: it must beat (or match) what the
    # monolithic dispatch achieved on this divergent grid
    assert rep.active_lane_fraction > rep.active_lane_fraction_monolithic


def test_chunked_report_carries_predicted_and_observed_fractions():
    _, rep = _fleet(chunk_size=8, with_report=True)
    assert 0.0 < rep.active_lane_fraction <= 1.0            # observed
    assert 0.0 < rep.active_lane_fraction_predicted <= 1.0  # cost model
    assert rep.active_lane_fraction_observed == rep.active_lane_fraction
    assert not rep.compacted and rep.refills == 0 and rep.segments == 0


# -- device-resident lane params ---------------------------------------------

LLM = dict(seeds=np.arange(8), n_requests=40)


def test_identity_batch_uploads_params_once(monkeypatch):
    """Every cell resident in order (no predicted cost, lanes = cells): the
    caller's params go to the device as they are — no gathered copy — once,
    and every later segment reuses the device copy, as it reuses the plan's
    shared request tables, put there once before them."""
    import jax

    from repro.core import vec_engine
    mono = run_sweep("llmserve_batch", LLM).outputs
    handed, put = [], []
    real_sweep, real_put = vec_engine.compact_sweep, jax.device_put

    def keep(step, params, **kw):
        handed.append(params)
        return real_sweep(step, params, **kw)

    def spy(x, *a, **k):
        put.append(x)
        return real_put(x, *a, **k)
    monkeypatch.setattr(vec_engine, "compact_sweep", keep)
    monkeypatch.setattr(jax, "device_put", spy)
    out, rep = run_sweep("llmserve_batch", LLM, config=SweepConfig(
        compact=True, chunk_size=8, segment_iters=16))
    assert rep.segments > 1 and rep.refills == 0
    assert rep.param_uploads == 1 and len(put) == 2
    tables, lanes = put
    assert rep.shared_bytes == np.asarray(tables).nbytes > 0
    for sent, given in zip(jax.tree_util.tree_leaves(lanes),
                           jax.tree_util.tree_leaves(handed[0])):
        assert np.shares_memory(sent, np.asarray(given))
    for k in mono:
        assert np.array_equal(mono[k], out[k]), k


def test_refill_reuploads_params_after_refills_only(mono, monkeypatch):
    """A refilling sweep sends the lane params once, then once more for each
    segment that follows a refill (its fresh mask names new lanes), and
    stays bit-identical to the monolithic dispatch."""
    from repro.core import vec_engine
    fresh, real = [], vec_engine.segment_step

    def spying(*a, **k):
        step5 = real(*a, **k)

        def step(lane_params, state, it, f, sid):
            fresh.append(np.array(f))
            return step5(lane_params, state, it, f, sid)
        return step
    monkeypatch.setattr(vec_engine, "segment_step", spying)
    out, rep = _fleet(compact=True, chunk_size=8, segment_iters=7,
                      with_report=True)
    assert len(fresh) == rep.segments and fresh[0].all()
    after_refill = sum(bool(f.any()) for f in fresh[1:])
    assert rep.refills == B - 8 and 0 < after_refill < rep.segments - 1
    assert rep.param_uploads == 1 + after_refill
    for k in mono:
        assert np.array_equal(mono[k], out[k]), k


def _jit_counting_step(budget=4):
    """Jitted segment step, state and counters donated as the engines' are:
    lane i runs ``need[i]`` iterations adding ``v[i]`` each."""
    import jax
    import jax.numpy as jnp

    def step(lane_params, state, it, fresh):
        v, need = lane_params
        state = jnp.where(fresh, 0.0, state)
        it = jnp.where(fresh, 0, it)
        j = jnp.clip(need - it, 0, budget)
        state, it = state + v * j, it + j
        return state, it, it >= need, j, {"total": state, "iterations": it}
    return jax.jit(step, donate_argnums=(1, 2))


@pytest.mark.parametrize("quarantine", [False, True])
def test_identity_compact_leaves_caller_params_unchanged(quarantine):
    """The resident batch aliases the caller's params on the identity path:
    a donated sweep, and a quarantine that frees a slot, write nothing into
    them."""
    from repro.core.sweep import compact_sweep
    v = np.arange(1, 9, dtype=np.float32)
    if quarantine:
        v[5] = np.nan
    need = np.arange(4, 36, 4, dtype=np.int32)
    before = (v.tobytes(), need.tobytes())
    out, rep = compact_sweep(_jit_counting_step(), (v, need), lanes=8,
                             state_prototype=np.zeros((), np.float32),
                             quarantine=quarantine)
    assert (v.tobytes(), need.tobytes()) == before
    assert rep.param_uploads == 1 and rep.segments == 8
    assert rep.quarantined == int(quarantine)
    ok = ~np.isnan(v)
    assert np.array_equal(out["total"][ok], (v * need)[ok])


# -- sharding ------------------------------------------------------------------

_SUBPROC_PRELUDE = f"""
import numpy as np
from repro.core.vec_cluster import simulate_fleet_batch
from repro.core.cluster import FleetConfig, StepCost
import jax
assert jax.device_count() == 2, jax.devices()
kw = dict(seeds=np.arange({B}),
          mtbf_hours=np.repeat([200.0, 20.0, 2.0, 0.5], {B // 4}),
          ckpt_every=np.tile([10, 50], {B // 2}))
cost = StepCost(compute_s=1.2, memory_s=0.5, collective_s=0.4,
                overlap_collective=0.6)
cfg = FleetConfig(n_nodes=8, n_spares=2, straggler_sigma=0.08,
                  repair_hours=0.5, degrade_mtbf_hours=1e9,
                  straggler_evict_factor=1e9)
"""


def _run_two_device(code: str) -> str:
    env = dict(os.environ,
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=2"),
               PYTHONPATH=os.pathsep.join(sys.path), JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", _SUBPROC_PRELUDE + code],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr
    return proc.stdout


def test_shard_map_two_device_parity(mono):
    """shard_map sharding over 2 forced host devices reproduces the
    1-device bits — on the chunked path and the compacting path.  Needs a
    fresh process (XLA device count is fixed at backend init)."""
    stdout = _run_two_device("""
out, rep = simulate_fleet_batch(cost, cfg, 60, chunk_size=16,
                                with_report=True, **kw)
assert rep.devices == 2 and rep.sharding == "shard_map", rep
print(out["wallclock_s"].tobytes().hex())
cout, crep = simulate_fleet_batch(cost, cfg, 60, compact=True,
                                  chunk_size=8, segment_iters=7,
                                  with_report=True, **kw)
assert crep.devices == 2 and crep.sharding == "shard_map", crep
assert crep.compacted and crep.refills > 0, crep
assert 2 <= crep.param_uploads < crep.segments, crep
# The lane params are put on the device split as the sharded step takes
# them: a reshard in any segment would be a device-to-device copy.
with jax.transfer_guard_device_to_device("disallow"):
    gout, grep = simulate_fleet_batch(cost, cfg, 60, compact=True,
                                      chunk_size=8, segment_iters=7,
                                      with_report=True, **kw)
assert grep.report_fields() == crep.report_fields()
assert all(np.array_equal(gout[k], cout[k]) for k in cout)
print(cout["wallclock_s"].tobytes().hex())
print(cout["goodput"].tobytes().hex())
""")
    shard_hex, compact_hex, compact_good = stdout.split()
    assert shard_hex == mono["wallclock_s"].tobytes().hex()
    assert compact_hex == mono["wallclock_s"].tobytes().hex()
    assert compact_good == mono["goodput"].tobytes().hex()


# -- direct compact_sweep error contracts -------------------------------------

def test_compact_sweep_rejects_empty_grid():
    from repro.core.sweep import compact_sweep
    with pytest.raises(ValueError, match="empty grid"):
        compact_sweep(lambda *a: None, (np.zeros((0, 3)),), lanes=4,
                      state_prototype=None)


def test_run_sweep_compact_through_registry(mono):
    """The scenario registry forwards the new controls end to end."""
    out, rep = run_sweep(
        "fleet_batch",
        dict(cost=COST, cfg=FLEET_CFG, total_steps=60, seeds=SEEDS,
             mtbf_hours=MTBF, ckpt_every=CKPT),
        config=SweepConfig(compact=True, chunk_size=8, segment_iters=7))
    assert rep.compacted and rep.refills == B - 8
    for k in mono:
        assert np.array_equal(mono[k], out[k]), k
