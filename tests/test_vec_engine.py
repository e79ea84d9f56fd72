"""Unit tests for the VecEngine substrate (``repro.core.vec_engine``) —
the declarative SoA event-loop layer under all five vec engines.

A toy "drain" engine (each cell counts down from ``start`` in unit steps,
recording the step at which a masked argmin fired) exercises the driver's
iteration counting, the ops plumbing, batching, the sweep routing, the
``Done`` short-circuit, and ``make_batch_entry`` registration end-to-end.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import vec_engine
from repro.core.backend import _SCENARIOS, run_scenario, run_sweep
from repro.core.sweep import SweepReport
from repro.core.vec_engine import (BatchPlan, Done, Loop, VecEngine,
                                   make_batch_entry, resolve_precision,
                                   run_one)


class _Statics:
    use_pallas = False


def _drain_build(params, statics, ops):
    start, costs, mask = params

    def body(c, it):
        left, pick = c
        return left - 1.0, ops.argmin(costs, mask).astype(jnp.int32)

    return Loop(init=(start, jnp.asarray(-1, jnp.int32)),
                cond=lambda c, it: c[0] > 0,
                body=body,
                finalize=lambda c, it: dict(left=c[0], pick=c[1]))


DRAIN = VecEngine("_drain", _drain_build)


def _params(starts):
    starts = np.asarray(starts, np.float64)
    b = starts.shape[0]
    costs = np.tile([3.0, 1.0, 1.0, 2.0], (b, 1))
    mask = np.tile([True, False, True, True], (b, 1))
    return starts, costs, mask


def test_run_one_counts_iterations_and_binds_ops():
    starts, costs, mask = _params([5.0])
    out = run_one(DRAIN, (starts[0], costs[0], mask[0]), _Statics())
    assert int(out["iterations"]) == 5
    assert float(out["left"]) == 0.0
    assert int(out["pick"]) == 2          # masked first-occurrence argmin


def test_run_plan_batches_and_reports():
    starts = np.asarray([3.0, 7.0, 1.0, 5.0])
    plan = BatchPlan(_params(starts), _Statics(),
                     predicted_cost=starts)
    out, report = vec_engine.run_plan(DRAIN, plan, with_report=True)
    assert isinstance(report, SweepReport) and report.n_cells == 4
    assert np.array_equal(out["iterations"], starts.astype(int))
    assert np.array_equal(out["pick"], [2, 2, 2, 2])
    # chunked schedule is bit-identical to monolithic
    mono = vec_engine.run_plan(DRAIN, plan)
    chunked, rep2 = vec_engine.run_plan(DRAIN, plan, chunk_size=2,
                                        with_report=True)
    assert rep2.n_chunks == 2
    for k in mono:
        assert np.array_equal(mono[k], chunked[k]), k


def test_finalize_may_override_iterations():
    eng = VecEngine("_drain2", lambda p, s, ops: Loop(
        init=jnp.asarray(2.0),
        cond=lambda c, it: c > 0,
        body=lambda c, it: c - 1.0,
        finalize=lambda c, it: dict(iterations=it + 10)))
    out = run_one(eng, None, _Statics())
    assert int(out["iterations"]) == 12


def test_done_short_circuits_without_dispatch():
    marker = dict(empty=True)
    out, report = vec_engine.run_plan(DRAIN, Done(marker), with_report=True)
    assert out is marker
    assert report.n_cells == 0 and report.n_chunks == 0


def test_resolve_precision():
    assert resolve_precision("exact") is False
    assert resolve_precision("fast") is True
    with pytest.raises(ValueError, match="precision"):
        resolve_precision("half")


def test_make_batch_entry_registers_scenario_and_routes_sweep():
    try:
        entry = make_batch_entry(
            DRAIN,
            lambda starts, *, use_pallas: BatchPlan(_params(starts),
                                                    _Statics()),
            kind="_drain_batch", name="simulate_drain")
        assert entry.__name__ == "simulate_drain"
        out = entry([2.0, 4.0])
        assert np.array_equal(out["iterations"], [2, 4])
        # registered under the substrate: run_scenario + run_sweep both work
        via_registry = run_scenario("_drain_batch", backend="vec",
                                    starts=[2.0, 4.0])
        assert np.array_equal(via_registry["iterations"], [2, 4])
        res, report = run_sweep("_drain_batch", backend="vec",
                                starts=[3.0, 3.0])
        assert report.n_cells == 2
        # backends=() skips registration
        unregistered = make_batch_entry(
            DRAIN, lambda s, *, use_pallas: Done({}), kind="_drain_none",
            backends=())
        assert "_drain_none" not in _SCENARIOS
    finally:
        _SCENARIOS.pop("_drain_batch", None)
        _SCENARIOS.pop("_drain_none", None)


def test_every_vec_engine_is_a_substrate_definition():
    """The refactor's contract: all five vec scenario kinds are VecEngine
    definitions (one driver, one ops layer — no hand-rolled loops left)."""
    from repro.core.vec_cluster import FLEET_ENGINE
    from repro.core.vec_netdc import NETDC_ENGINE
    from repro.core.vec_power import POWER_ENGINE
    from repro.core.vec_scheduler import CELLS_ENGINE
    from repro.core.vec_workflow import WORKFLOW_ENGINE
    engines = [FLEET_ENGINE, WORKFLOW_ENGINE, POWER_ENGINE, CELLS_ENGINE,
               NETDC_ENGINE]
    assert all(isinstance(e, VecEngine) for e in engines)
    assert sorted(e.kind for e in engines) == [
        "cloudlet_batch", "fleet_batch", "netdc_batch", "power_batch",
        "workflow_batch"]


def test_plain_path_never_loads_pallas():
    """use_pallas=False sweeps import no Pallas module: the kernels load
    only where their route is taken (a fresh process, CPU only)."""
    import os
    import subprocess
    import sys
    code = ("import sys\n"
            "from repro.core.backend import run_sweep\n"
            "run_sweep('power_batch', dict(seeds=[0, 1], n_hosts=4, n_vms=8,"
            " n_samples=8))\n"
            "run_sweep('netdc_batch', dict(seeds=[0, 1], n_dcs=3, n_jobs=8))\n"
            "print(sorted(m for m in sys.modules if 'pallas' in m))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(sys.path))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip().splitlines()[-1] == "[]", proc.stdout
