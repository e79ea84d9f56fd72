"""Cross-backend differential suite — random configs, oo vs vec, one harness.

Every batched scenario kind the substrate registers on *both* the ``oo``
and ``vec`` backends (``fleet_batch``, ``workflow_batch``,
``cloudlet_batch``, ``consolidation_batch``, ``power_batch``,
``netdc_batch``, ``llmserve_batch``) runs here through one generic
harness: a seeded generator draws a random scenario
config, both backends run it, and a per-kind comparator asserts the
agreement contract — **bit-exact** for deterministic scenarios
(fleet-deterministic, power) and **ε-close** where the engines share the
stochastic sample but not every float op (workflow streams, cloudlet
time-sharing, consolidation decisions at 1e-12).

The deterministic parametrization below always runs; when ``hypothesis``
is installed the same checks also run property-style over drawn seeds
(``test_differential_hypothesis``), so CI fuzzes fresh configs every run
while a hypothesis-less machine still covers every kind.

A vec engine that drifts from its OO reference — a changed decision, a
reordered float reduction, a lost output key — fails here first.
"""
import numpy as np
import pytest

from repro.core.backend import run_scenario

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


# -- comparators ---------------------------------------------------------------

def _assert_exact(oo, vec, keys=None):
    keys = keys if keys is not None else sorted(set(oo) & set(vec))
    assert keys, "no comparable output keys"
    for k in keys:
        a, b = np.asarray(oo[k]), np.asarray(vec[k])
        assert a.shape == b.shape, f"{k}: shape {a.shape} vs {b.shape}"
        assert np.array_equal(a, b), f"{k}: oo/vec outputs differ"


def _assert_close(a, b, key, rtol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.array_equal(np.isfinite(a), np.isfinite(b)), \
        f"{key}: finite-mask differs"
    m = np.isfinite(a)
    assert np.allclose(a[m], b[m], rtol=rtol), f"{key}: beyond rtol={rtol}"


# -- per-kind cases ------------------------------------------------------------
# Shapes stay fixed per kind (one vec compile across trials); the rng only
# varies traced parameters, seeds, and topology within those shapes.

def _gen_fleet(rng):
    """Deterministic fleet configs (σ=0, no failures): bit-exact contract."""
    from repro.core.cluster import FleetConfig, StepCost
    cost = StepCost(compute_s=float(rng.uniform(0.5, 2.0)),
                    memory_s=float(rng.uniform(0.2, 1.0)),
                    collective_s=float(rng.uniform(0.1, 0.8)),
                    overlap_collective=float(rng.uniform(0.0, 0.9)))
    cfg = FleetConfig(n_nodes=8, n_spares=2, straggler_sigma=0.0,
                      mtbf_hours_node=1e9, degrade_mtbf_hours=1e9,
                      straggler_evict_factor=1e9)
    return dict(cost=cost, cfg=cfg,
                total_steps=int(rng.integers(40, 90)),
                seeds=np.arange(4),
                ckpt_every=rng.integers(5, 30, 4))


def _run_fleet(backend, params):
    return run_scenario("fleet_batch", backend=backend, **params)


def _cmp_fleet(oo, vec):
    _assert_exact(oo, vec, keys=["wallclock_s", "steps_done", "failures",
                                 "restarts", "evictions", "lost_steps",
                                 "stall_s", "ckpt_s", "ideal_s", "goodput"])


def _gen_workflow(rng):
    """Random 5-node DAGs on 3 guests with a Poisson activation stream."""
    n = 5
    edges = [(i, j) for i in range(n) for j in range(i + 1, n)
             if rng.random() < 0.4]
    return dict(nodes=[float(rng.integers(500, 4000)) for _ in range(n)],
                edges=edges,
                guest_of=[int(rng.integers(0, 3)) for _ in range(n)],
                guest_mips=[1000.0, 1500.0, 800.0],
                payload=float(rng.uniform(0.0, 2e6)),
                activations=2, seed=int(rng.integers(0, 1000)),
                arrival_rate=0.5)


def _run_workflow(backend, params):
    return run_scenario("workflow_batch", backend=backend, **params)


def _cmp_workflow(oo, vec):
    # Streams share the arrival sample but not every float op: ε contract
    # (single-activation chains are bit-exact — covered in test_vec_workflow).
    _assert_close(oo["finish"], vec["finish"], "finish", rtol=1e-9)
    _assert_close(oo["makespans"], vec["makespans"], "makespans", rtol=1e-9)
    _assert_exact(oo, vec, keys=["missed_deadline"])


def _gen_cloudlet(rng):
    B, G, C = 4, 3, 4
    return dict(
        length=(rng.uniform(100, 4000, (B, G, C))
                * (rng.random((B, G, C)) < 0.8)),
        pes=rng.integers(1, 3, (B, G, C)).astype(float),
        submit=np.round(rng.uniform(0, 10, (B, G, C)), 3),
        guest_mips=rng.uniform(500, 1500, (B, G)),
        guest_pes=np.full((B, G), 2.0),
        mode=("time", "space")[int(rng.integers(0, 2))])


def _run_cloudlet(backend, params):
    return dict(finish=run_scenario("cloudlet_batch", backend=backend,
                                    **params))


def _cmp_cloudlet(oo, vec):
    _assert_close(oo["finish"], vec["finish"], "finish", rtol=1e-12)


def _gen_consolidation(rng):
    from repro.core.power import ALGORITHMS
    return dict(algos=tuple(rng.choice(ALGORITHMS, 2)),
                seeds=tuple(int(s) for s in rng.integers(0, 100, 2)),
                n_hosts=8, n_vms=16, n_samples=int(rng.integers(8, 16)))


def _run_consolidation(backend, params):
    res = run_scenario("consolidation_batch", backend=backend, **params)
    return dict(migrations=[r.migrations for r in res],
                energy_kwh=[r.energy_kwh for r in res],
                final_active_hosts=[r.final_active_hosts for r in res])


def _cmp_consolidation(oo, vec):
    # Decisions must match exactly; energy to 1e-12 (the vec manager's SoA
    # utilization sweep reproduces the OO doubles — see consolidation_sim).
    _assert_exact(oo, vec, keys=["migrations", "final_active_hosts"])
    _assert_close(oo["energy_kwh"], vec["energy_kwh"], "energy_kwh",
                  rtol=1e-12)


def _gen_netdc(rng):
    return dict(seeds=rng.integers(0, 1000, 3),
                n_dcs=int(rng.integers(2, 6)),
                n_jobs=int(rng.integers(8, 40)),
                locality_weight=float(rng.uniform(0.5, 4.0)),
                offline_dc=int(rng.integers(-1, 2)),
                hop_latency_s=float(rng.uniform(0.0, 0.1)),
                mean_gap_s=float(rng.uniform(0.5, 4.0)))


def _run_netdc(backend, params):
    return run_scenario("netdc_batch", backend=backend, **params)


def _cmp_netdc(oo, vec):
    # Every output, bit-exact — and the key sets must actually match
    # (modulo the vec loop's iteration counter), so a dropped/renamed
    # output can't silently shrink the comparison.
    assert set(vec) - {"iterations"} == set(oo), sorted(set(vec) ^ set(oo))
    _assert_exact(oo, vec, keys=sorted(oo))


def _gen_llmserve(rng):
    n_stages = int(rng.integers(1, 4))
    n_machines = int(rng.integers(n_stages, 4 * n_stages + 1))
    seeds = rng.integers(0, 1000, 3)
    n_regions = int(rng.integers(1, 5))
    return dict(seeds=seeds,
                n_machines=n_machines, n_regions=n_regions,
                n_stages=n_stages, n_requests=int(rng.integers(8, 40)),
                mean_gap_s=float(rng.uniform(0.1, 3.0)),
                locality_weight=float(rng.uniform(0.5, 4.0)),
                # an outage only of a region that exists
                offline_region=min(int(rng.integers(-1, 2)), n_regions - 1),
                offline_frac=float(rng.uniform(0.0, 1.0)),
                kv_penalty_s=float(rng.uniform(0.0, 2.0)),
                # straddle the pipeline KV capacities so drops occur
                decode_tokens=(16, int(rng.integers(512, 200_000))))


def _run_llmserve(backend, params):
    return run_scenario("llmserve_batch", backend=backend, **params)


def _cmp_llmserve(oo, vec):
    # Every output, bit-exact (same key-set contract as netdc): the
    # decision arithmetic is shared f64 tables + adds/max/compares.
    assert set(vec) - {"iterations"} == set(oo), sorted(set(vec) ^ set(oo))
    _assert_exact(oo, vec, keys=sorted(oo))


def _gen_storage(rng):
    n_nodes = int(rng.integers(2, 6))
    n_replicas = int(rng.integers(1, n_nodes + 1))
    return dict(seeds=rng.integers(0, 1000, 3),
                n_nodes=n_nodes,
                n_objects=int(rng.integers(8, 40)),
                n_replicas=n_replicas,
                quorum=int(rng.integers(1, n_replicas + 1)),
                placement_weight=float(rng.uniform(0.5, 4.0)),
                offline_node=(int(rng.integers(-1, 2))
                              if n_replicas < n_nodes else -1),
                hop_latency_s=float(rng.uniform(0.0, 0.1)),
                mean_gap_s=float(rng.uniform(0.5, 4.0)))


def _run_storage(backend, params):
    return run_scenario("storage_batch", backend=backend, **params)


def _cmp_storage(oo, vec):
    # Every output, bit-exact (same key-set contract as netdc): the
    # placement arithmetic is shared f64 tables + adds/max/min/compares.
    assert set(vec) - {"iterations"} == set(oo), sorted(set(vec) ^ set(oo))
    _assert_exact(oo, vec, keys=sorted(oo))


def _gen_power(rng):
    lo = float(rng.uniform(0.1, 0.4))
    return dict(seeds=rng.integers(0, 1000, 3),
                n_hosts=8, n_vms=int(rng.integers(8, 48)),
                n_samples=int(rng.integers(16, 48)),
                up_thr=float(rng.uniform(0.6, 0.95)), lo_thr=lo,
                cooldown=int(rng.integers(0, 6)),
                init_active=int(rng.integers(1, 9)),
                model_mix=("mixed", "linear", "cubic", "spec", "dvfs")[
                    int(rng.integers(0, 5))])


def _run_power(backend, params):
    return run_scenario("power_batch", backend=backend, **params)


def _cmp_power(oo, vec):
    _assert_exact(oo, vec)       # every output, bit-exact — the contract


CASES = {
    "fleet_batch": (_gen_fleet, _run_fleet, _cmp_fleet),
    "workflow_batch": (_gen_workflow, _run_workflow, _cmp_workflow),
    "cloudlet_batch": (_gen_cloudlet, _run_cloudlet, _cmp_cloudlet),
    "consolidation_batch": (_gen_consolidation, _run_consolidation,
                            _cmp_consolidation),
    "power_batch": (_gen_power, _run_power, _cmp_power),
    "netdc_batch": (_gen_netdc, _run_netdc, _cmp_netdc),
    "llmserve_batch": (_gen_llmserve, _run_llmserve, _cmp_llmserve),
    "storage_batch": (_gen_storage, _run_storage, _cmp_storage),
}


def _check(kind, seed):
    gen, run, cmp = CASES[kind]
    params = gen(np.random.default_rng(seed))
    cmp(run("oo", params), run("vec", params))


# The batched vec kinds that route through run_plan also run under the
# compacting lane scheduler; consolidation_batch is a host loop (the
# compact control does not apply there).
COMPACT_KINDS = ("fleet_batch", "workflow_batch", "cloudlet_batch",
                 "power_batch", "netdc_batch", "llmserve_batch",
                 "storage_batch")


def _check_compact(kind, seed):
    """Compaction is a schedule: vec+compact must be **bit-identical** to
    the monolithic vec dispatch on every kind — including the ε-contract
    kinds, where the engine is the same and only the schedule changes."""
    gen, run, _ = CASES[kind]
    params = gen(np.random.default_rng(seed))
    mono = run("vec", params)
    compact = run("vec", dict(params, compact=True, chunk_size=3,
                              segment_iters=5))
    keys = sorted(set(mono) & set(compact))
    assert keys
    for k in keys:
        a, b = np.asarray(mono[k]), np.asarray(compact[k])
        assert a.shape == b.shape, f"{k}: shape {a.shape} vs {b.shape}"
        assert np.array_equal(a, b), \
            f"{k}: compacting schedule changed bits vs monolithic"


# -- always-on deterministic parametrization -----------------------------------

@pytest.mark.parametrize("trial", range(3))
@pytest.mark.parametrize("kind", sorted(CASES))
def test_differential(kind, trial):
    _check(kind, 7919 * trial + sum(map(ord, kind)))


@pytest.mark.parametrize("trial", range(2))
@pytest.mark.parametrize("kind", COMPACT_KINDS)
def test_differential_compact(kind, trial):
    _check_compact(kind, 7919 * trial + sum(map(ord, kind)))


def test_covers_every_dual_backend_batched_kind():
    """The suite must grow with the registry: any batched kind registered
    on both oo and vec without a differential case fails here."""
    from repro.core.backend import _SCENARIOS, _load_scenarios
    _load_scenarios()
    dual = {k for k, table in _SCENARIOS.items()
            if k.endswith("_batch") and {"oo", "vec"} <= set(table)}
    assert dual == set(CASES), \
        f"differential coverage out of sync with registry: {dual ^ set(CASES)}"


# -- faulted cells: same contracts under an injected FaultPlan -----------------
# Extra parametrizations on top of CASES (the registry-sync guard above
# compares against CASES alone).  Each generator reuses its clean
# counterpart and layers a seeded fault schedule within the scenario's
# documented bit-exactness domain.

def _gen_netdc_faulted(rng):
    from repro.core.faults import RetryPolicy, make_chaos_plan
    params = _gen_netdc(rng)
    t_max = params["n_jobs"] * params["mean_gap_s"]
    plan = make_chaos_plan(int(rng.integers(0, 1000)), t_max,
                           n_targets=params["n_dcs"],
                           n_node_windows=2, n_link_windows=1,
                           transient_prob=float(rng.uniform(0.1, 0.5)))
    return dict(params, fault_plan=plan, timeout_s=float(t_max * 4),
                retry=RetryPolicy(max_retries=2, base_delay_s=0.25,
                                  backoff=2.0, jitter_frac=0.25,
                                  budget_s=t_max))


def _gen_llmserve_faulted(rng):
    from repro.core.faults import RetryPolicy, make_chaos_plan
    params = _gen_llmserve(rng)
    params["n_regions"] = int(rng.integers(2, 5))   # region outages need >1
    t_max = params["n_requests"] * params["mean_gap_s"]
    plan = make_chaos_plan(int(rng.integers(0, 1000)), t_max,
                           n_targets=params["n_machines"],
                           n_regions=params["n_regions"],
                           n_node_windows=2, n_link_windows=1,
                           n_region_windows=1,
                           transient_prob=float(rng.uniform(0.1, 0.5)))
    return dict(params, fault_plan=plan, timeout_s=float(t_max * 4),
                retry=RetryPolicy(max_retries=2, base_delay_s=0.25,
                                  backoff=1.5, jitter_frac=0.1,
                                  budget_s=t_max))


def _gen_power_faulted(rng):
    # Host-crash windows only (power's fault surface); single-target
    # windows over 8 hosts can never fail the whole datacenter at once.
    from repro.core.faults import make_chaos_plan
    params = _gen_power(rng)
    plan = make_chaos_plan(int(rng.integers(0, 1000)),
                           params["n_samples"] * 300.0,
                           n_targets=params["n_hosts"],
                           n_node_windows=3, n_link_windows=0,
                           transient_prob=0.0)
    return dict(params, fault_plan=plan)


def _gen_fleet_faulted(rng):
    """Planned outages inside the deterministic bit-exact domain: no
    spares, explicit targets, finite non-overlapping windows longer than
    ``restart_s`` and separated by more than it."""
    from repro.core.cluster import FleetConfig
    from repro.core.faults import FaultEvent, FaultPlan
    params = _gen_fleet(rng)
    cfg = FleetConfig(n_nodes=8, n_spares=0, straggler_sigma=0.0,
                      mtbf_hours_node=1e9, degrade_mtbf_hours=1e9,
                      straggler_evict_factor=1e9, restart_s=5.0)
    nodes = rng.choice(cfg.n_nodes, 2, replace=False)
    t = float(rng.uniform(5.0, 30.0))
    events = []
    for nid in nodes:
        dur = float(rng.uniform(3.0, 8.0)) * cfg.restart_s
        events.append(FaultEvent("node", t, t + dur, target=int(nid)))
        t += dur + cfg.restart_s * float(rng.uniform(1.5, 3.0))
    return dict(params, cfg=cfg, fault_plan=FaultPlan(events))


def _gen_storage_faulted(rng):
    """Chaos over the replica store: node windows sized to land mid-
    transfer (kills + re-sourcing), WAN degradation, flaky PUTs."""
    from repro.core.faults import RetryPolicy, make_chaos_plan
    params = _gen_storage(rng)
    t_max = params["n_objects"] * params["mean_gap_s"]
    plan = make_chaos_plan(int(rng.integers(0, 1000)), t_max,
                           n_targets=params["n_nodes"],
                           n_node_windows=3, n_link_windows=1,
                           transient_prob=float(rng.uniform(0.1, 0.5)))
    return dict(params, fault_plan=plan, timeout_s=float(t_max * 4),
                retry=RetryPolicy(max_retries=2, base_delay_s=0.25,
                                  backoff=2.0, jitter_frac=0.25,
                                  budget_s=t_max))


FAULTED_CASES = {
    "fleet_batch": (_gen_fleet_faulted, _run_fleet, _cmp_fleet),
    "power_batch": (_gen_power_faulted, _run_power, _cmp_power),
    "netdc_batch": (_gen_netdc_faulted, _run_netdc, _cmp_netdc),
    "llmserve_batch": (_gen_llmserve_faulted, _run_llmserve, _cmp_llmserve),
    "storage_batch": (_gen_storage_faulted, _run_storage, _cmp_storage),
}


@pytest.mark.parametrize("trial", range(2))
@pytest.mark.parametrize("kind", sorted(FAULTED_CASES))
def test_differential_faulted(kind, trial):
    gen, run, cmp = FAULTED_CASES[kind]
    params = gen(np.random.default_rng(7919 * trial + sum(map(ord, kind))))
    cmp(run("oo", params), run("vec", params))


@pytest.mark.parametrize("kind", sorted(FAULTED_CASES))
def test_differential_faulted_compact(kind):
    """Compaction stays a pure schedule under fault injection too."""
    gen, run, _ = FAULTED_CASES[kind]
    params = gen(np.random.default_rng(sum(map(ord, kind))))
    mono = run("vec", params)
    compact = run("vec", dict(params, compact=True, chunk_size=2,
                              segment_iters=5))
    for k in sorted(set(mono) & set(compact)):
        assert np.array_equal(np.asarray(mono[k]), np.asarray(compact[k])), \
            f"{k}: compacting schedule changed bits under faults"


# -- hypothesis-driven property layer ------------------------------------------

if HAVE_HYPOTHESIS:
    @given(seed=st.integers(0, 2 ** 31 - 1))
    @settings(max_examples=3, deadline=None)
    @pytest.mark.parametrize("kind", sorted(CASES))
    def test_differential_hypothesis(kind, seed):
        _check(kind, seed)


# -- llmserve's exact bit-pattern route (backends that emulate f64) ------------

@pytest.mark.parametrize("trial", range(3))
@pytest.mark.parametrize("gen", [_gen_llmserve, _gen_llmserve_faulted],
                         ids=["clean", "faulted"])
def test_llmserve_f64_bits_route(monkeypatch, gen, trial):
    """The route the TPU takes — doubles as int64 bit patterns through
    ``f64bits`` — forced here on the CPU: still bit-exact vs ``oo``,
    monolithic and compacting."""
    from repro.core import f64bits
    params = gen(np.random.default_rng(104729 * trial + 11))
    oo = _run_llmserve("oo", params)
    monkeypatch.setattr(f64bits, "native", lambda: False)
    _cmp_llmserve(oo, _run_llmserve("vec", params))
    compact = _run_llmserve("vec", dict(params, compact=True, chunk_size=2,
                                        segment_iters=5))
    _cmp_llmserve(oo, compact)
