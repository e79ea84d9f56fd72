"""Geo-distributed LLM serving (``llmserve_batch``) — scenario tests.

Covers the shared model layer (workload feeders, routing tables, the
InterDC ``delay_pairs`` arithmetic, the factored table build against a
plain per-cell construction), the OO broker vs vec engine
bit-exactness contract (drops, outages, batched placements), sweep
routing (chunked/compact schedules, ``ScenarioResult``), and the
serving-metric invariants of :func:`repro.core.llmserve.summarize`.
"""

import math

import jax
import numpy as np
import pytest

from repro.core import f64bits
from repro.core.backend import run_scenario, run_sweep
from repro.core.faults import (FaultEvent, FaultPlan, RetryPolicy,
                               apply_transient)
from repro.core.llmserve import (ACT_BYTES_PER_TOKEN, FIRST_TOKEN_BYTES,
                                 IN_BYTES_PER_TOKEN, OUT_BYTES_PER_TOKEN,
                                 LLMServeCell, build_cells,
                                 default_machines, default_placement,
                                 llmserve_workload, machine_regions)
from repro.core.network import InterDCTopology
from repro.core.sweep import SweepConfig
from repro.core.vec_engine import x64
from repro.core.vec_llmserve import _prepare_llmserve, _rows


def _run(backend="vec", **kw):
    kw.setdefault("seeds", (0, 1))
    kw.setdefault("n_requests", 24)
    return run_scenario("llmserve_batch", backend=backend, **kw)


# -- model layer ---------------------------------------------------------------

def test_delay_pairs_matches_scalar_transfer_delay():
    """delay_pairs is the scalar closed form, vectorized: bit-exact."""
    topo = InterDCTopology(5, link_bw=7e9, hop_latency_s=0.013)
    rng = np.random.default_rng(3)
    src = rng.integers(0, 5, 40)
    dst = rng.integers(0, 5, 40)
    payload = rng.uniform(1e3, 1e9, 40)
    got = topo.delay_pairs(src, dst, payload)
    want = np.array([topo.transfer_delay(int(s), int(t), float(p))
                     for s, t, p in zip(src, dst, payload)])
    assert np.array_equal(got, want)


def test_workload_feeders():
    wl = llmserve_workload(5, 40, 3, mean_gap_s=1.0,
                           offline_frac=0.3, prompt_tokens=(64, 1024),
                           decode_tokens=(16, 512))
    assert (wl["submit"][:12] == 0.0).all()        # offline batch at t=0
    assert not wl["online"][:12].any() and wl["online"][12:].all()
    assert (np.diff(wl["submit"]) >= 0).all()      # nondecreasing stream
    assert wl["src"].max() < 3 and wl["prompt_tok"].min() >= 64


def test_default_placement_is_fastest_first_and_distinct():
    m = default_machines(9)
    pl = default_placement(m["prompt_tls"], 4, 2)
    assert pl.shape == (4, 2)
    assert len(np.unique(pl)) == 8
    # stage 0 of pipeline 0 gets the fastest prefill machine
    assert m["prompt_tls"][pl[0, 0]] == m["prompt_tls"].max()
    with pytest.raises(ValueError, match="cluster has"):
        default_placement(m["prompt_tls"], 5, 2)


def test_build_cells_validation():
    with pytest.raises(ValueError, match="n_requests"):
        build_cells(seeds=(0,), n_requests=0)
    with pytest.raises(ValueError, match="offline_frac"):
        build_cells(seeds=(0,), offline_frac=1.5)
    with pytest.raises(ValueError, match="machine ids"):
        build_cells(seeds=(0,), placement=[[0, 99]])
    with pytest.raises(ValueError, match="distinct"):
        build_cells(seeds=(0,), placement=[[0, 1], [1, 2]])
    with pytest.raises(ValueError, match="offline_region"):
        build_cells(seeds=(0,), offline_region=7)
    with pytest.raises(ValueError, match=r"\[P, S\]"):
        build_cells(seeds=(0,), placement=[0, 1])


def test_cell_tables_shapes_and_eligibility():
    cells, b = build_cells(seeds=(0,), n_machines=6, n_regions=3,
                           n_stages=2, n_requests=10, offline_region=0)
    assert b == 1
    c = cells[0]
    assert isinstance(c, LLMServeCell)
    assert c.svc.shape == c.hop.shape == (10, 3, 2)
    assert c.tail.shape == c.bias.shape == c.eligible.shape == (10, 3)
    # any pipeline touching region 0 is knocked out for every request
    regions = machine_regions(6, 3)
    down = (regions[c.placement] == 0).any(axis=1)
    assert not c.eligible[:, down].any()


# -- the factored build against a plain per-cell construction -----------------

def _plain_cell(seed, pl, machines, regions, topo, *, n_requests, n_regions,
                n_layers, mean_gap_s, locality_weight, offline_region,
                offline_frac, kv_penalty_s, prompt_tokens, decode_tokens,
                fault_plan=None, retry=None, timeout_s=math.inf,
                workload=None):
    """One cell's tables built on their own, every formula written out."""
    wl = (dict(workload) if workload is not None else llmserve_workload(
        seed, n_requests, n_regions, mean_gap_s=mean_gap_s,
        offline_frac=offline_frac, prompt_tokens=prompt_tokens,
        decode_tokens=decode_tokens))
    faulted = fault_plan is not None or math.isfinite(timeout_s)
    plan = fault_plan if fault_plan is not None else FaultPlan()
    fx = {}
    if faulted:
        res = apply_transient(plan, retry, wl["submit"],
                              seed=plan.seed * 1_000_003 + seed)
        perm = np.argsort(res.eff_submit, kind="stable")
        wl = {k: v[perm] for k, v in wl.items()}
        wl["submit"] = res.eff_submit[perm]
        fx = dict(gave_up=res.gave_up[perm], attempts=res.attempts[perm],
                  perm=perm)
    n, (n_pipes, n_stages) = len(wl["submit"]), pl.shape
    p_tok = wl["prompt_tok"].astype(np.float64)
    d_tok = wl["decode_tok"].astype(np.float64)
    layers = float(n_layers) / float(n_stages)
    prompt_svc = (p_tok[:, None, None] * layers
                  / machines["prompt_tls"][pl][None])
    decode_svc = (d_tok[:, None, None] * layers
                  / machines["decode_tls"][pl][None])
    svc = prompt_svc + decode_svc
    m_region = regions[pl]
    ingress_rows = topo.delay_rows(wl["src"], p_tok * IN_BYTES_PER_TOKEN)
    act_bytes = p_tok * ACT_BYTES_PER_TOKEN
    hop = np.zeros((n, n_pipes, n_stages), np.float64)
    hop[:, :, 0] = ingress_rows[:, m_region[:, 0]]
    for s in range(1, n_stages):
        hop[:, :, s] = topo.delay_pairs(m_region[None, :, s - 1],
                                        m_region[None, :, s],
                                        act_bytes[:, None])
    tail = topo.delay_pairs(m_region[None, :, -1], wl["src"][:, None],
                            (d_tok * OUT_BYTES_PER_TOKEN)[:, None])
    first_delay = topo.delay_pairs(m_region[None, :, -1],
                                   wl["src"][:, None], FIRST_TOKEN_BYTES)
    if plan.has("link"):
        wan_f = plan.degrade_factor(wl["submit"], 1)[:, 0]
        hop *= wan_f[:, None, None]
        tail *= wan_f[:, None]
        first_delay *= wan_f[:, None]
    first_extra = prompt_svc[:, :, -1] + first_delay
    wan = hop.sum(axis=2) + tail
    kv_need = wl["prompt_tok"] + wl["decode_tok"]
    pipe_kv = machines["kv_tokens"][pl].min(axis=1)
    bias = ((float(locality_weight) - 1.0) * wan
            + float(kv_penalty_s)
            * (kv_need.astype(np.float64)[:, None]
               / pipe_kv.astype(np.float64)[None, :]))
    pipe_online = np.all(m_region != int(offline_region), axis=1)
    eligible = (kv_need[:, None] <= pipe_kv[None, :]) & pipe_online[None, :]
    if faulted:
        fx["base_eligible"] = eligible
        down = plan.down_mask("node", wl["submit"], len(regions))
        if plan.has("region"):
            down |= plan.down_mask(
                "region", wl["submit"], n_regions)[:, regions]
        pipe_up = ~np.any(down[:, pl], axis=2)
        eligible = eligible & pipe_up & ~fx["gave_up"][:, None]
        tgt, ts, te, _ = plan.select("node")
        windows = list(zip(tgt.tolist(), ts.tolist(), te.tolist()))
        for r, a, z in zip(*[v.tolist() for v in plan.select("region")[:3]]):
            windows += [(int(m), a, z) for m in np.flatnonzero(regions == r)]
        fx.update(windows=tuple(windows), timeout_s=float(timeout_s))
    cell = dict(wl, kv_need=kv_need, svc=svc, hop=hop, tail=tail,
                first_extra=first_extra, wan=wan, bias=bias,
                eligible=eligible, placement=pl)
    row = np.concatenate(
        [wl["submit"][:, None], svc.reshape(n, -1), hop.reshape(n, -1),
         tail, first_extra, bias, eligible, kv_need[:, None]], axis=1)
    return cell, fx, row.astype(np.float64)


def _placements(seed, n, n_machines, n_pipes, n_stages):
    rng = np.random.default_rng(seed)
    return np.stack([rng.permutation(n_machines)[:n_pipes * n_stages]
                     .reshape(n_pipes, n_stages) for _ in range(n)])


_PLAN = FaultPlan([FaultEvent("node", 1.0, 6.0, target=2),
                   FaultEvent("region", 3.0, 9.0, target=1),
                   FaultEvent("link", 2.0, 12.0, severity=3.0),
                   FaultEvent("transient", 0.0, 20.0, severity=0.3)], seed=7)
_FAULTS = dict(fault_plan=_PLAN, timeout_s=40.0,
               retry=RetryPolicy(max_retries=2, base_delay_s=0.2,
                                 jitter_frac=0.5))
_WORKLOAD = dict(submit=np.sort(np.random.default_rng(5).uniform(0, 25, 30)),
                 src=np.random.default_rng(6).integers(0, 3, 30, np.int32),
                 prompt_tok=np.random.default_rng(7).integers(1, 900, 30),
                 decode_tok=np.random.default_rng(8).integers(1, 90_000, 30),
                 online=np.arange(30) >= 5)
# case: (build_cells arguments, distinct request streams)
EQUIV = {
    "shared_seeds_gap_axis": (dict(
        seeds=np.tile([3, 9], 6), mean_gap_s=np.repeat([0.4, 1.5], 6),
        n_machines=8, placement=_placements(1, 12, 8, 4, 2)), 4),
    "distinct_seeds_locality_offline": (dict(
        seeds=np.arange(6) * 7919, n_machines=6,
        locality_weight=[0.5, 1.0, 2.0, 3.5, 1.0, 0.25],
        offline_region=[-1, 0, 1, 2, -1, 1]), 6),
    "one_stage": (dict(seeds=(0, 0, 1), n_machines=5, n_stages=1), 2),
    "three_stages_batched": (dict(
        seeds=np.tile([1, 2], 4), n_machines=10, n_stages=3,
        locality_weight=np.repeat([1.0, 2.0], 4),
        placement=_placements(2, 8, 10, 3, 3)), 2),
    "injected_workload": (dict(
        seeds=np.arange(4), mean_gap_s=[0.5, 1.0, 0.5, 2.0], n_machines=8,
        workload=_WORKLOAD, placement=_placements(3, 4, 8, 4, 2)), 1),
    "faulted": (dict(
        seeds=[4, 4, 5, 4, 6, 5], n_machines=9, n_stages=3,
        offline_region=[-1, -1, 2, -1, -1, 0],
        locality_weight=[1.0, 1.5, 1.0, 1.0, 0.5, 1.0],
        placement=_placements(4, 6, 9, 3, 3), **_FAULTS), 3),
    "faulted_injected_workload": (dict(
        seeds=[4, 4, 5], n_machines=8, workload=_WORKLOAD, **_FAULTS), 2),
}


def _same(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape and \
        a.tobytes() == b.tobytes()


def _gathered_rows(kw, exact_bits: bool, monkeypatch) -> np.ndarray:
    """The packed ``[B, J, K]`` rows the lanes' loops read on one route:
    the plan's lane params and shared request tables, gathered on the
    device as the engine gathers them."""
    monkeypatch.setattr(f64bits, "native", lambda: not exact_bits)
    plan = _prepare_llmserve(use_pallas=False, **kw)
    assert plan.statics.f64_bits == exact_bits
    with x64():
        return np.asarray(jax.jit(jax.vmap(_rows, in_axes=(0, None)))(
            plan.params, plan.shared))


@pytest.mark.parametrize("case", sorted(EQUIV))
def test_factored_build_matches_per_cell_construction(case, monkeypatch):
    """Every packed row a lane gathers on the device and every
    ``LLMServeCell`` field equals a plain construction of that cell alone,
    byte for byte, on both routes; the batch draws one request stream per
    distinct input."""
    kw, n_streams = EQUIV[case]
    kw = dict(n_requests=30, decode_tokens=(16, 90_000), **kw)
    batch, b = build_cells(**kw)
    assert len(batch.table) == n_streams
    machines = default_machines(kw["n_machines"])
    n_stages = kw.get("n_stages", 2)
    regions = machine_regions(kw["n_machines"], 3)
    pls = kw.get("placement", default_placement(
        machines["prompt_tls"], kw["n_machines"] // n_stages, n_stages))
    pls = np.broadcast_to(pls, (b,) + np.shape(pls)[-2:])
    axis = {k: np.broadcast_to(kw.get(k, d), (b,))
            for k, d in (("seeds", 0), ("mean_gap_s", 1.0),
                         ("locality_weight", 1.0), ("offline_region", -1))}
    topo = InterDCTopology(3, link_bw=10e9, hop_latency_s=0.03)
    rows = []
    for i in range(b):
        want, fx, row = _plain_cell(
            int(axis["seeds"][i]), np.asarray(pls[i], np.int64), machines,
            regions, topo, n_requests=30, n_regions=3, n_layers=32,
            mean_gap_s=float(axis["mean_gap_s"][i]),
            locality_weight=float(axis["locality_weight"][i]),
            offline_region=int(axis["offline_region"][i]),
            offline_frac=0.25, kv_penalty_s=0.5, prompt_tokens=(64, 1024),
            decode_tokens=(16, 90_000), fault_plan=kw.get("fault_plan"),
            retry=kw.get("retry"), timeout_s=kw.get("timeout_s", math.inf),
            workload=kw.get("workload"))
        rows.append(row)
        got = batch[i]
        assert isinstance(got, LLMServeCell)
        for k, v in want.items():
            assert _same(getattr(got, k), v), (i, k)
        assert (got.fx is None) == (not fx)
        assert got.fx is None or set(got.fx._fields) == set(fx)
        for k, v in fx.items():
            assert _same(getattr(got.fx, k), v), (i, k)
    packed = np.stack(rows)
    assert _same(_gathered_rows(kw, False, monkeypatch), packed)
    bits = _gathered_rows(kw, True, monkeypatch)
    want_bits = f64bits.bits(packed.copy())
    want_bits[..., -1] = packed[..., -1].astype(np.int64)
    assert bits.dtype == np.int64 and _same(bits, want_bits)
    assert np.array_equal(bits[..., -1],
                          np.stack([batch[i].kv_need for i in range(b)]))


# -- backend agreement ---------------------------------------------------------

CFG = dict(seeds=(0, 1, 2), n_requests=32, n_machines=9, n_regions=3,
           n_stages=3, mean_gap_s=(0.3, 1.0, 3.0),
           decode_tokens=(16, 90_000))            # straddles KV → drops


def _assert_all_equal(a, b, what):
    for k in set(a) & set(b):
        assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), \
            f"{what}: {k} differs"


def test_three_backends_bit_exact():
    oo = _run("oo", **CFG)
    vec = _run("vec", **CFG)
    legacy = _run("legacy", **CFG)
    _assert_all_equal(oo, vec, "oo vs vec")
    _assert_all_equal(oo, legacy, "oo vs legacy")
    assert (oo["served"] + oo["dropped"] == CFG["n_requests"]).all()
    assert oo["dropped"].sum() > 0 and oo["served"].sum() > 0


def test_dropped_requests_marked_consistently():
    out = _run("vec", **CFG)
    dropped = out["dst"] < 0
    assert np.isinf(out["finish"][dropped]).all()
    assert np.isinf(out["ttft"][dropped]).all()
    assert np.isfinite(out["finish"][~dropped]).all()
    assert (out["ttft"][~dropped] <= out["finish"][~dropped]).all()


def test_batched_placements_one_layout_per_cell():
    rng = np.random.default_rng(0)
    pls = np.stack([rng.permutation(8)[:6].reshape(2, 3).T
                    for _ in range(5)])            # [5, 3, 2]
    oo = _run("oo", seeds=np.zeros(5, np.int64), n_machines=8,
              placement=pls)
    vec = _run("vec", seeds=np.zeros(5, np.int64), n_machines=8,
               placement=pls)
    _assert_all_equal(oo, vec, "batched placement")
    # layouts genuinely differ → at least two distinct makespans
    assert len(np.unique(oo["makespan"])) > 1


def test_use_pallas_force_is_bit_identical():
    base = _run("vec", seeds=(4, 5))
    forced = _run("vec", seeds=(4, 5), use_pallas="force")
    _assert_all_equal(base, forced, "pallas vs jnp")


# -- sweep routing -------------------------------------------------------------

def test_chunked_and_compact_bit_identical():
    params = dict(seeds=np.arange(6), n_requests=20,
                  mean_gap_s=np.tile([0.5, 2.0], 3))
    mono = _run("vec", **params)
    chunked, rep = run_sweep("llmserve_batch", params,
                             config=SweepConfig(chunk_size=2))
    assert rep.n_chunks == 3
    _assert_all_equal(mono, chunked, "chunked")
    compact, rep2 = run_sweep(
        "llmserve_batch", params,
        config=SweepConfig(compact=True, chunk_size=2, segment_iters=6))
    assert rep2.compacted and rep2.refills == 4
    # equal-length lanes: the compacting scheduler wastes nothing
    assert rep2.active_lane_fraction_observed == 1.0
    _assert_all_equal(mono, compact, "compact")


def test_run_sweep_scenario_result_both_backends():
    for backend in ("vec", "oo"):
        res = run_sweep("llmserve_batch",
                        dict(seeds=(0, 1), n_requests=12), backend=backend)
        assert res.kind == "llmserve_batch" and res.backend == backend
        assert res.report.n_cells == 2
        assert res.summary()["served"] >= 0
        assert "observed_active_lane_fraction" in res.report_fields()


def test_empty_batch_short_circuits():
    out, rep = run_sweep("llmserve_batch", dict(seeds=[]))
    assert rep.n_cells == 0 and out["dst"].shape[0] == 0
    oo = _run("oo", seeds=[])
    assert set(out) - {"iterations"} == set(oo)


# -- summary invariants --------------------------------------------------------

def test_summary_invariants():
    out = _run("vec", **CFG)
    served_m = out["dst"] >= 0
    assert np.array_equal(out["pipe_requests"].sum(axis=1), out["served"])
    # every served request's context is committed once per pipeline stage
    cells, _ = build_cells(**CFG)
    for i, c in enumerate(cells):
        kv_expect = c.kv_need[served_m[i]].sum() * c.placement.shape[1]
        assert out["kv_assigned_tokens"][i].sum() == kv_expect
        assert out["kv_used"][i].sum() == \
            c.kv_need[served_m[i]].sum() * c.placement.shape[1]
    assert (out["utilization"] >= 0).all() and (out["utilization"] <= 1).all()
    assert (out["tokens_out"] <= CFG["n_requests"] * 90_000).all()
    busiest = out["machine_busy_s"][np.arange(3), out["busiest_machine"]]
    assert (busiest == out["machine_busy_s"].max(axis=1)).all()


def test_outage_reroutes_or_drops():
    """Taking a region offline must never leave requests routed through it."""
    out = _run("vec", seeds=(0,), n_machines=6, n_regions=3,
               offline_region=1, n_requests=20)
    cells, _ = build_cells(seeds=(0,), n_machines=6, n_regions=3,
                           offline_region=1, n_requests=20)
    regions = machine_regions(6, 3)
    c = cells[0]
    for j, p in enumerate(out["dst"][0]):
        if p >= 0:
            assert (regions[c.placement[p]] != 1).all()
