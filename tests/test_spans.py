"""The sweep path's host spans and counters in the profiler's trace: every
span nested in one ``sweep`` span per ``run_sweep`` call, the sweep's
counters on it, and nothing changed with the profiler off."""
import glob
import os
import re

import jax
import numpy as np
import pytest

from bench import programspans
from repro.core import backend, spans, vec_engine
from repro.core.backend import run_sweep
from repro.core.sweep import SweepConfig
from repro.core.vec_llmserve import LLMSERVE_ENGINE, _prepare_llmserve
from repro.core.vec_power import POWER_ENGINE, _prepare_power

KIND = "llmserve_batch"
PARAMS = dict(seeds=np.arange(8), n_requests=40)
POWER = dict(seeds=np.arange(8), n_hosts=6, n_vms=20, n_samples=24,
             up_thr=np.linspace(0.5, 0.9, 8))
CONFIGS = {"compact": SweepConfig(compact=True, chunk_size=4,
                                  segment_iters=16),
           "chunked": SweepConfig(chunk_size=4)}
NAMES = {"sweep", "sweep.validate", "sweep.prepare", "sweep.stage",
         "sweep.dispatch", "sweep.wait", "sweep.finalize"}
# The engine's own host build under ``sweep.prepare``: llmserve's request
# tables go to the device as they are built; power packs its lane params.
LLMSERVE_PREPARE = ("sweep.prepare.build",)
POWER_PREPARE = ("sweep.prepare.build", "sweep.prepare.pack")


def _traced(directory, config, kind=KIND, params=PARAMS):
    run_sweep(kind, params, config=config)          # compiles untraced
    with jax.profiler.trace(str(directory)):
        res = run_sweep(kind, params, config=config)
    path = max(glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                         recursive=True))
    return res, programspans.load_program(path)


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def traced(request, tmp_path_factory):
    res, found = _traced(tmp_path_factory.mktemp(request.param),
                         CONFIGS[request.param])
    return request.param, res, found


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def traced_power(request, tmp_path_factory):
    res, found = _traced(tmp_path_factory.mktemp("power-" + request.param),
                         CONFIGS[request.param], "power_batch", POWER)
    return request.param, res, found


def _assert_nested(path, found, prepare):
    sweeps = [p for p in found if p[0] == "sweep"]
    assert len(sweeps) == 1
    _, lo, hi, _ = sweeps[0]
    assert all(lo <= s and e <= hi for _, s, e, _ in found)
    want = NAMES | set(prepare) | (
        {"sweep.retire"} if path == "compact" else set())
    assert {p[0] for p in found} == want
    (_, plo, phi, _), = [p for p in found if p[0] == "sweep.prepare"]
    for child in prepare:
        (_, s, e, _), = [p for p in found if p[0] == child]
        assert plo <= s and e <= phi


def test_every_span_nests_under_one_sweep(traced):
    path, _, found = traced
    _assert_nested(path, found, LLMSERVE_PREPARE)


def test_power_sweep_spans_nest_under_one_sweep(traced_power):
    path, _, found = traced_power
    _assert_nested(path, found, POWER_PREPARE)


def test_sweep_span_carries_the_report(traced):
    path, res, found = traced
    (_, _, _, stats), = [p for p in found if p[0] == "sweep"]
    rep = res.report
    assert stats["dispatches"] == (rep.segments if path == "compact"
                                   else rep.n_chunks)
    assert stats["dispatches"] == rep.dispatches > 1
    assert stats["id"] >= 1
    for k, v in rep.report_fields().items():
        if v is None:
            assert k not in stats
        elif not isinstance(v, str):
            assert stats[k] == v, k
    n_dispatch = sum(p[0] == "sweep.dispatch" for p in found)
    assert n_dispatch == rep.dispatches
    assert sum(p[0] == "sweep.wait" for p in found) >= n_dispatch
    # No span per cell or per lane: a few per dispatch and a fixed few more.
    assert len(found) <= 3 * rep.dispatches + 8


def _h2d_from_shapes(engine, plan, path, rep, lanes=4):
    """The bytes a sweep of ``plan`` on ``lanes`` lanes hands the device, as
    the shapes of its lane params and loop state give them, and its shared
    operand's, once a sweep."""
    lane_params = sum(lanes * leaf[0].nbytes
                      for leaf in jax.tree_util.tree_leaves(plan.params))
    shared = sum(leaf.nbytes
                 for leaf in jax.tree_util.tree_leaves(plan.shared))
    assert rep.shared_bytes == shared
    if path == "compact":
        with vec_engine.x64():
            proto = vec_engine.state_prototype(engine, plan.statics,
                                               plan.params, plan.shared)
        state = sum(lanes * int(np.prod(sd.shape)) * sd.dtype.itemsize
                    for sd in jax.tree_util.tree_leaves(proto))
        # The lane params go to the device once, and again for each segment
        # after a refill (8 cells on 4 lanes do refill); every segment sends
        # the fresh mask; the first also the zeroed state and iteration
        # counters, which stay on the device after.
        assert rep.refills > 0
        assert 2 <= rep.param_uploads <= rep.segments
        want = (rep.param_uploads * lane_params + rep.segments * lanes
                + state + 4 * lanes)
    else:
        assert rep.param_uploads == rep.n_chunks
        want = rep.n_chunks * lane_params
    return want + shared


def test_h2d_bytes_from_shapes(traced):
    path, res, found = traced
    plan = _prepare_llmserve(use_pallas=False, **PARAMS)
    rep = res.report
    want = _h2d_from_shapes(LLMSERVE_ENGINE, plan, path, rep)
    assert rep.h2d_bytes == want
    (_, _, _, stats), = [p for p in found if p[0] == "sweep"]
    assert stats["h2d_bytes"] == want
    assert stats["shared_bytes"] == rep.shared_bytes == plan.shared.nbytes


def test_power_sweep_counters_from_shapes(traced_power):
    """Power's lane params are a pytree of per-cell arrays (trace, host
    capacity and efficiency, thresholds): every leaf is counted."""
    path, res, found = traced_power
    plan = _prepare_power(use_pallas=False, **POWER)
    rep = res.report
    want = _h2d_from_shapes(POWER_ENGINE, plan, path, rep)
    assert rep.h2d_bytes == want
    (_, _, _, stats), = [p for p in found if p[0] == "sweep"]
    assert stats["h2d_bytes"] == want
    assert stats["param_uploads"] == rep.param_uploads
    assert stats["shared_bytes"] == rep.shared_bytes == 0
    # 8 cells, 24 intervals: 2 chunks of 4 lanes, or 4 lanes of 16-interval
    # segments, each cell taking 2.
    assert stats["dispatches"] == rep.dispatches == (
        rep.segments if path == "compact" else rep.n_chunks)
    assert rep.dispatches == (4 if path == "compact" else 2)
    assert sum(p[0] == "sweep.dispatch" for p in found) == rep.dispatches


_STREAM = np.random.default_rng(3)
# case: (sweep params, distinct request streams the build draws)
STREAMS = {
    "tiled": (dict(seeds=np.tile([11, 12, 13, 14], 64), n_requests=8), 4),
    "distinct": (PARAMS, 8),
    "workload": (dict(seeds=np.arange(3), workload=dict(
        submit=np.sort(_STREAM.uniform(0, 10, 12)),
        src=_STREAM.integers(0, 3, 12, np.int32),
        prompt_tok=_STREAM.integers(64, 1024, 12),
        decode_tok=_STREAM.integers(16, 512, 12),
        online=np.arange(12) >= 3)), 1),
}


@pytest.mark.parametrize("case", sorted(STREAMS))
def test_build_span_counts_request_streams(case, tmp_path):
    """``sweep.prepare.build`` carries the number of distinct request
    streams drawn: one per scenario seed a population shares, one a cell
    with distinct seeds, one for an injected workload."""
    params, want = STREAMS[case]
    _, found = _traced(tmp_path, SweepConfig(), params=params)
    (_, _, _, stats), = [p for p in found if p[0] == "sweep.prepare.build"]
    assert stats["workloads"] == want


@pytest.mark.parametrize("path", sorted(CONFIGS))
def test_profiler_off_changes_nothing(path, tmp_path, monkeypatch):
    config = CONFIGS[path]
    traced, _ = _traced(tmp_path, config)
    assert not spans.tracing()

    def no_stats(report):
        raise AssertionError("stats built with the profiler off")
    monkeypatch.setattr(backend, "report_stats", no_stats)
    plain = run_sweep(KIND, PARAMS, config=config)
    assert plain.outputs.keys() == traced.outputs.keys()
    for key, v in plain.outputs.items():
        v, w = np.asarray(v), np.asarray(traced.outputs[key])
        assert v.dtype == w.dtype and v.tobytes() == w.tobytes(), key
    assert plain.report_fields() == traced.report_fields()


def test_each_call_has_its_own_sweep_id(tmp_path):
    config = CONFIGS["chunked"]
    run_sweep(KIND, PARAMS, config=config)
    with jax.profiler.trace(str(tmp_path)):
        run_sweep(KIND, PARAMS, config=config)
        run_sweep(KIND, PARAMS, config=config)
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    found = programspans.load_program(path)
    first, second = [p for p in found if p[0] == "sweep"]
    assert second[3]["id"] == first[3]["id"] + 1
    for _, s, e, _ in found:
        assert any(lo <= s and e <= hi for _, lo, hi, _ in (first, second))


def test_device_ops_carry_the_engine_scope():
    plan = _prepare_llmserve(use_pallas=False, seeds=np.arange(2),
                             n_requests=8)
    with vec_engine.x64():
        loop = jax.jit(vec_engine.batched_sim(LLMSERVE_ENGINE, plan.statics,
                                              shared=True))
        hlo = loop.lower(plan.params, plan.shared).compile().as_text()
        seg = jax.jit(vec_engine._segment_sim(LLMSERVE_ENGINE, plan.statics,
                                              4, shared=True))
        proto = vec_engine.state_prototype(LLMSERVE_ENGINE, plan.statics,
                                           plan.params, plan.shared)
        state = jax.tree_util.tree_map(
            lambda sd: np.zeros((2,) + sd.shape, sd.dtype), proto)
        seg_hlo = seg.lower(plan.params, state, np.zeros(2, np.int32),
                            np.ones(2, bool), plan.shared).compile().as_text()
    assert re.search(r'op_name="[^"]*llmserve_batch\)?/loop/while', hlo)
    assert re.search(r'op_name="[^"]*llmserve_batch\)?/segment/while',
                     seg_hlo)
