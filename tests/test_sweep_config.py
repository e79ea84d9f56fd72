"""The typed sweep/scenario API contract (ISSUE 7 satellite).

``run_sweep(kind, params, config=SweepConfig(...))`` separates scenario
parameters from sweep scheduling; results come back as a
:class:`ScenarioResult` that unpacks as the historical ``(outputs,
report)`` pair.  These tests pin the contract: SweepConfig validation and
round-trips, the legacy loose-kwargs shim (one warning, did-you-mean
rejections, bit-identical dispatch), misplaced-key errors from the typed
path, and the ScenarioResult surface every consumer (benchmarks, examples,
the CEM objectives) now reads.
"""
import warnings

import numpy as np
import pytest

from repro.core import backend as backend_mod
from repro.core.backend import (BackendError, ScenarioResult,
                                ScenarioUnsupported, run_sweep,
                                supporting_backends)
from repro.core.sweep import SweepConfig, SweepReport

PARAMS = dict(seeds=(0, 1), n_requests=16, n_machines=6, n_regions=3)
KIND = "llmserve_batch"


@pytest.fixture
def fresh_warning_gate():
    """Reset the one-time legacy-kwargs DeprecationWarning latch."""
    old = backend_mod._warned_legacy_controls
    backend_mod._warned_legacy_controls = False
    yield
    backend_mod._warned_legacy_controls = old


# -- SweepConfig ---------------------------------------------------------------

def test_config_defaults_round_trip():
    cfg = SweepConfig()
    assert cfg.to_kwargs() == {}          # defaults add nothing to a call
    assert SweepConfig.from_kwargs(**cfg.to_kwargs()) == cfg


def test_config_non_default_round_trip():
    cfg = SweepConfig(compact=True, chunk_size=64, segment_iters=7,
                      quarantine=True, precision="exact",
                      use_pallas="force", donate=False)
    kw = cfg.to_kwargs()
    assert kw == dict(compact=True, chunk_size=64, segment_iters=7,
                      quarantine=True, precision="exact",
                      use_pallas="force", donate=False)
    assert SweepConfig.from_kwargs(**kw) == cfg


def test_config_replace_is_functional():
    cfg = SweepConfig(chunk_size=8)
    cfg2 = cfg.replace(compact=True)
    assert cfg2.compact and cfg2.chunk_size == 8
    assert not cfg.compact                 # frozen original untouched


def test_config_validates_enums_and_bounds():
    with pytest.raises(ValueError, match="precision"):
        SweepConfig(precision="double")
    with pytest.raises(ValueError, match="chunk_size"):
        SweepConfig(chunk_size=0)
    with pytest.raises(ValueError, match="segment_iters"):
        SweepConfig(segment_iters=-3)


def test_from_kwargs_rejects_unknown_with_suggestion():
    with pytest.raises(TypeError, match="did you mean 'chunk_size'"):
        SweepConfig.from_kwargs(chunksize=8)
    with pytest.raises(TypeError, match="valid fields"):
        SweepConfig.from_kwargs(warp_factor=9)


# -- typed calling convention --------------------------------------------------

def test_typed_path_returns_scenario_result():
    res = run_sweep(KIND, PARAMS, config=SweepConfig(chunk_size=1))
    assert isinstance(res, ScenarioResult)
    out, rep = res                               # tuple unpack still works
    assert out is res.outputs and rep is res.report
    assert isinstance(rep, SweepReport) and rep.chunk_size == 1
    assert res.kind == KIND and res.backend == "vec"
    assert KIND in repr(res)


def test_report_fields_slice_uniform():
    res = run_sweep(KIND, PARAMS)
    fields = res.report_fields()
    assert fields == res.report.report_fields()
    for key in ("devices", "chunk_size", "n_chunks", "compacted",
                "refills", "observed_active_lane_fraction"):
        assert key in fields


def test_summary_digest():
    res = run_sweep(KIND, PARAMS)
    s = res.summary()
    assert s["kind"] == KIND and s["backend"] == "vec"
    assert s["n_cells"] == 2
    assert s["served"] == float(np.mean(res.outputs["served"]))


def test_typed_path_rejects_control_in_params():
    with pytest.raises(TypeError, match="config=SweepConfig"):
        run_sweep(KIND, dict(PARAMS, compact=True))


def test_typed_path_rejects_loose_kwargs_with_suggestion():
    with pytest.raises(TypeError, match="did you mean 'chunk_size'"):
        run_sweep(KIND, PARAMS, chunksize=4)
    with pytest.raises(TypeError, match="did you mean 'seeds'"):
        # close match drawn from the params dict's own keys too
        run_sweep(KIND, PARAMS, seedz=(0,))


def test_config_must_be_sweep_config():
    with pytest.raises(TypeError, match="SweepConfig"):
        run_sweep(KIND, PARAMS, config={"chunk_size": 4})
    with pytest.raises(TypeError, match="mapping"):
        run_sweep(KIND, [("seeds", (0,))])


# -- legacy loose-kwargs shim --------------------------------------------------

def test_legacy_controls_warn_once_and_match_typed(fresh_warning_gate):
    typed = run_sweep(KIND, PARAMS, config=SweepConfig(chunk_size=1))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        legacy = run_sweep(KIND, chunk_size=1, **PARAMS)
        run_sweep(KIND, chunk_size=1, **PARAMS)       # second call: silent
    dep = [x for x in w if issubclass(x.category, DeprecationWarning)]
    assert len(dep) == 1 and "SweepConfig" in str(dep[0].message)
    assert isinstance(legacy, ScenarioResult)
    for k in typed.outputs:
        assert np.array_equal(np.asarray(typed.outputs[k]),
                              np.asarray(legacy.outputs[k])), k


def test_legacy_path_without_controls_does_not_warn(fresh_warning_gate):
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        res = run_sweep(KIND, **PARAMS)
    assert res.report.n_cells == 2


def test_legacy_control_typo_rejected():
    with pytest.raises(TypeError, match="did you\\s+mean.*'chunk_size'"):
        run_sweep(KIND, chunksize=4, **PARAMS)
    with pytest.raises(TypeError, match="segment_iters"):
        run_sweep(KIND, segment_iter=4, **PARAMS)


def test_legacy_controls_and_config_are_exclusive():
    with pytest.raises(TypeError, match="not both"):
        run_sweep(KIND, compact=True, config=SweepConfig(), **PARAMS)


# -- error-message contract (satellite 3) --------------------------------------

def test_unknown_kind_error_lists_kinds():
    with pytest.raises(BackendError, match="llmserve_batch"):
        run_sweep("warp_batch", dict(seeds=(0,)))


def test_unsupported_backend_error_names_supporters_and_aliases():
    # Every not-implemented / no-sweep-path message must carry the kind's
    # supporting_backends() plus their registered aliases (satellite 3).
    from repro.core.backend import _SCENARIOS, scenario
    try:
        @scenario("_cfg_probe", backends=("oo",))
        def _probe(backend, **kw):
            return "bare result"
        with pytest.raises(BackendError) as ei:
            run_sweep("_cfg_probe", dict(), backend="vec")
        assert "supported backends: 'oo' (aliases: '7g'→'oo')" in str(ei.value)
        with pytest.raises(ScenarioUnsupported) as ei2:
            run_sweep("_cfg_probe", dict(), backend="oo")
        msg = str(ei2.value)
        assert "no sweep-aware path" in msg
        for name in supporting_backends("_cfg_probe"):
            assert f"'{name}'" in msg
        assert "aliases" in msg
    finally:
        _SCENARIOS.pop("_cfg_probe", None)
