"""The comparison that decides ``correct``: sound runs pass, the lower
precision control and each fault of the timed path fail.  The control's
and the faults' readings at the cells' own sizes are taken on the chip by
``bench/control.py``; here at sizes a test run holds."""
import dataclasses

import numpy as np
import pytest

from bench import check, harness
from bench.tests.held import root_with_held
from repro.core import vec_engine, sweep as sweep_mod
from repro.core.backend import run_sweep

POWER = {"n_hosts": 24, "n_vms": 31, "n_samples": 80}
LLM = {"n_requests": 80}
TINY = {"power_planetlab800.cem_compact": POWER,
        "power_planetlab800.sweep_mono": POWER,
        "llmserve_helix24.placement_compact": LLM,
        "llmserve_helix24.placement_mono": LLM}
CELLS = sorted(TINY)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return root_with_held(tmp_path_factory.mktemp("root"))


@pytest.fixture(autouse=True)
def no_cache(monkeypatch):
    monkeypatch.setattr(harness, "use_compile_cache", lambda root: "off")


@pytest.fixture
def run_cell(root):
    def _run(cell, program=None, control=False, seed=2 ** 31 + 5):
        return harness.run(cell, seed, 0.5, False, t_start=0.0,
                           require_chip=False, overrides=TINY[cell],
                           program=program, control=control, root=root)
    return _run


def test_output_gap():
    ref = {"x": np.array([[1.0, 2.0, np.inf]]), "n": np.array([[3, 4]])}
    assert check.compare(ref, ref)[0] == 0.0
    moved = {"x": np.array([[1.0, 2.0 + 2e-9, np.inf]]), "n": ref["n"]}
    assert check.compare(moved, ref)[0] == pytest.approx(1e-9)
    assert check.compare({"x": ref["x"], "n": np.array([[3, 5]])},
                         ref)[0] == pytest.approx(0.25)
    inf_lost = {"x": np.array([[1.0, 2.0, 5.0]]), "n": ref["n"]}
    assert check.compare(inf_lost, ref)[0] == np.inf
    assert check.compare({"x": ref["x"]}, ref)[0] == np.inf
    nan = {"x": np.array([[np.nan, 2.0, np.inf]]), "n": ref["n"]}
    assert check.compare(nan, ref)[0] == np.inf


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_control_is_not(cell, run_cell):
    out = run_cell(cell)
    assert out["correct"], out["check"]
    assert out["check"]["max_rel_gap"]["value"] == 0.0     # the CPU is IEEE
    ctl = run_cell(cell, control=True)
    assert not ctl["correct"]
    assert ctl["check"]["max_rel_gap"]["value"] > \
        ctl["check"]["max_rel_gap"]["limit"]


def _half_batch(kind, params, config):
    """Half of the cells run; the others take their outputs."""
    n = len(params["seeds"])
    half = {k: (v[: n // 2] if isinstance(v, np.ndarray) and len(v) == n
                else v) for k, v in params.items()}
    res = run_sweep(kind, half, config=config)
    out = {k: np.concatenate([v, v]) for k, v in res.outputs.items()}
    return type(res)(out, res.report, kind=kind, backend="vec")


def _altered(kind, params, config):
    """One float answer of every cell moved by one part in a million."""
    res = run_sweep(kind, params, config=config)
    out = dict(res.outputs)
    key = next(k for k in sorted(out) if np.asarray(out[k]).dtype.kind == "f"
               and np.isfinite(np.asarray(out[k])).all())
    v = np.array(out[key], np.float64)
    v.reshape(len(v), -1)[:, 0] *= 1 + 1e-6
    out[key] = v
    return type(res)(out, res.report, kind=kind, backend="vec")


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["half_batch", "altered_answer"])
def test_a_broken_timed_path_is_not_correct(cell, fault, run_cell):
    program = {"half_batch": _half_batch, "altered_answer": _altered}[fault]
    out = run_cell(cell, program=program)
    assert not out["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_a_step_that_returns_its_state_unchanged_is_not_correct(cell,
                                                                run_cell):
    from repro.core import vec_llmserve, vec_power
    engine = (vec_power.POWER_ENGINE if "power" in cell
              else vec_llmserve.LLMSERVE_ENGINE)
    build = engine.build

    def frozen(params, statics, ops):
        return build(params, statics, ops)._replace(body=lambda s, it: s)

    caches = (vec_engine.batched_sim, vec_engine._segment_sim,
              vec_engine.segment_step, sweep_mod._executor)
    object.__setattr__(engine, "build", frozen)
    try:
        for c in caches:
            c.cache_clear()
        out = run_cell(cell)
    finally:
        object.__setattr__(engine, "build", build)
        for c in caches:
            c.cache_clear()
    assert dataclasses.is_dataclass(engine) and engine.build is build
    assert not out["correct"]


FOUR_CHIPS = '''
import json
import pathlib
import numpy as np
from bench import harness
from repro.core.backend import run_sweep

harness.use_compile_cache = lambda root: "off"


def exchange_left_out(kind, params, config):
    """Every chip's lanes come back as the first chip's: the results of
    the other chips are never gathered."""
    res = run_sweep(kind, params, config=config)
    out = {}
    for k, v in res.outputs.items():
        v = np.array(v)
        per = len(v) // 4
        v[per:] = np.concatenate([v[:per]] * 3)
        out[k] = v
    return type(res)(out, res.report, kind=kind, backend="vec")


rows = [harness.run(CELL, 2 ** 31 + 9, 1.0, False, t_start=0.0,
                    require_chip=False, overrides={"n_requests": 40},
                    program=program, root=pathlib.Path(ROOT))
        for program in (None, exchange_left_out)]
print(json.dumps([[r["correct"], r["device"]["count"],
                   r["check"]["max_rel_gap"]["value"]] for r in rows]))
'''


def test_four_chip_cell_on_four_devices_and_the_exchange_left_out(root):
    """The four-chip cell on four virtual CPU devices: a sound run is
    correct, and one whose other chips' results are never gathered is
    not."""
    import json
    import os
    import subprocess
    import sys
    from bench import spec
    cell = "llmserve_helix24.placement_mono_4chip"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(spec.ROOT / "src"), str(spec.ROOT)]))
    proc = subprocess.run(
        [sys.executable, "-c",
         f"CELL = {cell!r}\nROOT = {str(root)!r}\n" + FOUR_CHIPS],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    (ok, n, gap), (bad, _, bad_gap) = json.loads(
        proc.stdout.strip().splitlines()[-1])
    assert n == 4 and ok and gap == 0.0
    assert not bad and bad_gap > 0.0
