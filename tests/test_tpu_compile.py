"""Compile the main path's loops and kernels for a described TPU v5e chip.

Nothing runs: each test lowers and compiles for a chip that is described,
not attached, so what the TPU compiler would refuse (a misaligned Pallas
block, a dtype the kernel lowering lacks, a program too big for the chip)
fails here at no chip time.  The topology is described inside a fixture,
never at import, so every test worker collects the same tests and only
the one that runs this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.vec_engine import batched_sim, x64

# chip_smoke.py's power width per lane: CloudSim's PlanetLab set-up.
POWER = dict(n_hosts=800, n_vms=1052, n_samples=288, interval=300.0)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep these out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                       sharding=sharding), tree)


def _compile_loop(engine, plan, sharding):
    """The monolithic loop of ``plan``, with its shared operand beside the
    lane params where it has one (llmserve's request tables, which each
    lane gathers its rows from before the loop)."""
    args = (plan.params,) if plan.shared is None else (plan.params,
                                                       plan.shared)
    return jax.jit(batched_sim(engine, plan.statics,
                               plan.shared is not None)).lower(
        *_shapes(args, sharding)).compile()


def test_power_loop_compiles_at_planetlab_width(one_chip):
    from repro.core.vec_power import POWER_ENGINE, _prepare_power
    with x64():
        plan = _prepare_power(use_pallas=False, seeds=np.arange(16), **POWER)
        compiled = _compile_loop(POWER_ENGINE, plan, one_chip)
    out = compiled.memory_analysis().output_size_in_bytes
    assert out >= 16 * POWER["n_hosts"] * 10 * 12     # seg_count + seg_frac


def test_llmserve_loop_compiles_at_bench_width(one_chip, monkeypatch):
    """The chip's route: doubles as int64 bit patterns (f64bits)."""
    from repro.core import f64bits
    from repro.core.search import placement_from_keys
    from repro.core.vec_llmserve import LLMSERVE_ENGINE, _prepare_llmserve
    monkeypatch.setattr(f64bits, "native", lambda: False)
    rng = np.random.default_rng(7)
    placement = placement_from_keys(rng.uniform(0.0, 1.0, (256, 24)), 12, 2)
    with x64():
        plan = _prepare_llmserve(
            use_pallas=False, seeds=np.arange(256), placement=placement,
            n_machines=24, n_regions=3, n_stages=2, n_requests=512,
            decode_tokens=(16, 90_000))
        assert plan.statics.f64_bits and plan.shared.dtype == np.int64
        _compile_loop(LLMSERVE_ENGINE, plan, one_chip)


@pytest.mark.parametrize("shape", [(256, 24), (256, 4096)])
def test_next_event_f32_compiles(one_chip, shape):
    """Rows a multiple of 8 or all of R, blocks a multiple of 128 or all
    of M: the tiling the TPU lowering accepts, at shapes it once refused."""
    from repro.kernels.next_event import next_event
    compiled = jax.jit(lambda t: next_event(t, interpret=False)).lower(
        jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_next_event_vmapped_under_x64_compiles(one_chip):
    """The engines' form: vmapped, traced with x64 on — int32 indices
    throughout, or the lowering refuses the kernel."""
    from repro.kernels.next_event import next_event
    with x64():
        jax.jit(jax.vmap(lambda t: next_event(t, interpret=False))).lower(
            jax.ShapeDtypeStruct((1024, 800), jnp.float32,
                                 sharding=one_chip)).compile()
