"""Sharding resolution tests + a miniature dry-run in a subprocess.

The subprocess carries its own XLA_FLAGS (8 fake devices) so the main test
process stays single-device (the dry-run flag locks device count at first
jax init — see the launch/dryrun.py preamble).
"""
import json
import os
import subprocess
import sys
import tempfile

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
# Children run on the CPU backend only: none of them may reach for a chip.
CHILD_ENV = dict(os.environ, JAX_PLATFORMS="cpu")


# -- resolve_spec properties -----------------------------------------------------

def _mesh(shape=(2, 4), axes=("data", "model")):
    # AbstractMesh: resolve_spec/cache_spec only read mesh.shape, and the
    # main test process has a single CPU device (no 8-device mesh possible).
    import jax
    return jax.sharding.AbstractMesh(tuple(shape), tuple(axes))


def test_resolve_divisibility_fallback():
    from repro.distributed.sharding import LOGICAL_RULES_BASE, resolve_spec
    mesh = _mesh()
    # kv_heads=3 doesn't divide model=4 → replicated
    spec = resolve_spec((64, 3, 16), ("embed", "kv_heads", "head_dim"),
                        mesh, LOGICAL_RULES_BASE)
    assert spec[1] is None
    # mlp=8 divides model=4 → sharded
    spec = resolve_spec((64, 8), ("embed", "mlp"), mesh, LOGICAL_RULES_BASE)
    assert spec == ("data", "model") or tuple(spec) == ("data", "model")


def test_resolve_no_duplicate_mesh_axes():
    from repro.distributed.sharding import LOGICAL_RULES_BASE, resolve_spec
    mesh = _mesh()
    # experts and mlp both want "model": first-come wins, second replicates
    spec = resolve_spec((4, 64, 8), ("experts", "embed", "mlp"),
                        mesh, LOGICAL_RULES_BASE)
    assert spec[0] == "model" and spec[2] is None


# test_resolve_spec_never_errors (property-based): moved to test_properties.py

def test_cache_spec_kv_fallback_to_seq():
    from repro.distributed.sharding import cache_spec
    mesh = _mesh((2, 4), ("data", "model"))
    # K=2 doesn't divide model=4 → shard the sequence dim instead
    spec = cache_spec((8, 64, 2, 16), "attn_kv", mesh, stacked=False)
    assert spec[2] is None and spec[1] == "model"
    # K=4 divides → shard heads
    spec = cache_spec((8, 64, 4, 16), "attn_kv", mesh, stacked=False)
    assert spec[2] == "model"


# -- miniature dry-run (subprocess, 8 fake devices) --------------------------------

DRYRUN_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json, dataclasses
sys.path.insert(0, {src!r})
import jax
from repro.configs.base import load_tiny, ShapeConfig
from repro.launch.steps import build_cell
from repro.launch.mesh import make_mesh
from repro.launch.roofline import collective_bytes_per_device, cost_of

cfg = dataclasses.replace(load_tiny({arch!r}), scan_layers=False)
mesh = make_mesh(2, 4)
shape = ShapeConfig("t", 64, 8, {kind!r})
with mesh:
    fn, args = build_cell(cfg, shape, mesh)
    compiled = fn.lower(*args).compile()
coll = collective_bytes_per_device(compiled.as_text())
print(json.dumps({{"cost": cost_of(compiled), "coll_total": coll["total"]}}))
"""


@pytest.mark.parametrize("arch,kind", [("qwen3_8b", "train"),
                                       ("moonshot_v1_16b_a3b", "train"),
                                       ("rwkv6_7b", "decode"),
                                       ("hubert_xlarge", "prefill")])
def test_mini_dryrun_subprocess(arch, kind):
    code = DRYRUN_SNIPPET.format(src=os.path.abspath(SRC), arch=arch, kind=kind)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=CHILD_ENV)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["cost"]["flops"] > 0
    if kind == "train":
        assert rec["coll_total"] > 0        # grad/TP collectives must exist


def test_production_mesh_shapes():
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import sys
sys.path.insert(0, {src!r})
from repro.launch.mesh import make_production_mesh
m1 = make_production_mesh()
m2 = make_production_mesh(multi_pod=True)
assert dict(m1.shape) == {{"data": 16, "model": 16}}, m1.shape
assert dict(m2.shape) == {{"pod": 2, "data": 16, "model": 16}}, m2.shape
print("ok")
""".format(src=os.path.abspath(SRC))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, env=CHILD_ENV)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "ok" in out.stdout


DP_COMPRESS_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys, json
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import load_tiny
from repro.models.model import build
from repro.optim import make_optimizer
from repro.train.dp_step import make_dp_train_step

mesh = jax.make_mesh((4,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))
arch = load_tiny("granite_20b")
model = build(arch, seq_impl="scan")
opt = make_optimizer("adamw")
params = model.init(jax.random.PRNGKey(0))
opt_state = opt.init(params)
batch = {{"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, arch.vocab),
         "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, arch.vocab)}}
results = {{}}
for compress in (False, True):
    step, ef_init = make_dp_train_step(model, opt, mesh, compress=compress)
    ef = ef_init(params)
    with mesh:
        p, o, loss, ef = step(params, opt_state, batch, ef)
        p2, o2, loss2, ef = step(p, o, batch, ef)
    results[compress] = (float(loss), float(loss2),
                         [np.asarray(x) for x in jax.tree.leaves(p2)])
(le, le2, pe), (lc, lc2, pc) = results[False], results[True]
rel = max(float(np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-9))
          for a, b in zip(pe, pc))
print(json.dumps({{"loss_exact": le, "loss_comp": lc, "loss2_exact": le2,
                  "loss2_comp": lc2, "max_rel_param_diff": rel}}))
"""


def test_dp_compressed_gradients_subprocess():
    """int8 EF-compressed psum ≈ exact pmean; training still descends."""
    code = DP_COMPRESS_SNIPPET.format(src=os.path.abspath(SRC))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=CHILD_ENV)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert abs(rec["loss_exact"] - rec["loss_comp"]) < 1e-3
    assert rec["max_rel_param_diff"] < 0.05
    assert rec["loss2_comp"] < rec["loss_comp"]      # still learning
