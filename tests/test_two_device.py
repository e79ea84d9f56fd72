"""Two-device parity of the one multi-device executor, for every vec kind
other than fleet (``test_sweep.py`` and ``test_compact.py`` cover fleet).

Each kind runs three ways in one subprocess with 2 forced host devices:
on one device (the reference), monolithic over both devices with a lane
count that is not a device multiple (the chunk is padded to one), and
through the compacting scheduler over both devices with refills.  Both
two-device runs go through ``shard_map`` on the lane mesh and must
reproduce the one-device outputs bit for bit.  One module-scoped process
serves all six cases: XLA's device count is fixed at backend init.
"""
import json
import os
import subprocess
import sys

import pytest

KINDS = ("cloudlet_batch", "workflow_batch", "power_batch", "netdc_batch",
         "storage_batch", "llmserve_batch")

_CODE = """
import hashlib, json
import numpy as np
import jax
from repro.core.backend import run_sweep
from repro.core.sweep import SweepConfig
assert jax.device_count() == 2, jax.devices()

B = 5                                   # not a multiple of 2 devices
rng = np.random.default_rng(7)
PARAMS = {
    "cloudlet_batch": dict(
        length=rng.uniform(100, 4000, (B, 3, 4))
        * (rng.random((B, 3, 4)) < 0.8),
        pes=np.ones((B, 3, 4)), submit=rng.uniform(0, 10, (B, 3, 4)),
        guest_mips=rng.uniform(500, 1500, (B, 3)),
        guest_pes=np.full((B, 3), 2.0)),
    "workflow_batch": dict(
        nodes=[1000.0, 2000.0, 1500.0, 1000.0],
        edges=[(0, 1), (0, 2), (1, 3), (2, 3)], guest_of=[0, 1, 2, 0],
        guest_mips=[1000.0] * 3, payload=list(np.linspace(0.0, 2e6, B)),
        activations=3, arrival_rate=0.5),
    "power_batch": dict(seeds=np.arange(B), n_hosts=4, n_vms=8,
                        n_samples=16),
    "netdc_batch": dict(seeds=np.arange(B), n_dcs=3, n_jobs=8),
    "storage_batch": dict(seeds=np.arange(B), n_nodes=4, n_objects=16),
    "llmserve_batch": dict(seeds=np.arange(B), n_requests=16,
                           n_machines=6, n_regions=3),
}
CONFIGS = {
    "one": SweepConfig(devices=1),
    "mono": SweepConfig(),
    "compact": SweepConfig(compact=True, chunk_size=2, segment_iters=3),
}


def digest(out):
    if not isinstance(out, dict):       # cloudlet_batch: one finish array
        out = {"finish": out}
    h = hashlib.sha256()
    for k in sorted(out):
        v = np.asarray(out[k])
        h.update(f"{k}:{v.dtype}:{v.shape}".encode())
        h.update(np.ascontiguousarray(v).tobytes())
    return h.hexdigest()


result = {}
for kind, params in PARAMS.items():
    result[kind] = {}
    for name, config in CONFIGS.items():
        out, rep = run_sweep(kind, params, config=config)
        result[kind][name] = dict(digest=digest(out),
                                  report=rep.report_fields())
print(json.dumps(result))
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ,
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=2"),
               PYTHONPATH=os.pathsep.join(sys.path), JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", _CODE], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("kind", KINDS)
def test_two_device_shard_map_bit_identical(runs, kind):
    one, mono, comp = (runs[kind][n] for n in ("one", "mono", "compact"))
    assert one["report"]["devices"] == 1
    assert one["report"]["sharding"] is None
    rep = mono["report"]
    assert rep["devices"] == 2 and rep["sharding"] == "shard_map", rep
    assert rep["chunk_size"] % 2 == 0, rep          # padded to the devices
    rep = comp["report"]
    assert rep["devices"] == 2 and rep["sharding"] == "shard_map", rep
    assert rep["compacted"] and rep["refills"] > 0, rep
    assert mono["digest"] == one["digest"]
    assert comp["digest"] == one["digest"]
