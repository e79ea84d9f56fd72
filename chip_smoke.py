#!/usr/bin/env python3
"""Smoke run of the vec sweep path on a TPU, through the user entry points.

    python chip_smoke.py             # one chip: every vec kind + the kernel
    python chip_smoke.py --chips 4   # four chips: the sharded power sweep only

One chip: ``run_sweep(kind, ..., backend="vec")`` for ``power_batch`` at
the size of CloudSim's ``examples.power.planetlab`` set-up (800 hosts,
1052 VMs — the 2011-03-03 PlanetLab day — 288 intervals of 300 s; the
sizes of Beloglazov & Buyya 2012) over 1024 seeds, once monolithic and
once through the compacting scheduler; ``llmserve_batch``,
``netdc_batch``, ``storage_batch`` and ``fleet_batch`` at the full widths
of their ``benchmarks/*_sweep.py`` grids; then the float32 next-event
Pallas kernel, natively, against ``next_event_ref``.  Four lanes of every
kind are compared with the ``oo`` reference on the host: every output of
llmserve (exact on the chip), every integer and bool output of the rest.

Four chips: the same power cells with ``devices=4`` through the chunked
executor and the compacting scheduler, both ``shard_map`` over the four
chips, each compared bit for bit with a ``devices=1`` run in this
process.

Every phase prints its wall and compile seconds (one unwarmed run each —
not metrics).  The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``
only when a TPU was found and every phase passed; otherwise the script
prints no such line and exits non-zero.  One process, no children.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
import traceback

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

SEEDS = 1024
N_REF = 4                    # lanes of each kind replayed on the oo backend
POWER = dict(n_hosts=800, n_vms=1052, n_samples=288, interval=300.0)
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class Failed(Exception):
    """A phase's output broke its check."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise Failed(msg)


def ulps(a: np.ndarray, b: np.ndarray) -> int:
    """Largest distance in units in the last place between two float
    arrays of one dtype (-1 where they disagree on NaN)."""
    if np.isnan(a).any() or np.isnan(b).any():
        return 0 if np.array_equal(a, b, equal_nan=True) else -1
    top = 2 ** (8 * a.dtype.itemsize - 1)

    def ordered(x):                  # ± the magnitude bits: monotone in x
        i = x.view(f"i{x.dtype.itemsize}").astype(object)
        return np.where(i < 0, -top - i, i)
    return int(np.max(np.abs(ordered(a) - ordered(b)), initial=0))


def compare(name: str, vec: dict, ref: dict, *, keys=None,
            ints_only: bool = False) -> None:
    """``vec``'s first lanes against ``ref``, output by output, printing
    each float output's largest ulp distance and each integer or bool
    output's count of differing elements.  Every output must be
    bit-identical; with ``ints_only`` (the chip against the host's IEEE
    doubles — the chip emulates f64 with pairs of f32) only the integer
    and bool outputs must: events, migrations, served, dropped,
    iterations, routing choices."""
    keys = sorted(ref) if keys is None else keys
    differ, held = [], []
    for k in keys:
        a = np.asarray(ref[k])
        b = np.asarray(vec[k])[:len(a)]
        check(a.shape == b.shape, f"{name} {k}: shape {b.shape} vs {a.shape}")
        if a.dtype.kind == "f":
            fin = np.isfinite(a) & np.isfinite(b) & (a != 0)
            rel = np.abs(b[fin] - a[fin]) / np.abs(a[fin])
            print(f"  {name} {k}: max_ulp={ulps(a, b.astype(a.dtype))} "
                  f"max_rel={float(np.max(rel, initial=0.0))!r}")
        else:
            print(f"  {name} {k}: differing={int(np.sum(a != b))}")
        if not np.array_equal(a, b, equal_nan=True):
            differ.append(k)
            if not ints_only or a.dtype.kind in "biu":
                held.append(k)
    print(f"  {name}: {len(keys) - len(differ)}/{len(keys)} outputs "
          f"bit-identical; differ: {differ}")
    check(not held, f"{name}: outputs differ in {held}")


def finite(name: str, out: dict, n: int) -> None:
    for k, v in out.items():
        v = np.asarray(v)
        check(v.shape[:1] == (n,), f"{name} {k}: shape {v.shape}, want ({n}, ...)")
        if v.dtype.kind == "f":
            check(not np.isnan(v).any(), f"{name} {k}: NaN")


class Phases:
    """Runs each phase once, times it, and records failures."""

    def __init__(self) -> None:
        import jax
        self.compile_s = 0.0
        self.failed = []
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.compile_s += duration

    def run(self, name: str, fn) -> None:
        c0, t0 = self.compile_s, time.perf_counter()
        try:
            fn()
            status = "passed"
        except Exception:            # report every phase, then fail the run
            traceback.print_exc()
            self.failed.append(name)
            status = "FAILED"
        print(f"phase {name}: {status} wall_s={time.perf_counter() - t0!r} "
              f"compile_s={self.compile_s - c0!r} "
              f"(one unwarmed smoke run, not a metric)", flush=True)


def power_params(seeds):
    return dict(seeds=seeds, **POWER)


def phase_power(run_sweep, run_scenario, SweepConfig):
    seeds = np.arange(SEEDS)
    res = run_sweep("power_batch", power_params(seeds))
    out = res.outputs
    finite("power", out, SEEDS)
    check(np.asarray(out["energy_wh"]).shape == (SEEDS, POWER["n_hosts"]),
          "power energy_wh shape")
    check(np.all(np.asarray(out["iterations"]) == POWER["n_samples"]),
          "power: every lane runs n_samples intervals")
    ref = run_scenario("power_batch", backend="oo",
                       **power_params(seeds[:N_REF]))
    compare("power", out, ref, ints_only=True)
    print(f"  power report: {res.report_fields()}")

    cres = run_sweep("power_batch", power_params(seeds),
                     config=SweepConfig(compact=True))
    check(cres.report.compacted, "power compact: not compacted")
    compare("power compact vs monolithic", cres.outputs, out,
            keys=sorted(out))
    print(f"  power compact report: {cres.report_fields()}")


def phase_llmserve(run_sweep, run_scenario):
    from benchmarks import llmserve_sweep as bench
    b = 256
    params = bench._params(*bench._grid(b), 512)
    out = run_sweep("llmserve_batch", params).outputs
    finite("llmserve", out, b)
    ref = run_scenario("llmserve_batch", backend="oo",
                       **{k: (v[:N_REF] if isinstance(v, np.ndarray) else v)
                          for k, v in params.items()})
    # Its loop carries the doubles as int64 bit patterns on the chip
    # (repro.core.f64bits), so every output must match, floats included.
    compare("llmserve", out, ref)


def phase_netdc(run_sweep, run_scenario):
    from benchmarks import netdc_sweep as bench
    seeds, w, off = bench._grid(256)
    params = dict(seeds=seeds, n_dcs=8, n_jobs=160, locality_weight=w,
                  offline_dc=off)
    out = run_sweep("netdc_batch", params).outputs
    finite("netdc", out, 256)
    ref = run_scenario("netdc_batch", backend="oo",
                       **{k: v[:N_REF] if isinstance(v, np.ndarray) else v
                          for k, v in params.items()})
    compare("netdc", out, ref, ints_only=True)


def phase_storage(run_sweep, run_scenario):
    from benchmarks import storage_sweep as bench
    seeds, w, off = bench._grid(256)
    params = dict(seeds=seeds, n_nodes=8, n_objects=160, n_replicas=2,
                  quorum=2, placement_weight=w, offline_node=off)
    out = run_sweep("storage_batch", params).outputs
    finite("storage", out, 256)
    ref = run_scenario("storage_batch", backend="oo",
                       **{k: v[:N_REF] if isinstance(v, np.ndarray) else v
                          for k, v in params.items()})
    compare("storage", out, ref, ints_only=True)


def phase_fleet(run_sweep, run_scenario):
    from dataclasses import replace

    from benchmarks import batch_sweep as bench
    b, steps = 256, 1000
    cfg = bench._fleet_cfg(64)
    mt, ck, seeds = bench._sweep_axes(b)
    out = run_sweep("fleet_batch", dict(
        cost=bench.COST, cfg=cfg, total_steps=steps, seeds=seeds,
        mtbf_hours=mt, ckpt_every=ck)).outputs
    finite("fleet", out, b)
    check(np.all((np.asarray(out["goodput"]) > 0)
                 & (np.asarray(out["goodput"]) <= 1)), "fleet goodput range")
    # Bit-exactness vs oo is promised on deterministic configs only
    # (no stragglers, no failures): replay the grid's cadences so.
    det = replace(cfg, straggler_sigma=0.0, mtbf_hours_node=1e9)
    params = dict(cost=bench.COST, cfg=det, total_steps=steps,
                  seeds=seeds[:N_REF], ckpt_every=ck[:N_REF])
    vec = run_sweep("fleet_batch", params).outputs
    ref = run_scenario("fleet_batch", backend="oo", **params)
    compare("fleet (deterministic)", vec, ref, ints_only=True)


def phase_next_event():
    import jax
    import jax.numpy as jnp

    from repro.kernels.next_event import next_event, next_event_ref
    r, m = SEEDS, POWER["n_hosts"]           # lanes × hosts, a sweep shape
    rng = np.random.default_rng(0)
    t = rng.choice(np.arange(64, dtype=np.float32), size=(r, m))  # ties
    mask = rng.random((r, m)) < 0.9
    mask[:3] = False                          # all-masked rows
    t, mask = jnp.asarray(t), jnp.asarray(mask)
    v, i = jax.jit(lambda t, k: next_event(t, k, interpret=False))(t, mask)
    vr, ir = jax.jit(next_event_ref)(t, mask)
    v, i, vr, ir = map(np.asarray, (v, i, vr, ir))
    print(f"  next_event [{r}, {m}]: min differing={int(np.sum(v != vr))} "
          f"argmin differing={int(np.sum(i != ir))}")
    check(v.dtype == np.float32 and i.dtype == np.int32, "dtypes")
    check(np.array_equal(v, vr), "next_event min differs from reference")
    check(np.array_equal(i, ir), "next_event argmin differs from reference")
    text = jax.jit(lambda t, k: next_event(t, k, interpret=False)).lower(
        t, mask).as_text()
    check("tpu_custom_call" in text, "kernel did not lower to Mosaic")


def phase_four_chips(run_sweep, SweepConfig):
    seeds = np.arange(SEEDS)
    one = run_sweep("power_batch", power_params(seeds),
                    config=SweepConfig(devices=1)).outputs
    four = run_sweep("power_batch", power_params(seeds),
                     config=SweepConfig(devices=4))
    check(four.report.devices == 4 and four.report.sharding == "shard_map",
          f"chunked ran as {four.report_fields()}")
    compare("power devices=4 chunked (shard_map) vs devices=1", four.outputs,
            one, keys=sorted(one))
    comp = run_sweep("power_batch", power_params(seeds),
                     config=SweepConfig(devices=4, compact=True))
    check(comp.report.devices == 4 and comp.report.sharding == "shard_map",
          f"compact ran as {comp.report_fields()}")
    compare("power devices=4 compact (shard_map) vs devices=1",
            comp.outputs, one, keys=sorted(one))
    print(f"  chunked report: {four.report_fields()}")
    print(f"  compact report: {comp.report_fields()}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    from repro import compile_cache
    from repro.core.backend import run_scenario, run_sweep
    from repro.core.sweep import SweepConfig

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    print(f"platform={platform} device_kind={kind} count={len(devices)}")
    if platform != "tpu":
        print(f"error: no TPU (JAX found {platform!r})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"error: --chips {args.chips} but {len(devices)} devices",
              file=sys.stderr)
        return 2
    print(f"compile cache: {compile_cache.enable()}")

    phases = Phases()
    if args.chips == 4:
        phases.run("power_4chips",
                   lambda: phase_four_chips(run_sweep, SweepConfig))
    else:
        phases.run("power", lambda: phase_power(run_sweep, run_scenario,
                                                SweepConfig))
        phases.run("llmserve", lambda: phase_llmserve(run_sweep,
                                                      run_scenario))
        phases.run("netdc", lambda: phase_netdc(run_sweep, run_scenario))
        phases.run("storage", lambda: phase_storage(run_sweep, run_scenario))
        phases.run("fleet", lambda: phase_fleet(run_sweep, run_scenario))
        phases.run("next_event", phase_next_event)
    if phases.failed:
        print(f"error: failed phases {phases.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
