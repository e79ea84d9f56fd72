# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark harness.

  python -m benchmarks.run [--quick] [--only consolidation,case_study,...]

Benchmarks (paper artifact → module):
  Table 2   → consolidation      (6G vs 7G vs vec run-time + allocation)
  Figure 6  → case_study         (single-activation makespan vs Eq.(2))
  Figure 7  → case_study         (20-activation eCDF + qualitative claims)
  §4.4      → engine_micro       (event-queue data structures)
  beyond    → vec_speedup        (vectorized Algorithm 1 vs OO)
  §6→ML     → cluster_sim        (fleet goodput vs MTBF/ckpt/stragglers)
  beyond    → batch_sweep        (sweep-layer fleet sweep vs OO loop → BENCH_substrate.json)
  beyond    → workflow_sweep     (vmap case-study DAG grid vs OO loop → BENCH_workflow.json)
  beyond    → sweep_runner       (sweep-layer schedule vs monolithic vmap + lane-scaling curve → BENCH_sweep.json)
  beyond    → power_sweep        (elastic-datacenter energy/SLA sweep vs OO loop → BENCH_power.json)
  beyond    → netdc_sweep        (multi-DC routing sweep vs OO loop → BENCH_netdc.json)
  beyond    → llmserve_sweep     (geo LLM-serving sweep vs OO loop → BENCH_llmserve.json)
  beyond    → storage_sweep      (replicated-store sweep + trace replay vs OO loop → BENCH_storage.json)
  beyond    → compaction_sweep   (compacting lane scheduler vs bucketing → BENCH_compaction.json)
  roofline  → dryrun_report      (reads artifacts from launch/dryrun runs)

``--lanes`` overrides the lane-count curve for benches that sweep batch
size (``sweep_runner``), e.g. ``--lanes 256,4096,65536``.

``check_regression.py`` (not a suite) gates the recorded speedups in CI.

The harness turns on the persistent compile cache
(:mod:`repro.compile_cache`).  ``compile_s`` (cold call minus warm call)
therefore measures a real compile only when the cache holds no entry for
the shape; from the second run on it is the time to load the cached
executable.  Compare it only between runs made with the same cache state —
the committed baselines were recorded without the cache.
"""
from __future__ import annotations

import argparse
import inspect
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="reduced sizes")
    ap.add_argument("--only", type=str, default="",
                    help="comma-separated subset of benchmark names")
    ap.add_argument("--lanes", type=str, default="",
                    help="lane-count curve for batch-size-scaling benches "
                         "(comma-separated, e.g. 256,4096,65536)")
    args = ap.parse_args()

    from repro import compile_cache
    compile_cache.enable()
    from . import (batch_sweep, case_study, cluster_sim, compaction_sweep,
                   consolidation, engine_micro, llmserve_sweep, netdc_sweep,
                   power_sweep, storage_sweep, sweep_runner, vec_speedup,
                   workflow_sweep)
    suites = {
        "engine_micro": engine_micro.run,
        "case_study": case_study.run,
        "consolidation": consolidation.run,
        "vec_speedup": vec_speedup.run,
        "cluster_sim": cluster_sim.run,
        "batch_sweep": batch_sweep.run,
        "workflow_sweep": workflow_sweep.run,
        "sweep_runner": sweep_runner.run,
        "power_sweep": power_sweep.run,
        "netdc_sweep": netdc_sweep.run,
        "llmserve_sweep": llmserve_sweep.run,
        "storage_sweep": storage_sweep.run,
        "compaction_sweep": compaction_sweep.run,
    }
    try:
        from . import dryrun_report
        suites["dryrun_report"] = dryrun_report.run
    except ImportError:
        pass

    chosen = [s.strip() for s in args.only.split(",") if s.strip()] or list(suites)
    print("name,us_per_call,derived")
    t0 = time.perf_counter()
    for name in chosen:
        if name not in suites:
            print(f"# unknown benchmark: {name}", file=sys.stderr)
            continue
        print(f"# --- {name} ---")
        kw = {"quick": args.quick}
        if "lanes" in inspect.signature(suites[name]).parameters:
            kw["lanes"] = args.lanes
        suites[name](**kw)
    print(f"# total benchmark time: {time.perf_counter() - t0:.1f}s")


if __name__ == '__main__':
    main()
