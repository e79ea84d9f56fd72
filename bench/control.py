#!/usr/bin/env python3
"""Readings that set a cell's limit, in one process on the chip.

    python3 bench/control.py --workload <cell> --seeds 12 --control-seeds 3

For each seed: one measured window at the cell's own load and length,
then ``max_rel_gap`` of the program's lanes against the plain reference
(the sound run's reading) and, on the first ``--control-seeds`` seeds, of
the reference computed in float32 put in the program's place (the
control's reading).  The limit in the configuration lies between the
largest sound reading and the smallest control reading.  The benchmark's
own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 7001)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)

    from bench import harness, spec
    cell = spec.load_cell(args.workload)
    seconds = args.seconds or json.loads(
        (spec.ROOT / "BENCHMARK.json").read_text())["run_seconds"]
    try:
        harness.devices_for(cell.chips, True)
    except harness.NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    harness.use_compile_cache(spec.ROOT)
    sys.path.insert(0, str(spec.ROOT / "src"))
    counter = harness.Counter()
    sound, ctl = [], []
    for i in range(args.seeds):
        seed = args.first_seed + i
        run = harness.Cellrun(cell, seed)
        if i == 0:
            for index in range(harness.WARM_SWEEPS):
                run.sweep(run.cells(index))
        win = harness.measure(run, seconds, counter)
        gap, gaps, lanes = harness.compare(run, win)
        row = {"seed": seed, "sweeps": len(win.durations),
               "failed": win.failed, "lanes": lanes, "program": gap,
               "worst": max(gaps, key=gaps.get) if gaps else None}
        if i < args.control_seeds:
            cgap, cgaps, _ = harness.compare(run, win, control=True)
            row.update(control=cgap, control_worst=max(cgaps, key=cgaps.get))
            ctl.append(cgap)
        sound.append(gap)
        print(json.dumps(row), flush=True)
    print(json.dumps({"workload": args.workload, "lower": max(sound),
                      "upper": min(ctl) if ctl else None,
                      "limit": cell.config["check"]["limits"]["max_rel_gap"],
                      "seconds": time.perf_counter() - T_START}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
