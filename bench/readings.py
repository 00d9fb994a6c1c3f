#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, in one process.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 1,2,3 --seconds 5 [--fault <name>] [--out <dir>]

For each seed it sets the cell up, runs a short window at the cell's own
load, and prints the numbers the run's check compares (the program's
readings); for each control seed it also prints the same numbers with the
driver's control in the program's place (the reference one precision
lower, or a broken guarantee where the cell states no precision). With
``--fault`` every seed runs with that fault of ``faults.py`` planted
under the timed path, for the upper readings of a limit. The benchmark's
own runs never run the control or a fault. Same chip rules as
``run.py``: no TPU, no readings.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run as harness_run  # noqa: E402
from harness import Run, measure_window  # noqa: E402


def readings(workload: str, seeds: list[int], control_seeds: set[int],
             seconds: float, fault: str = "") -> list[dict]:
    manifest = harness_run.load_json(harness_run.ROOT / "BENCHMARK.json", "manifest")
    cell, config, mix, driver = harness_run.resolve(manifest, workload)
    sys.path.insert(0, str(harness_run.ROOT / "src"))
    peaks = harness_run.require_chip(cell)
    harness_run.use_compile_cache()
    if fault:
        import faults

        getattr(faults, fault)(setattr)
    out = []
    for seed in seeds:
        t0 = time.perf_counter()
        run = Run(cell=cell, config=config, mix=mix, seed=seed, seconds=seconds,
                  trace=False, t_start=t0, peaks=peaks)
        run.state = driver.setup(run)
        run.counters = {}
        run.window_s, _ = measure_window(lambda: driver.step(run), seconds)
        row = {"workload": workload, "seed": seed, "fault": fault,
               "attempted": int(run.counters.get("attempted", 0))}
        if seed in control_seeds:
            checks, failed = driver.control(run)
            row["control"] = {n: v for n, v, _, _ in checks}
            row["control_failed"] = failed
        driver.release(run)
        gc.collect()
        checks, failed = driver.check(run)
        row["program"] = {n: v for n, v, _, _ in checks}
        row["failed"] = failed
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
        out.append(row)
        del run
        gc.collect()
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--fault", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    rows = readings(args.workload, seeds, control, args.seconds, args.fault)
    if args.out:
        path = Path(args.out)
        path.mkdir(parents=True, exist_ok=True)
        with open(path / f"{args.workload}.jsonl", "a") as f:
            for row in rows:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
