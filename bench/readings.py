#!/usr/bin/env python3
"""Read the numbers that ``correct`` compares, over many seeds of one
cell, in one process: the readings each limit under ``bench/limits`` is
set from.

    python3 bench/readings.py --workload <name> --seeds 1,2,3 \\
        --seconds <s> [--control 1] [--fault half_batch]

Each seed drives a whole run of the cell through its driver, as
``bench/run.py`` does, and prints one JSON line with the program's
numbers and, with ``--control 1``, the float8 control's numbers on the
same inputs.  ``--fault`` plants one of the drivers' faults in the timed
path.  The last line sums up: per number, the largest that the program
read (the lower reading) and the smallest that the control read (the
upper).  The benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402
from bench.run import log  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", action="append", default=[])
    args = ap.parse_args(argv)

    harness.prepare_process()
    cell = harness.resolve(harness.load_spec(), args.workload)
    try:
        devices = harness.check_devices(cell.chips)
    except harness.NoChip as err:
        print(f"readings: {err}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    peaks = harness.peaks_for(devices[0].device_kind)
    program, control = {}, {}
    for seed in (int(s) for s in args.seeds.split(",")):
        run = harness.Run(cell=cell, seed=seed, seconds=args.seconds,
                          trace=False, t_start=time.perf_counter(),
                          devices=devices, peaks=peaks, log=log,
                          faults=frozenset(args.fault),
                          control=bool(args.control))
        out = cell.driver.run(run)
        print(json.dumps({"seed": seed, "faults": args.fault,
                          "checks": out.checks, "control": out.control,
                          "attempted": out.attempted, "failed": out.failed}),
              flush=True)
        for name, v in out.checks.items():
            program[name] = max(program.get(name, v), v)
        for name, v in out.control.items():
            control[name] = min(control.get(name, v), v)
        del out, run
        gc.collect()
    print(json.dumps({"workload": args.workload, "faults": args.fault,
                      "program_max": program, "control_min": control}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
