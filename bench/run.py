#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on this machine's chips.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (JAX start, weights made on the device from the seed, compile or
cache load, warm-up of the cell's shapes) is timed as ``setup_s``; then
the cell's driver measures for ``--seconds``, checks what the timed path
produced against the plain references under ``bench/reference/``, and
the last line of standard output is one JSON object.  ``--trace 1``
reports the per-layer metrics, read from a profiler trace of part of the
window, instead of the end-to-end ones.  Without an accelerator, or with
fewer chips than the cell asks for, it exits non-zero and prints no
result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402


def log(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T_START:8.2f}s] {msg}",
          file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    harness.prepare_process()
    cell = harness.resolve(harness.load_spec(), args.workload)
    try:
        devices = harness.check_devices(cell.chips)
    except harness.NoChip as err:
        print(f"bench: {err}", file=sys.stderr)
        return 2
    dev = devices[0]
    log(f"{len(devices)} x {dev.device_kind} ({dev.platform}); compile "
        f"cache {harness.enable_compile_cache()}")
    run = harness.Run(cell=cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), t_start=T_START,
                      devices=devices, peaks=harness.peaks_for(
                          dev.device_kind), log=log)
    outcome = cell.driver.run(run)
    line = harness.result_line(cell, run, outcome, devices)
    harness.print_result(line, outcome.checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
