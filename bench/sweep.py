#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell: the highest arrival rate
that the engine sustains without a growing backlog.

    python3 bench/sweep.py --workload <name> --seed <n> \\
        --rates 1.0,1.5,2.0 --hold 60

For each rate, in the order given, a fresh engine with the cell's
settings and weights takes the cell's mix at that rate for ``--hold``
seconds.  The backlog is the number of requests due but not yet in a
slot, sampled after every tick.  A rate is sustained when the backlog's
least-squares slope over the last two thirds of the hold is under
``--slope`` requests per second.  One JSON line per rate, then the knee:
the highest rate below which every rate was sustained.  Run it once,
when a cell is defined, and write the cell's fixed rate into its mix.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

from bench import harness, serving, traffic  # noqa: E402
from bench.run import log  # noqa: E402


def hold(run: harness.Run, rate: float, seconds: float) -> dict:
    """The cell's mix at ``rate`` for ``seconds`` on a fresh engine."""
    mix = run.cell.traffic
    cfg, _, engine = serving.make_engine(run)
    serving.warm(engine, 40, log)
    book = serving.Book(engine)
    n = int(rate * seconds * 1.5) + 64
    _, reqs = traffic.requests(mix, n, run.seed, cfg.vocab_size)
    due = traffic.arrivals(rate, n, traffic.rng_for(run.seed, 2))
    samples = []

    def tick():
        book.tick()
        samples.append((time.perf_counter() - t0,
                        sum(math.isnan(r.admitted) for r in book.reqs.values())))

    t0 = time.perf_counter()
    serving.open_loop(book, reqs, due, t0, lambda now: now >= t0 + seconds,
                      tick)
    t = np.array([s for s, _ in samples])
    backlog = np.array([b for _, b in samples], float)
    tail = t >= seconds / 3
    slope = float(np.polyfit(t[tail], backlog[tail], 1)[0])
    late = [r for r in book.reqs.values() if r.due - t0 >= seconds / 2]
    ttft = [(r.token_times[0] - r.due) * 1e3 if r.token_times else math.inf
            for r in late]
    tokens = sum(len(r.token_times) for r in book.reqs.values())
    finished = sum(r.status == "ok" for r in book.reqs.values())
    book.engine = engine = None
    gc.collect()
    return {"rate_per_s": rate, "hold_s": seconds, "ticks": len(samples),
            "tick_ms": 1e3 * seconds / max(1, len(samples)),
            "submitted": len(book.reqs), "finished": finished,
            "tokens_per_s": tokens / seconds,
            "backlog_end": int(backlog[-1]), "backlog_max": int(backlog.max()),
            "backlog_slope_per_s": slope,
            "ttft_p50_ms_late": serving.pct(ttft, 0.5),
            "ttft_p90_ms_late": serving.pct(ttft, 0.9)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    ap.add_argument("--hold", type=float, default=60.0)
    ap.add_argument("--slope", type=float, default=0.05)
    args = ap.parse_args(argv)

    harness.prepare_process()
    cell = harness.resolve(harness.load_spec(), args.workload)
    if cell.traffic["kind"] != "serve_open":
        print(f"sweep: {args.workload} is not an open-loop cell",
              file=sys.stderr)
        return 2
    try:
        devices = harness.check_devices(cell.chips)
    except harness.NoChip as err:
        print(f"sweep: {err}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    run = harness.Run(cell=cell, seed=args.seed, seconds=args.hold,
                      trace=False, t_start=T_START, devices=devices,
                      peaks=harness.peaks_for(devices[0].device_kind), log=log)
    knee, below = None, True
    for rate in (float(r) for r in args.rates.split(",")):
        row = hold(run, rate, args.hold)
        row["sustained"] = row["backlog_slope_per_s"] < args.slope
        below &= row["sustained"]
        if below:
            knee = rate
        print(json.dumps(row), flush=True)
    print(json.dumps({"workload": args.workload, "knee_per_s": knee}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
