#!/usr/bin/env python3
"""Find a serving cell's knee: offer its traffic at several fixed rates,
one deployment built once, and print what each rate left behind.

    python3 chipbench/sweep.py --workload <cell> --seed <n> \
        --seconds <s> --rates 50,100,200

For each rate: requests offered and delivered within the window, the
backlog (due but not delivered) at half and at the end of the window,
and p50/p95 latency over all requests.  The knee is the highest rate at
which deliveries keep pace and the backlog does not grow; the cell file
records 0.8 x the knee as a number.  This is a tool for writing a cell,
not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
from pathlib import Path

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR.parent))
sys.path.insert(0, str(BENCH_DIR.parent / "src"))


class _NoTrace:
    t_start = t_stop = float("nan")

    def tick(self, now):
        pass


def main(argv=None) -> int:
    from chipbench import run as R
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    import jax
    spec = R.load_cell(args.workload)
    R.require_chips(int(spec["workload"]["chips"]))
    R.enable_cache()
    system = importlib.import_module(
        f"chipbench.systems.{spec['cell']['system']}")
    gen = importlib.import_module(
        f"chipbench.traffic.{spec['traffic']['generator']}")
    sut = system.System(spec["config"], spec["cell"], spec["traffic"],
                        args.seed)
    gen.prepare(sut, spec, args.seed, args.seconds)
    for rate in (float(r) for r in args.rates.split(",")):
        spec["cell"] = {**spec["cell"], "rate_per_s": rate}
        t0 = time.perf_counter()
        win = gen.drive(sut, spec, args.seed, args.seconds, _NoTrace(),
                           jax.profiler.TraceAnnotation)
        e2e = gen.end_to_end(win)
        due, got = win.sched["due"], win.delivered
        got = np.where(np.isnan(got), np.inf, got)
        T = args.seconds

        def backlog(t):
            return int(np.sum(due <= t) - np.sum(got <= t))
        rows = int(np.sum(win.sched["rows"]))
        print(json.dumps({
            "rate_per_s": rate, "offered": len(due),
            "delivered_in_window": int(np.sum(got <= T)),
            "backlog_half": backlog(T / 2), "backlog_end": backlog(T),
            "p50_ms": e2e["p50_latency_ms"],
            "p95_ms": e2e["notes"]["p95_latency_ms"],
            "failed": e2e["failed"], "rows_per_s_offered": rows / T,
            "late_p95_ms": e2e["notes"]["generator_late_p95_ms"],
            "wall_s": time.perf_counter() - t0}), flush=True)
    sut.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
