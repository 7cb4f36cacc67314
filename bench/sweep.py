#!/usr/bin/env python3
"""Find the highest rate an open-loop search cell sustains: one set-up,
then a window at each offered rate, printing latency percentiles and how
far the queue fell behind.

    python bench/sweep.py --config simgnn_aids --traffic search_2stage --seed 1 --seconds 8 --rates 100,200,400,800

This is how an open-loop cell's fixed `rate_qps` is chosen (about 0.8 of
the knee); the benchmark's own runs never call it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(os.path.dirname(HERE), "src")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [HERE, SRC]

    import numpy as np

    from harness import spec
    from harness.counters import CompileCounter
    from harness.device import describe
    from harness.drivers.search import Driver
    from harness.runner import enable_compile_cache

    cell = spec.make_cell("sweep", args.config, args.traffic)
    print(json.dumps(describe(cell.chips)), flush=True)
    enable_compile_cache()
    compiles = CompileCounter()
    rates = [float(r) for r in args.rates.split(",")]
    driver = Driver(cell, args.seed, rate_qps=rates[0])
    driver.setup(compiles)
    for rate in rates:
        driver.rate = rate
        before = compiles.lowered
        driver.window(args.seconds)
        lat = driver.latencies_ms()
        late = [(q.sent - q.due) * 1e3 for q in driver.queries]
        print(json.dumps({
            "rate_qps": rate, "queries": len(lat),
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "p99_ms": float(np.percentile(lat, 99)),
            "late_p95_ms": float(np.percentile(late, 95)),
            "late_max_ms": float(np.max(late)),
            "overrun_s": driver.window_s - args.seconds,
            "mean_flush": float(np.mean(driver.flush_sizes)),
            "compiles": compiles.lowered - before}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
