#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload aids_score_b1000 --seed 7 --seconds 10 --trace 0

The cell, its configuration, traffic mix, limits and metrics are found by
name from `BENCHMARK.json` (see `bench/harness/spec.py`). Set-up builds
the weights and inputs from `--seed` and warms every shape; the window
then runs for `--seconds`; afterwards the answers are compared with the
plain float32 reference. `--trace 1` profiles the window and reports the
per-layer metrics; `--trace 0` reports the end-to-end ones.

`--config NAME` runs the cell with another configuration of
`bench/configs/` in place of its own, at the cell's traffic and limits; it
is how a configuration that the program cannot yet serve correctly is
read (PERF.md), and the benchmark's own runs never pass it.

`--control high` puts the reference, run at the three-pass `high`
matmul precision, in the program's place; it is how the correctness limits
were shown to catch a lower precision, and the benchmark's own runs never
pass it.

Exits 3 without a result when JAX finds no TPU or fewer chips than the
cell asks for, and 2 when the program is not beside the benchmark.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SRC = os.path.join(ROOT, "src")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None, choices=("high",))
    ap.add_argument("--config", default=None)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"bench: no program under {SRC}", file=sys.stderr)
        return 2
    sys.path[:0] = [HERE, SRC]

    from harness import spec
    from harness.device import NoDevice
    from harness.runner import run

    cell = spec.load_cell(args.workload)
    if args.config:
        cell = spec.with_config(cell, args.config)
    try:
        result = run(cell, args.seed, args.seconds, bool(args.trace),
                     t_start=T_START, control=args.control)
    except NoDevice as exc:
        print(f"bench: {exc}; nothing was run", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
