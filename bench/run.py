#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip(s) of this machine.

    python bench/run.py --workload sensor1m.apply_b64 --seed 7 \
        --seconds 10 --trace 0

The cell, its configuration, traffic, metrics and limits are found by
name from ``BENCHMARK.json`` (see ``bench/harness.py``).  Plan facts go
to standard output on ``#`` lines; the last line is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1``, ``breakdown``), and ``checks`` last: each number compared
with its limit, which also close standard error.  Without a TPU, or with
fewer chips than the cell asks for, it exits 3 and prints no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), t0=T0)
    except harness.NoChip as exc:
        print(f"bench/run.py: {exc}; refusing to run", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
