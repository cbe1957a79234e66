#!/usr/bin/env python3
"""The readings a cell's limit is set from, in one process.

    python bench/calibrate.py --workload sensor1m.apply_b64 \
        --seeds 101 102 ... --control-seeds 201 202 203 --seconds 3 \
        [--program-control '{"sweep_dtype": "bf16"}']

The plan is built once.  For every seed of ``--seeds`` the program runs
a short window of the cell's own traffic and its sampled results are
compared with the reference, exactly as a run compares them: the worst
of these readings is the limit's lower reading.  Then the control runs
on ``--control-seeds``: the reference itself, at the MXU's "high"
(bf16_3x) precision, put in the program's place; and, where
``--program-control`` names the program's own lower-precision option,
the program with it switched on.  The least of the control's readings
is the upper one.  Benchmark runs never run this.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def control_entry(entry, cell):
    """The reference at "high" precision in the program's place."""
    from bench.reference import Reference

    op = cell.operator
    return Reference(cell.graph, op["K"], op["J"], cell.devices[0], "high")


def readings(cell, seeds, seconds, wrap=None):
    import numpy as np

    from bench import harness

    out = []
    for seed in seeds:
        pool, entry, keep = harness.prepare(cell, seed, wrap)
        rng = np.random.default_rng(np.random.SeedSequence(int(seed)))
        window = harness.drive(entry, pool, cell.batch, seconds, keep, rng)
        errs = harness.check(cell.spec, cell.graph, window.samples, pool,
                             cell.batch, cell.devices[0])
        out.append(max(errs))
        harness.log(f"seed {seed}: {window.calls} calls, max_rel_err "
                    f"{out[-1]!r}")
        del pool, entry, window
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--program-control", type=json.loads, default=None)
    args = ap.parse_args(argv)

    import jax

    from bench import harness

    spec = harness.load_spec(args.workload)
    devices = harness.chips_for(int(spec.workload["chips"]))
    harness.use_compile_cache()
    cell = harness.build_cell(spec, devices)
    found = {"workload": args.workload,
             "sound": readings(cell, args.seeds, args.seconds),
             "control_high": readings(cell, args.control_seeds,
                                      args.seconds, control_entry)}
    if args.program_control:
        del cell
        jax.clear_caches()
        cell = harness.build_cell(spec, devices, args.program_control)
        found["control_program"] = {
            "options": args.program_control,
            "readings": readings(cell, args.control_seeds, args.seconds)}
    print(json.dumps(found), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
