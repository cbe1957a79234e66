#!/usr/bin/env python3
"""Record a short traced window of one cell as a trace fixture.

    python bench/record_trace.py --workload sensor500.apply_b64 --seed 1 \
        --seconds 0.3 --out bench/tests/data/sweep_scoped.events.json.gz

Sets the cell up as a run does, drives a window of `--seconds` under the
profiler and writes gzipped JSON that ``Events.from_json`` reads back,
with two keys more: ``scopes``, the phase of every op that ran
(``bench/scopes.py``), and ``caller``, every span of the host thread
that holds the ``bench.*`` spans (jaxlib's ``PjitFunction(...)``,
``ParseArguments``, ...).  Needs the chip(s) the cell asks for; nothing
is checked against the reference.
"""
import argparse
import glob
import gzip
import json
import os
import shutil
import sys
import tempfile

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from bench import devtrace, harness, scopes

    spec = harness.load_spec(args.workload)
    devices = harness.chips_for(int(spec.workload["chips"]))
    harness.use_compile_cache()
    cell = harness.build_cell(spec, devices)
    pool, entry, _ = harness.prepare(cell, args.seed)
    rng = np.random.default_rng(args.seed)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    with devtrace.record(trace_dir):
        window = harness.drive(entry, pool, cell.batch, args.seconds, 0, rng)
    harness.program_facts(cell, entry, pool[0])
    events = devtrace.Events.load(trace_dir, [d.id for d in devices])
    caller = caller_spans(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    ran = {n for ops in events.ops.values() for n, _, _ in ops}
    phases = {n: p for n, p in scopes.of_cell(cell).items() if n in ran}
    with gzip.open(args.out, "wt") as f:
        json.dump({"ops": events.ops, "host": events.host, "caller": caller,
                   "scopes": phases}, f)
    print(json.dumps({"workload": args.workload, "calls": window.calls,
                      "window_s": events.window_s(),
                      "busy_s": events.busy_s(),
                      "caller_events": len(caller),
                      "unscoped_share": scopes.phase_share(events, phases,
                                                           None),
                      "kernels": cell.facts["kernels"]}))
    return 0


def caller_spans(trace_dir):
    """[(name, start, end)] of every span on the host thread that holds
    the harness's ``bench.*`` spans, in seconds on the profiler's clock."""
    import jax

    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                     for e in line.events]
            if any(n.startswith("bench.") for n, _, _ in spans):
                return spans
    return []


if __name__ == "__main__":
    sys.exit(main())
