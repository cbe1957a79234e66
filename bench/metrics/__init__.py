"""One reader per metric, found by the metric's name in BENCHMARK.json.

``read(run)`` takes a :class:`bench.harness.Run` and returns the value,
or None where the run holds nothing to read it from (a per-layer metric
in a run without a trace, a collective share on one chip).
"""
