"""Seconds the program spent tracing, lowering and compiling (or loading
from the compile cache) before the window opened, from the compile
events ``repro.obs`` keeps."""
from bench import counters


def read(run):
    return counters.compile_seconds_before(run.window.start)
