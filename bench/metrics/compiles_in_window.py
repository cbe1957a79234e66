"""Backend compiles (or loads from the compile cache) that ended inside
the measured window, from the compile events ``repro.obs`` keeps: 0 when
every shape was warmed up in set-up."""
from bench import counters


def read(run):
    return counters.compiles_between(run.window.start, run.window.end)
