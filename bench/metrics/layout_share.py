"""Device seconds in ops of the program's ``repro.layout`` phase (pads,
crops, partition-order gathers, the batch transposes around each
kernel) over all busy seconds in the traced window, averaged over the
cell's devices (``bench/scopes.py``: a fused op counts under its root's
phase).  None for a program without phases."""
from bench import scopes


def read(run):
    if run.events is None:
        return None
    phases = scopes.of_cell(run.cell)
    if not phases:
        return None
    return 100.0 * scopes.phase_share(run.events, phases, "repro.layout")
