"""Device seconds in ops of the program's ``repro.reorder`` phase (a
sharded plan's signals moved between vertex order and partition order,
shard to shard) over all busy seconds in the traced window, averaged
over the cell's devices.  None for a program without that phase."""
from bench import scopes

PHASE = "repro.reorder"


def read(run):
    if run.events is None:
        return None
    phases = scopes.of_cell(run.cell)
    if PHASE not in phases.values():
        return None
    return 100.0 * scopes.phase_share(run.events, phases, PHASE)
