"""Exposed device seconds of the boundary exchange's permutes: seconds in
which a collective-permute op of the program's ``repro.exchange`` phase
runs on a device and no other op does (the wait for boundary tiles from
the neighbouring chips that nothing hides), over all busy seconds in the
traced window, averaged over the cell's devices (``bench/scopes.py`` maps
each op to its phase).  The phase's packing, unpacking and coupling ops
are compute and are not counted.  None for a program without phases or
without an exchange."""
from bench import scopes
from bench.devtrace import is_collective_permute

PHASE = "repro.exchange"


def read(run):
    if run.events is None:
        return None
    phases = scopes.of_cell(run.cell)
    if PHASE not in phases.values():
        return None
    ev = run.events

    def permute(name):
        return phases.get(name) == PHASE and is_collective_permute(name)

    exposed = sum(ev.exposed(d, permute) for d in ev.ops) / len(ev.ops)
    return 100.0 * exposed / ev.busy_s()
