"""Stored non-zeros of the plan's Block-ELL structure over the entries
it holds (``plan.info["blockell_fill"]``): the share of the structure's
bytes an SpMV streams that carry a matrix entry."""


def read(run):
    fill = run.cell.plan.info.get("blockell_fill")
    return None if fill is None else 100.0 * fill
