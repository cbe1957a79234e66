"""The least time one apply could take (``bench/work.py``: its bytes over
HBM bandwidth or its operations over peak rate, whichever is larger, on
the cell's chips) over the device's busy time per call in the trace."""
from bench import work


def read(run):
    if run.events is None:
        return None
    cell = run.cell
    least = work.least_seconds(cell.graph.n, cell.graph.nnz, cell.batch,
                               cell.operator["K"], cell.facts["eta"],
                               len(cell.devices), run.device_kind)
    busy_per_call = run.events.busy_s() / run.window.calls
    return 100.0 * least / busy_per_call
