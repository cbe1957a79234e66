"""Share of the traced window in which no operation runs on the device,
averaged over the cell's devices."""


def read(run):
    if run.events is None:
        return None
    return 100.0 * run.events.idle_share()
