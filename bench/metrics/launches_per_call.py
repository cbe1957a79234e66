"""Device operations (Pallas kernels and XLA ops) that start in the
traced window, per device, over the calls completed in it."""


def read(run):
    if run.events is None:
        return None
    return run.events.op_count() / run.window.calls
