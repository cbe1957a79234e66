"""Process start to window start: graph, partition, plan, input pool,
compile or cache load, warm-up calls."""


def read(run):
    return run.setup_s
