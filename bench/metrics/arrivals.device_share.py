"""arrivals.device_share: per cent of the traced window's busy device
time in the `tick.arrivals` scope: the open-loop request injection."""

from bench import program_trace


def read(run):
    return program_trace.scope_share(run, "tick.arrivals")
