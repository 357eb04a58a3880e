"""linkstate.device_share: per cent of the traced window's busy device
time in the `tick.linkstate` scope: the epoch view and masked radii at the
head of the tick."""

from bench import program_trace


def read(run):
    return program_trace.scope_share(run, "tick.linkstate")
