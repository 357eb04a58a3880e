"""preshed.device_share: per cent of the traced window's busy device time
in the `tick.preshed` scope: the warn-window and death-tick transplants."""

from bench import program_trace


def read(run):
    return program_trace.scope_share(run, "tick.preshed")
