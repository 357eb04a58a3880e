"""tick.device_share: per cent of the traced window's busy device time
in the `stepper.tick` scope (the tick body), outside its `tick.*` child
scopes."""

from bench import program_trace


def read(run):
    return program_trace.scope_share(run, "stepper.tick")
