"""leap.device_share: per cent of the traced window's busy device time
in the `stepper.leap` scope: the next-event horizon and the leap."""

from bench import program_trace


def read(run):
    return program_trace.scope_share(run, "stepper.leap")
