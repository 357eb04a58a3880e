"""famine.device_share: per cent of the traced window's busy device time
in the `stepper.famine` scope: the famine horizon, the victim draws, the
replay scan and its trailing next-event horizon."""

from bench import program_trace


def read(run):
    return program_trace.scope_share(run, "stepper.famine")
