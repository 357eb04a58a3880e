"""recorder.device_share: per cent of the traced window's busy device
time in the `tick.recorder` scope: the tick body's flight-recorder emission
and time-series add."""

from bench import program_trace


def read(run):
    return program_trace.scope_share(run, "tick.recorder")
