"""device.unscoped_share: per cent of the traced window's busy device
time in ops that no scope of `program_trace.SCOPES` encloses. With the
scope shares it sums to 100."""

from bench import program_trace


def read(run):
    return program_trace.scope_share(run, program_trace.UNSCOPED)
