"""simulate.host_share: per cent of one answer's `simulate` span (a
sweep's: `simulate_sweep`) outside its `simulate.wait` child: the host
time in which the device loop is not running, from the host-only profile
of the window's first answer."""

from bench import program_trace


def read(run):
    return program_trace.host_metric(run, "simulate.host_share")
