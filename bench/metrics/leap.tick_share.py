"""leap.tick_share: per cent of the simulated ticks that the leap skipped
(leap_ticks / ticks), from the `simulate` span's counters in the host-only
profile of the window's first answer. An exact count."""

from bench import program_trace


def read(run):
    return program_trace.host_metric(run, "leap.tick_share")
