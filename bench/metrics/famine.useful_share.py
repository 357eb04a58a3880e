"""famine.useful_share: per cent of the famine replay's scan steps that
replayed a tick: famine_ticks / (famine_batch x famine_replays), from the
`simulate` span's counters in the host-only profile of the window's first
answer. Absent when no replay ran."""

from bench import program_trace


def read(run):
    return program_trace.host_metric(run, "famine.useful_share")
