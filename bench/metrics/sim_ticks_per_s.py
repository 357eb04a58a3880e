"""sim_ticks_per_s: simulated ticks of every answer in the window over the
host seconds from the first answer's start to the last answer's end,
results fetched. A sweep's answer counts the ticks of all its grid
points, so the rate is grid points × ticks per second."""


def read(run):
    ticks = sum(a.ticks for a in run.answers)
    return ticks / run.window_s if run.window_s > 0 else None
