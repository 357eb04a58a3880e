"""commit.device_share: per cent of the traced window's busy device time
in the `tick.commit` scope: the tick's deque commit (`deque_apply`)."""

from bench import program_trace


def read(run):
    return program_trace.scope_share(run, "tick.commit")
