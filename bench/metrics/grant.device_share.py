"""grant.device_share: per cent of the traced window's busy device time
in the `tick.grant` scope: grant resolution and the grant export
(`steal_compact`)."""

from bench import program_trace


def read(run):
    return program_trace.scope_share(run, "tick.grant")
