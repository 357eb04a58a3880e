"""stepper.ms_per_event: host milliseconds of the window per loop
iteration of the stepper. A sweep's answer counts the iterations of its
longest point, which is how often the loop that steps every point
together runs: milliseconds per vmapped iteration."""


def read(run):
    events = sum(a.events for a in run.answers)
    return 1e3 * run.window_s / events if events > 0 else None
