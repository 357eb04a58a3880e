"""stepper.ticks_per_event: simulated ticks per loop iteration of the
stepper (the leap factor), over every answer in the window. An exact
count. For a sweep, the ticks of every grid point over the iterations of
the loop that steps them together, which are its longest point's."""


def read(run):
    ticks = sum(a.ticks for a in run.answers)
    events = sum(a.events for a in run.answers)
    return ticks / events if events > 0 else None
