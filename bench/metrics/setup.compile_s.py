"""setup.compile_s: host seconds of the warm call that loads (or, on a
checkout's first run, compiles) the programs the window drives."""


def read(run):
    return run.compile_s
