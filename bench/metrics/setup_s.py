"""setup_s: host seconds from the process's start to the first answer's
start (interpreter and TPU start, table builds, program loads, warm call)."""


def read(run):
    return run.setup_s
