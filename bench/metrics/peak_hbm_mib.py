"""peak_hbm_mib: the device allocator's `peak_bytes_in_use` after the
window, the largest over the cell's chips, in MiB; read before the
reference runs."""


def read(run):
    return run.peak_bytes / 2**20 if run.peak_bytes > 0 else None
