"""setup.tables_s: host seconds of the deployment's table builds
(`linkstate.build_tables`, `arrivals.device_tables`); absent where the cell
builds none."""


def read(run):
    return run.tables_s
