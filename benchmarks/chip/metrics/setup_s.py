"""Seconds from the process's start to the window's: imports, data, the
serving stack, loading or compiling every program the cell reaches, and one
request of each kind through the whole stack."""


def read(run):
    return run.setup_s
