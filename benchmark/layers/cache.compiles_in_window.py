"""New entries in the persistent compile cache between the window's
opening and its end.  Must be 0 in a throughput cell."""


def read(run):
    return float(run.compiles_in_window)
