"""What stalls cost the rate: 1 - records_per_s / (median reading of the
window's whole-task slices), of this run's own window.  A stall that hits
one slice in ten moves the rate and not the median.  Nothing in a window
of too few slices."""


def read(run):
    return run.window["stall_share"] if run.window else None
