"""Peak bytes in use on the fullest chip, from the workers' end-of-run
lines, in GB (1e9)."""


def read(run):
    peak = run.memory_peak_bytes()
    return peak / 1e9 if peak else None
