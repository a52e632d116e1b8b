"""Share of the traced window a chip spent in collective operations on
its op line, where nothing else runs (mean over the chips)."""


def read(run):
    t = run.trace
    if not t or t["chips"] < 2:
        return None
    return 100.0 * t["collective_exposed_s"] / t["window_s"]
