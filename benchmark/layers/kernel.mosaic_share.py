"""Share of the device's busy time spent in Pallas (Mosaic) custom
calls."""


def read(run):
    t = run.trace
    if not t or not t["busy_s"]:
        return None
    return 100.0 * t["mosaic_s"] / t["busy_s"]
