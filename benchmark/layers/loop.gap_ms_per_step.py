"""Milliseconds a step during which no operation ran on the device, from
the first traced step's start to the last one's end."""


def read(run):
    t = run.trace
    if not t or not t["steps"]:
        return None
    return 1e3 * (t["step_range_s"] - t["step_busy_s"]) / t["steps"]
