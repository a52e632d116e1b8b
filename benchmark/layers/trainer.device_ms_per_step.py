"""Milliseconds a step during which some operation ran on the device
(union of busy intervals, mean over the chips), from the first traced
step's start to the last one's end."""


def read(run):
    t = run.trace
    if not t or not t["steps"]:
        return None
    return 1e3 * t["step_busy_s"] / t["steps"]
